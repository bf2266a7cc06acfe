"""Pipeline-parallel forward for Llama — wires models/llama.py into
parallel/pipeline.py (VERDICT r1 next-#5: PP as a capability, not a demo).

The reference has no pipeline parallelism (SURVEY.md §2: PP "unknown — no
evidence"), so this is capability beyond the contract, built the TPU way:
the ``nn.scan``-stacked decoder weights [L, ...] regroup into [P, L/P, ...]
stages (a pure reshape — no model rewrite), the embed/head run replicated
over the ``pipe`` axis (they are a few % of FLOPs; dedicating stages to them
would only deepen the bubble), and the GPipe ring of
:func:`..parallel.pipeline.pipeline` carries the decoder trunk.

No flax refactor: the embedding/norm/head submodules are re-instantiated
standalone with the SAME constructor arguments the full model uses and
applied to the corresponding parameter subtrees, so the math — dtype
promotion included — is the model's own code, and the parameter tree remains
byte-compatible with non-PP checkpoints (PP is a runtime layout choice, not
a model variant).

Limitations (asserted): ``scan_layers=True``, ``num_layers % pipe == 0``,
no ``attention_mask`` (causal-LM packing handles padding via ``loss_mask``,
as the config-5 fine-tune does).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh

from distributeddeeplearningspark_tpu.models.llama import (
    DecoderLayer,
    LlamaConfig,
    RMSNorm,
)
from distributeddeeplearningspark_tpu.parallel.mesh import AXIS_PIPE
from distributeddeeplearningspark_tpu.parallel.pipeline import pipeline, stack_stages


def check_pp_config(cfg: LlamaConfig, p: int) -> None:
    """The shared pipeline-compatibility ladder (single-program GPipe and
    the MPMD multi-gang trainer enforce the same contract)."""
    if not cfg.scan_layers:
        raise ValueError("pipeline parallelism requires scan_layers=True "
                         "(stacked [L, ...] params are what stages reshape)")
    if cfg.moe_experts:
        raise NotImplementedError(
            "MoE is not wired through pipeline parallelism: the stage "
            "forward discards each layer's load-balance aux loss, so the "
            "router would silently collapse (no balancing gradient) — use "
            "the data×expert(+fsdp/tensor) layout for MoE models")
    if cfg.fused_head_loss:
        raise ValueError(
            "fused_head_loss is not supported with pipeline parallelism: "
            "the GPipe forward emits real logits — pair PP with "
            "losses.causal_lm (or drop the config flag)")
    if cfg.num_layers % p:
        raise ValueError(f"num_layers {cfg.num_layers} must divide by pipe {p}")


def build_stage_modules(cfg: LlamaConfig, stage_len: int):
    """(stage_mod, embed_mod, norm_mod, head_mod) — the EXACT module stack
    both pipeline implementations run, factored so the MPMD per-gang stage
    program (train/pipeline_trainer.py) computes bit-for-bit the same math
    as this module's single-program GPipe ring."""
    layer_cls = DecoderLayer
    if cfg.scan_param_barrier:
        # same whole-stack relayout hazard as the non-PP scan (see
        # LlamaConfig.scan_param_barrier): each stage's [L/P, ...] stacked
        # weights would otherwise grow hoisted fwd+bwd layout copies.
        # Ordering as in llama.py: inside the remat region, or the barrier
        # outputs become per-layer saved residuals.
        layer_cls = nn.map_variables(
            layer_cls, "params",
            trans_in_fn=lambda tree: jax.tree.map(
                jax.lax.optimization_barrier, tree))
    if cfg.remat:
        layer_cls = nn.remat(layer_cls, prevent_cse=False)
    stage_mod = nn.scan(
        layer_cls,
        variable_axes={"params": 0},
        split_rngs={"params": True},
        in_axes=nn.broadcast,
        length=stage_len,
    )(cfg)
    embed_mod = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype)
    norm_mod = RMSNorm(cfg.rms_eps, cfg.dtype)
    head_mod = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype)
    return stage_mod, embed_mod, norm_mod, head_mod


def make_pp_apply(cfg: LlamaConfig, mesh: Mesh, num_microbatches: int | None = None):
    """Build an ``apply_fn(variables, batch, train=..., rngs=...)`` running
    the decoder trunk through P pipeline stages.

    Drop-in for ``model.apply`` in :func:`..train.step.make_train_step`; the
    parameter tree is the ordinary :class:`LlamaForCausalLM` one.
    """
    p = int(mesh.shape[AXIS_PIPE])
    if p < 2:
        raise ValueError(f"pipeline apply needs a pipe axis > 1 (mesh {dict(mesh.shape)})")
    check_pp_config(cfg, p)
    m = num_microbatches or p
    stage_len = cfg.num_layers // p
    stage_mod, embed_mod, norm_mod, head_mod = build_stage_modules(cfg, stage_len)

    def stage_fn(stage_params: Any, act):
        out, _ = stage_mod.apply({"params": stage_params}, act, None, None)
        return out

    def apply_fn(variables, batch, *, train: bool = False, rngs=None, mutable=None):
        del train, rngs, mutable  # no dropout/BN in Llama-2
        params = variables["params"]
        if batch.get("attention_mask") is not None:
            raise NotImplementedError(
                "pipeline-parallel Llama supports causal packing only; "
                "handle padding via loss_mask (as config 5 does)")
        if batch.get("segment_ids") is not None:
            raise NotImplementedError(
                "pipeline-parallel Llama does not thread segment_ids to the "
                "stage forwards — packed batches would silently attend "
                "across documents; drop segment_ids (GPT-style packing) or "
                "use a non-PP layout")
        ids = batch["input_ids"]
        if ids.shape[1] > cfg.max_position:
            raise ValueError(
                f"sequence length {ids.shape[1]} exceeds max_position "
                f"{cfg.max_position}")
        x = embed_mod.apply({"params": params["token_embed"]}, ids)
        stage_params = stack_stages(params["layers"], p)
        x = pipeline(stage_fn, stage_params, x, mesh=mesh, num_microbatches=m)
        x = norm_mod.apply({"params": params["final_norm"]}, x)
        logits = head_mod.apply({"params": params["lm_head"]}, x)
        return logits.astype(jnp.float32)

    return apply_fn
