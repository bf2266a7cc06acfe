"""A decoder whose attention reads a learned selection of keys and whose
feed-forward is routed experts: grouped-query attention with per-head q/k
RMSNorm and an explicit head size, a sparse-attention indexer
(:mod:`..ops.indexed_attention`), and :class:`..models.moe.RoutedExperts` in
every layer. Norms, rotary embedding, the untied head, the layer scan with
``remat`` and the fused head loss are the Llama path's (:mod:`.llama`).

One block, ``x`` ``[B, S, hidden]``, ``h = RMSNorm(x)``::

    q, k, v  = h Wq, h Wk, h Wv            num_heads / num_kv_heads of head_dim
    q, k     = rotary(RMSNorm_head(q)), rotary(RMSNorm_head(k))
    u        = stop_gradient(h)
    qI, kI   = rotary(u WqI), rotary(LayerNorm(u WkI))   index_heads of
                                           index_head_dim, ONE key head
    w        = u Ww * index_heads^-0.5 * index_head_dim^-0.5
    o, L_I   = indexed_attention(q, k, v, qI, kI, w, topk=index_topk)
    x        = x + o Wo
    x        = x + RoutedExperts(RMSNorm(x))

The indexer reads the block's normed input DETACHED, and its loss ``L_I``
has no gradient on q, k, v: the language-model loss trains the main model,
``L_I`` trains ``WqI, WkI, Ww`` and the LayerNorm, and neither trains the
other's parameters. The model returns the fused-head dictionary of the
Llama path (``hidden``, ``lm_head``) with the two auxiliary losses, already
weighted, and the step's counters; :func:`..train.losses.sparse_moe_lm`
is its loss.

Batch: ``input_ids`` ``[B, S]`` int32, causal over the whole window (no
padding mask and no segment ids: a selection over a prefix cut into
documents would mostly have fewer keys than ``index_topk`` to choose from).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from distributeddeeplearningspark_tpu.models.llama import (
    RMSNorm,
    _LMHead,
    rotary_embedding,
)
from distributeddeeplearningspark_tpu.models.moe import RoutedExperts
from distributeddeeplearningspark_tpu.ops.attention import indexed_attention
from distributeddeeplearningspark_tpu.ops.indexed_attention import (
    ATTEND_NAME,
    SELECTION_NAME,
)
from distributeddeeplearningspark_tpu.parallel.sharding import ShardingRules

#: the counters a layer reports beside its two auxiliary losses; the model
#: outputs their means over the layers and ``losses.sparse_moe_lm`` carries
#: them into the step's metrics (docs/OBSERVABILITY.md)
COUNTERS = ("moe_load_max_over_mean", "moe_rows_held_share",
            "dsa_selected_share")


@dataclasses.dataclass(frozen=True)
class SparseDecoderConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    num_layers: int = 4
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128              # stated, not hidden_size // num_heads
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    max_position: int = 262144
    # routed experts (models/moe.py RoutedExperts)
    num_experts: int = 128           # the router's width
    experts_per_token: int = 8
    expert_size: int = 768
    experts_held: tuple[int, int] | None = None   # (first, count); None: all
    norm_topk_prob: bool = True
    router_aux_weight: float = 0.001
    # the sparse-attention indexer (ops/indexed_attention.py)
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    index_loss_weight: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def tiny(**kw) -> "SparseDecoderConfig":
        """2 layers, 64 wide, 8 experts of which 4 a token: CPU tests."""
        base = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                    num_kv_heads=2, head_dim=32, num_experts=8,
                    experts_per_token=4, expert_size=32, index_heads=4,
                    index_head_dim=16, index_topk=16, max_position=4096,
                    dtype=jnp.float32)
        base.update(kw)
        return SparseDecoderConfig(**base)


class SparseDecoderLayer(nn.Module):
    """Returns ``(x, stats)``, the (carry, out) pair ``nn.scan`` wants;
    ``stats`` holds ``moe_aux``, ``index_kl`` and :data:`COUNTERS`,
    unweighted scalars."""

    cfg: SparseDecoderConfig

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.cfg
        b, s, _ = x.shape
        dense = lambda feats, name, axis=-1: nn.DenseGeneral(
            feats, axis=axis, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        positions = jnp.arange(s)[None, :]
        rope = lambda t: rotary_embedding(t, positions, cfg.rope_theta)

        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="attention_norm")(x)
        q = dense((cfg.num_heads, cfg.head_dim), "wq")(h)
        k = dense((cfg.num_kv_heads, cfg.head_dim), "wk")(h)
        v = dense((cfg.num_kv_heads, cfg.head_dim), "wv")(h)
        q = rope(RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(q))
        k = rope(RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(k))

        u = jax.lax.stop_gradient(h)
        qi = rope(dense((cfg.index_heads, cfg.index_head_dim), "index_wq")(u))
        ki = nn.LayerNorm(epsilon=cfg.rms_eps, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name="index_k_norm")(
            dense(cfg.index_head_dim, "index_wk")(u))
        ki = rope(ki[:, :, None, :])[:, :, 0, :]
        wi = dense(cfg.index_heads, "index_w")(u).astype(jnp.float32) * (
            cfg.index_heads ** -0.5 * cfg.index_head_dim ** -0.5)

        o, kl, selected = indexed_attention(
            q, k, v, qi, ki, wi, topk=cfg.index_topk,
            scale=cfg.head_dim ** -0.5)
        x = x + dense(cfg.hidden_size, "wo", axis=(-2, -1))(o)

        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="mlp_norm")(x)
        y, moe = RoutedExperts(
            cfg.hidden_size, cfg.expert_size, cfg.num_experts,
            cfg.experts_per_token, held=cfg.experts_held,
            norm_topk=cfg.norm_topk_prob, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="moe")(h)
        stats = {
            "moe_aux": moe["aux"],
            "index_kl": jnp.mean(kl),
            "moe_load_max_over_mean": moe["load_max_over_mean"],
            "moe_rows_held_share": moe["rows_held_share"],
            "dsa_selected_share": jnp.sum(selected)
            / jnp.float32(b * s * (s + 1) // 2),
        }
        return x + y, stats


class SparseDecoderLM(nn.Module):
    """``{"hidden" [B, S, hidden], "lm_head" [hidden, vocab], "moe_aux",
    "index_kl"`` (each summed over layers and weighted), and the counters
    ``"moe_load_max_over_mean", "moe_rows_held_share", "dsa_selected_share"``
    (means over layers) ``}``."""

    cfg: SparseDecoderConfig

    @nn.compact
    def __call__(self, batch: dict[str, jax.Array], *, train: bool = False):
        del train  # no dropout, and the same outputs either way
        cfg = self.cfg
        ids = batch["input_ids"]
        if ids.shape[1] > cfg.max_position:
            raise ValueError(f"sequence length {ids.shape[1]} exceeds "
                             f"max_position {cfg.max_position}")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="token_embed")(ids)
        # remat keeps the selection (an int8 [B, S, S] mask a layer): the
        # replay must not select again (ops/indexed_attention.select_topk);
        # and the attention's output and log-sum-exp (bf16 [B, S, H, D]):
        # 0.27 GB a layer together at the published widths, for a sixth of
        # the attention kernels' time
        layer_cls = nn.remat(
            SparseDecoderLayer, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names(
                SELECTION_NAME, ATTEND_NAME))
        x, stats = nn.scan(
            layer_cls, variable_axes={"params": 0},
            split_rngs={"params": True}, length=cfg.num_layers,
        )(cfg, name="layers")(x)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="final_norm")(x)
        head = _LMHead(cfg.vocab_size, cfg.dtype, cfg.param_dtype,
                       name="lm_head")
        out = {"hidden": x, "lm_head": head(x, return_kernel=True)}
        out["moe_aux"] = cfg.router_aux_weight * jnp.sum(stats["moe_aux"])
        out["index_kl"] = cfg.index_loss_weight * jnp.sum(stats["index_kl"])
        for name in COUNTERS:
            out[name] = jnp.mean(stats[name])
        return out


def sparse_decoder_rules(cfg: SparseDecoderConfig, *, fsdp: bool = True,
                         fsdp_min_size: int = 2 ** 14) -> ShardingRules:
    """Batch-parallel layouts only: the vocabulary over ``tensor``, and
    auto-FSDP over the largest dim of what is left. The experts a module
    holds are ITS rank's; exchanging tokens between ranks over ``expert`` is
    not built (ROADMAP queue 2, A.1)."""
    del cfg
    rules = ((r"token_embed/embedding", P("tensor", None)),
             (r"lm_head/kernel", P(None, "tensor")))
    return ShardingRules(rules=rules, fsdp=fsdp, fsdp_min_size=fsdp_min_size)
