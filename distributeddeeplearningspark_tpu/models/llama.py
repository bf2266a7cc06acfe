"""Llama-2 decoder + LoRA — BASELINE.json config 5.

The reference fine-tunes Llama-2 7B with LoRA adapters, FSDP-style sharded
"across Spark executors" on a v4-32 (SURVEY.md §2 'Models: Llama-2 7B + LoRA').
Architecture per Touvron et al. 2023: pre-norm RMSNorm, rotary position
embeddings, SwiGLU MLP, untied LM head; 7B = 32 layers x 4096 hidden,
32 heads, 11008 intermediate. GQA (separate ``num_kv_heads``) is supported so
the 70B-family configs load too.

TPU-first decisions:

- ``nn.scan`` over the layer stack (default on): one traced layer instead of
  32 unrolled copies — compile time and HLO size stay O(1) in depth, and the
  stacked [L, ...] params give FSDP a large, evenly divisible leading dim.
- ``nn.remat`` per layer (default on): rematerialize activations in backward —
  the HBM-for-FLOPs trade that makes 7B training fit (SURVEY.md 'HBM').
- bf16 matmuls, f32 RMSNorm/softmax/rotary — the MXU mixed-precision recipe.
- LoRA lives inside :class:`LoRADenseGeneral`: base kernel frozen via
  ``optax`` masking (see :func:`lora_trainable`), adapters are the only
  trained params. Adapter matmuls are rank-r — tiny — so they ride along the
  main matmul without a fused kernel.
- No parallelism logic in model code: FSDP/TP layouts come from
  :func:`llama_rules` path-regex shardings (GSPMD inserts the collectives).

Batch dict: ``input_ids`` [B,S] i32, optional ``attention_mask`` [B,S] 1/0,
optional ``loss_mask`` (consumed by the loss, not the model). Returns logits
[B,S,vocab] f32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from distributeddeeplearningspark_tpu.ops.attention import (
    dot_product_attention,
    padding_mask,
)
from distributeddeeplearningspark_tpu.parallel.sharding import ShardingRules


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32          # < num_heads → grouped-query attention
    intermediate_size: int = 11008
    max_position: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # STORAGE dtype of the base weights (embed, attention/MLP kernels, LM
    # head). Default f32 — full-parameter training wants f32 masters, and HF
    # checkpoint interchange stays bit-faithful. Set "bfloat16" for frozen-
    # base LoRA fine-tuning: the base never takes an optimizer step, so f32
    # masters are pure waste — the r4 memval run measured f32 storage at
    # 25.2 GiB of arguments for the 7B (vs 12.6 analytic bf16), which alone
    # overflows a 16 GiB chip and doubles the v4-32 per-chip budget. LoRA
    # A/B adapters and RMSNorm scales stay f32 regardless (they train).
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    scan_layers: bool = True
    remat: bool = True
    # What the per-layer remat may keep instead of recomputing (names map to
    # jax.checkpoint_policies): None = save nothing (lowest memory, full
    # recompute); "dots" = dots_with_no_batch_dims_saveable — keep matmul
    # outputs so the backward pass skips recomputing the MXU-heavy ops and
    # only replays the cheap elementwise chain. Memory sits between remat-off
    # and full remat; the right default depends on whether the workload is
    # HBM-bound (7B FSDP: None) or compute-bound (sub-chip-sized: "dots").
    remat_policy: str | None = None
    # Fuse the LM-head matmul into the loss (train/fused_ce.py): the model
    # returns {"hidden", "lm_head"} instead of [B,S,V] f32 logits, so the
    # logits and their backward cotangent (~2×B·S·V f32 — 2.1 GB at the
    # config-5 bench shape) never materialize. Pair with
    # ``losses.causal_lm_fused``. Ignored in decode mode (generation needs
    # real logits).
    fused_head_loss: bool = False
    # Mixture-of-Experts FFN (models/moe.py RoutedExperts; 0 = dense
    # SwiGLU). When >0 every layer's MLP becomes a top-k-routed expert bank
    # (no capacity, nothing dropped) whose stacked kernels shard over the
    # `expert` mesh axis; the model returns {"logits", "moe_aux"} in
    # training so the load-balance loss reaches the optimizer
    # (losses.causal_lm/_fused add it).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_aux_weight: float = 0.01
    # QLoRA-style int8 base storage ("int8" | None). One step below bf16:
    # every frozen projection/FFN base kernel is stored int8 with a per-
    # output-channel f32 scale (absmax), dequantized INTO the matmul (the
    # int8→bf16 convert+multiply fuses as a dot-operand read, so HBM sees
    # ~1 byte/weight). Frozen-base LoRA only — the base never takes an
    # optimizer step, so storage precision is a pure memory/bandwidth
    # knob: 7B base drops 12.6 → ~6.3 GiB (b=2 headroom on a 16 GiB
    # chip; decode's per-token weight reads halve). Embeddings, LM head
    # and norm scales stay at param_dtype/f32 (QLoRA convention —
    # quantizing the embedding hurts quality for no meaningful bytes).
    # Requires lora_rank > 0; rejected with MoE (experts train).
    base_quant: str | None = None
    # LoRA (rank 0 = disabled → plain full-parameter model)
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: Sequence[str] = ("wq", "wv")
    # Autoregressive decoding (models/llama_gen.py): static-config switch so
    # the scanned-layer call signature never changes. decode=True gives each
    # attention a KV cache ("cache" collection) of max_cache_len positions;
    # every call appends its tokens at the cache index and attends over the
    # cached prefix. Equal-length prompts per batch (prefill writes [0, T)).
    decode: bool = False
    max_cache_len: int | None = None
    # Keep weight-relayout copies INSIDE the layer scan. XLA's layout
    # assignment gives the scan-stacked projection kernels one entry layout,
    # but the forward dot (contract hidden) and the backward dx dot
    # (contract heads·head_dim) each prefer a different one; XLA then
    # commutes copy(dynamic_slice(W_stacked)) → dynamic_slice(copy(W_stacked))
    # and hoists WHOLE-STACK relayout copies out of the loop. Measured on the
    # r4 chip window (7B, b=1, s=1024): three 1.0 GiB copies of the stacked
    # wq/wk/wv — 3.0 of the 3.79 GiB program HBM — overflowing a 16 GiB chip
    # by 0.7 GiB that the weights themselves fit. An optimization_barrier on
    # each SLICED param blocks the commutation, so the (same total bytes of)
    # relayout runs per-layer inside the loop: peak temp drops by ~2× the
    # stack size at the cost of re-running slice-relayouts in the remat
    # replay. Default on; set False to let XLA hoist when HBM is plentiful.
    scan_param_barrier: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        # Frozen-base LoRA fine-tunes default to bf16 base-weight STORAGE
        # (see param_dtype docstring: the r4 memval run measured f32 masters
        # at 25.2 GiB for the 7B — unfittable on a 16 GiB chip and double
        # the v4-32 budget, for weights that never take an optimizer step).
        # Full-parameter 7B keeps f32 masters.
        if kw.get("lora_rank") and "param_dtype" not in kw:
            kw["param_dtype"] = jnp.bfloat16
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        """Llama-2 13B geometry (MHA — 13B predates GQA): the pod-scale
        step-up of config 5. The analytic budget (utils/memory.py) places
        the LoRA fine-tune comfortably inside a v4-32 fsdp=8 layout
        (tests/test_memory.py::test_13b_count_and_v4_32_fsdp_layout_fits);
        delegates to llama2_7b so the LoRA-implies-bf16-storage policy
        lives in exactly one place."""
        base = dict(hidden_size=5120, num_layers=40, num_heads=40,
                    num_kv_heads=40, intermediate_size=13824)
        base.update(kw)
        return LlamaConfig.llama2_7b(**base)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """4-layer/128-wide config for CPU tests."""
        base = dict(vocab_size=512, hidden_size=128, num_layers=4, num_heads=4,
                    num_kv_heads=2, intermediate_size=256, max_position=128,
                    dtype=jnp.float32)
        base.update(kw)
        return LlamaConfig(**base)


def _remat_policy(name: str | None):
    """Map LlamaConfig.remat_policy to a jax.checkpoint policy (None = save
    nothing)."""
    if name is None:
        return None
    policies = {
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
    }
    if name not in policies:
        raise ValueError(
            f"unknown remat_policy {name!r}; use None, 'dots', or 'dots_saveable'")
    return policies[name]


def rotary_embedding(x: jax.Array, positions: jax.Array, theta: float, *,
                     interleaved: bool = False) -> jax.Array:
    """Apply RoPE to [B,S,H,D] in f32: half-split (rotate-half) convention,
    or with ``interleaved`` over ADJACENT pairs ``(2i, 2i+1)`` (DeepSeek's
    ``rope_interleave``), the result in the same adjacent layout."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq      # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]                              # [B,S,1,D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    """Llama RMSNorm: f32 accumulation, learned scale, no bias."""

    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        xf = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


class LoRADenseGeneral(nn.Module):
    """DenseGeneral with an optional rank-r LoRA delta: y = xW + (alpha/r)·xAB.

    ``rank == 0`` → exactly ``nn.DenseGeneral`` (no extra params), so the same
    model class serves pretraining and adapter fine-tuning; the base ``kernel``
    is frozen by the optimizer mask, never by the module. A and B are stored
    f32 (tiny) and named ``lora_a``/``lora_b`` — the path fragment both
    :func:`lora_trainable` and :func:`llama_rules` key on. B starts at zero so
    step 0 matches the base model (Hu et al. 2021).
    """

    features: int | Sequence[int]
    axis: int | Sequence[int] = -1
    rank: int = 0
    alpha: float = 16.0
    use_bias: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32  # base-kernel STORAGE; A/B stay f32
    base_quant: str | None = None   # "int8": kernel int8 + per-out-channel scale

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        axes = (self.axis,) if isinstance(self.axis, int) else tuple(self.axis)
        axes = tuple(a % x.ndim for a in axes)
        feats = (self.features,) if isinstance(self.features, int) else tuple(self.features)
        in_dim = math.prod(x.shape[a] for a in axes)
        batch_shape = tuple(s for i, s in enumerate(x.shape) if i not in axes)

        def fold(t: jax.Array) -> jax.Array:  # x → [batch..., in_dim]
            t = jnp.moveaxis(t, axes, range(t.ndim - len(axes), t.ndim))
            return t.reshape(batch_shape + (in_dim,)).astype(self.dtype)

        if self.base_quant == "int8":
            if self.use_bias:
                raise NotImplementedError("int8 base_quant has no bias path")
            # Deterministic shared init scale (≈clip at 4σ of lecun-normal)
            # keeps kernel/scale self-consistent under random init; real
            # use quantizes pretrained weights via
            # llama_io.quantize_base_int8 (per-channel absmax).
            q0 = 4.0 / math.sqrt(in_dim) / 127.0

            def qinit(key, shape, _dtype=jnp.int8):
                w = nn.initializers.lecun_normal()(
                    key, (shape[0], math.prod(shape[1:])), jnp.float32)
                return jnp.clip(jnp.round(w / q0), -127, 127).astype(
                    jnp.int8).reshape(shape)

            kernel_q = self.param("base_q8", qinit, (in_dim,) + feats)
            scale = self.param("base_scale",
                               lambda _k, shape: jnp.full(shape, q0, jnp.float32),
                               feats)
            # dequant rides the dot's operand read (convert+mul fuse into
            # the matmul on TPU): HBM traffic stays ~1 byte/weight
            w = kernel_q.astype(self.dtype) * scale.astype(self.dtype)
            y = fold(x) @ w.reshape(in_dim, math.prod(feats))
            y = y.reshape(batch_shape + feats)
        else:
            y = nn.DenseGeneral(self.features, axis=self.axis,
                                use_bias=self.use_bias, dtype=self.dtype,
                                param_dtype=self.param_dtype, name="base")(x)
        if self.rank:
            a_mat = self.param("lora_a", nn.initializers.he_uniform(), (in_dim, self.rank),
                               jnp.float32)
            b_mat = self.param("lora_b", nn.initializers.zeros,
                               (self.rank, math.prod(feats)), jnp.float32)
            delta = (fold(x) @ a_mat.astype(self.dtype)) @ b_mat.astype(self.dtype)
            delta = delta.reshape(batch_shape + feats) * (self.alpha / self.rank)
            y = y + delta.astype(y.dtype)
        return y


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array, mask: jax.Array | None,
                 segment_ids: jax.Array | None = None) -> jax.Array:
        cfg = self.cfg
        hd, nh, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads

        def proj(name, heads):
            rank = cfg.lora_rank if name in cfg.lora_targets else 0
            return LoRADenseGeneral((heads, hd), rank=rank, alpha=cfg.lora_alpha,
                                    dtype=cfg.dtype,
                                    param_dtype=cfg.param_dtype,
                                    base_quant=cfg.base_quant, name=name)

        q = proj("wq", nh)(x)                                   # [B,S,nh,hd]
        k = proj("wk", nkv)(x)
        v = proj("wv", nkv)(x)
        if cfg.decode:
            if mask is not None:
                raise ValueError(
                    "decode mode has no padding-mask support: the KV cache "
                    "assumes equal-length prompts (drop attention_mask and "
                    "bucket/pad prompts to one length upstream)")
            if segment_ids is not None:
                raise ValueError(
                    "decode mode does not take segment_ids (generation is "
                    "one document per row)")
            y = self._decode_attend(q, k, v)
        else:
            positions = jnp.arange(x.shape[1])[None, :]
            q = rotary_embedding(q, positions, cfg.rope_theta)
            k = rotary_embedding(k, positions, cfg.rope_theta)
            # GQA K/V stay at nkv heads: flash indexes groups directly, ring
            # runs grouped einsums; only the xla fallback broadcasts.
            y = dot_product_attention(q, k, v, mask=mask, causal=True,
                                      segment_ids=segment_ids,
                                      impl=cfg.attention_impl)
        rank = cfg.lora_rank if "wo" in cfg.lora_targets else 0
        return LoRADenseGeneral(cfg.hidden_size, axis=(-2, -1), rank=rank,
                                alpha=cfg.lora_alpha, dtype=cfg.dtype,
                                param_dtype=cfg.param_dtype,
                                base_quant=cfg.base_quant, name="wo")(y)

    def _decode_attend(self, q, k, v):
        """KV-cached attention: append the T new tokens at the cache index,
        attend q over the cached prefix. One code path serves prefill (T =
        prompt length at index 0) and decode (T = 1). Static shapes: the
        cache is [B, max_cache_len, nkv, hd]; masking, not slicing, bounds
        the attended positions (XLA-friendly — no dynamic shapes).

        The write index is PER ROW (``index`` is [B], not a scalar): plain
        ``generate`` advances every row in lockstep so the values stay
        equal, but the continuous-batching server (serve/generate.py) keys
        each KV slot at its own sequence position — a request admitted
        mid-flight decodes from its prompt length while its neighbors are
        hundreds of tokens in. Rows never see each other's stale cache:
        ``kpos <= qpos`` bounds attention at each row's own position, and
        every decode step writes its token before attending, so any
        garbage beyond a row's index is both masked and overwritten before
        it could ever be read."""
        cfg = self.cfg
        b, t = q.shape[0], q.shape[1]
        max_len = cfg.max_cache_len or cfg.max_position
        ck = self.variable("cache", "k", jnp.zeros,
                           (b, max_len, cfg.num_kv_heads, cfg.head_dim), cfg.dtype)
        cv = self.variable("cache", "v", jnp.zeros,
                           (b, max_len, cfg.num_kv_heads, cfg.head_dim), cfg.dtype)
        cidx = self.variable("cache", "index",
                             lambda: jnp.zeros((b,), jnp.int32))
        idx = cidx.value                                       # [B]
        positions = idx[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        q = rotary_embedding(q, positions, cfg.rope_theta)
        k = rotary_embedding(k, positions, cfg.rope_theta)

        def write_row(cache_row, new_row, start):
            return jax.lax.dynamic_update_slice(
                cache_row, new_row, (start, 0, 0))

        ck.value = jax.vmap(write_row)(ck.value, k.astype(cfg.dtype), idx)
        cv.value = jax.vmap(write_row)(cv.value, v.astype(cfg.dtype), idx)
        cidx.value = idx + t
        kpos = jnp.arange(max_len, dtype=jnp.int32)[None, None, None, :]
        qpos = positions[:, None, :, None]
        attend = kpos <= qpos                     # causal over cached prefix
        return dot_product_attention(q, ck.value, cv.value, mask=attend,
                                     causal=False, impl="xla")


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg

        def proj(name, feats, axis=-1):
            rank = cfg.lora_rank if name in cfg.lora_targets else 0
            return LoRADenseGeneral(feats, axis=axis, rank=rank, alpha=cfg.lora_alpha,
                                    dtype=cfg.dtype,
                                    param_dtype=cfg.param_dtype,
                                    base_quant=cfg.base_quant, name=name)

        gate = proj("gate", cfg.intermediate_size)(x)
        up = proj("up", cfg.intermediate_size)(x)
        return proj("down", cfg.hidden_size)(nn.silu(gate) * up)


class DecoderLayer(nn.Module):
    """Pre-norm block; returns (x, aux) — the (carry, out) pair nn.scan
    wants; ``aux`` is the layer's router balance loss (0 when dense)."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array, mask: jax.Array | None,
                 segment_ids: jax.Array | None = None):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="attention_norm")(x)
        x = x + LlamaAttention(cfg, name="attention")(h, mask,
                                                      segment_ids)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="mlp_norm")(x)
        if cfg.moe_experts:
            from distributeddeeplearningspark_tpu.models.moe import (
                RoutedExperts,
            )

            y, stats = RoutedExperts(
                cfg.hidden_size, cfg.intermediate_size, cfg.moe_experts,
                cfg.moe_top_k, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="moe")(h)
            aux = stats["aux"]
        else:
            y = LlamaMLP(cfg, name="mlp")(h)
            aux = jnp.float32(0.0)
        return x + y, aux


class _LMHead(nn.Module):
    """Untied LM head with the exact param path/init/compute of
    ``nn.Dense(vocab, use_bias=False, name="lm_head")`` — replaced only so
    the fused-loss path can read the kernel without applying it (param tree,
    TP rule ``lm_head/kernel`` and HF interchange stay byte-identical)."""

    vocab: int
    dtype: Any
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, *, return_kernel: bool = False):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.vocab), self.param_dtype)
        if return_kernel:
            return kernel
        return jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype))


class LlamaForCausalLM(nn.Module):
    """Decoder-only LM; logits [B,S,vocab] f32 (untied head, as in Llama-2)."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, batch: dict[str, jax.Array], *, train: bool = False) -> jax.Array:
        # no dropout in Llama-2; `train` only gates whether the MoE aux
        # loss is returned (predict/eval consumers expect a plain logits
        # array — see the returns below)
        cfg = self.cfg
        ids = batch["input_ids"]
        if ids.shape[1] > cfg.max_position:
            raise ValueError(
                f"sequence length {ids.shape[1]} exceeds max_position {cfg.max_position}"
            )
        if cfg.base_quant is not None:
            if cfg.base_quant != "int8":
                raise ValueError(f"unknown base_quant {cfg.base_quant!r}; "
                                 "supported: 'int8'")
            if not cfg.lora_rank:
                # int8 leaves carry float0 tangents — full-parameter
                # training would feed them to the optimizer; the quantized
                # base only makes sense frozen under adapters
                raise ValueError("base_quant='int8' requires lora_rank > 0 "
                                 "(frozen-base LoRA; train with "
                                 "trainable=lora_trainable)")
            if cfg.moe_experts:
                raise NotImplementedError(
                    "base_quant with moe_experts: the expert bank TRAINS "
                    "from scratch (f32) — quantizing it would silently "
                    "freeze garbage; drop one of the two")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="token_embed")(ids)
        pad = batch.get("attention_mask")
        # causal handled inside attention; only pass an explicit mask for padding
        mask = padding_mask(pad) if pad is not None else None
        # packed-document batches (data/text.py lm_dataset(segment_ids=True)):
        # per-position doc ids block cross-document attention — streamed
        # natively by the flash kernel and the ring's riding blocks
        segment_ids = batch.get("segment_ids")

        layer_cls = DecoderLayer
        if cfg.scan_layers and cfg.scan_param_barrier:
            # barrier each SLICED layer's params (see the config field's
            # rationale). MUST wrap inside the remat region (i.e. before
            # nn.remat): outside it, the barrier's outputs become per-layer
            # saved residuals and the forward scan stashes a full stacked
            # copy of every weight (+12.5 GiB at 7B, measured) — inside,
            # the backward replay re-slices the loop-invariant params and
            # re-applies the free barrier instead.
            layer_cls = nn.map_variables(
                layer_cls, "params",
                trans_in_fn=lambda tree: jax.tree.map(
                    jax.lax.optimization_barrier, tree),
                init=self.is_initializing())
        if cfg.remat:
            layer_cls = nn.remat(layer_cls, prevent_cse=False,
                                 policy=_remat_policy(cfg.remat_policy))
        if cfg.scan_layers:
            var_axes = {"params": 0}
            if cfg.decode:
                var_axes["cache"] = 0           # per-layer KV caches, stacked
            stacked = nn.scan(
                layer_cls,
                variable_axes=var_axes,
                split_rngs={"params": True},
                in_axes=nn.broadcast,           # mask is shared, not scanned
                length=cfg.num_layers,
            )(cfg, name="layers")
            x, aux = stacked(x, mask, segment_ids)
            moe_aux = jnp.sum(aux) if cfg.moe_experts else None
        else:
            auxes = []
            for i in range(cfg.num_layers):
                x, aux = layer_cls(cfg, name=f"layers_{i}")(
                    x, mask, segment_ids)
                auxes.append(aux)
            moe_aux = (jnp.sum(jnp.stack(auxes))
                       if cfg.moe_experts else None)

        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="final_norm")(x)
        head = _LMHead(cfg.vocab_size, cfg.dtype, cfg.param_dtype,
                       name="lm_head")
        if cfg.fused_head_loss and not cfg.decode:
            # hand the pieces to losses.causal_lm_fused; the [B,S,V] f32
            # logits (and their cotangent) never exist
            out = {"hidden": x, "lm_head": head(x, return_kernel=True)}
            if moe_aux is not None and train:
                out["moe_aux"] = cfg.moe_aux_weight * moe_aux
            return out
        logits = head(x).astype(jnp.float32)
        if moe_aux is not None and train and not cfg.decode:
            # train only: predict/eval consumers (Trainer.predict row
            # indexing, argmax output_fns) expect a bare logits array
            return {"logits": logits,
                    "moe_aux": cfg.moe_aux_weight * moe_aux}
        return logits


def llama2_7b(**kw) -> LlamaForCausalLM:
    return LlamaForCausalLM(LlamaConfig.llama2_7b(**kw))


def llama2_13b(**kw) -> LlamaForCausalLM:
    return LlamaForCausalLM(LlamaConfig.llama2_13b(**kw))


def llama_tiny(**kw) -> LlamaForCausalLM:
    return LlamaForCausalLM(LlamaConfig.tiny(**kw))


def lora_trainable(path: str) -> bool:
    """Optimizer mask for LoRA fine-tuning: train adapters only.

    Use with :func:`distributeddeeplearningspark_tpu.train.optim.masked` — the
    rebuild of the reference's per-param-group ``requires_grad=False`` on all
    base weights.
    """
    return "lora_a" in path or "lora_b" in path


def llama_rules(cfg: LlamaConfig, *, fsdp: bool = True,
                fsdp_min_size: int = 2**14, pipeline: bool = False) -> ShardingRules:
    """FSDP + Megatron-style tensor-parallel layout for the Llama tree.

    Attention QKV shard heads over ``tensor``; the out-projection and MLP
    down-projection shard their *input* (contracting) dim so GSPMD turns the
    pair into a split-matmul + psum (one all-reduce per block, the Megatron
    pattern). Embedding and LM head shard the vocab dim. LoRA adapters stay
    replicated — rank-r factors are too small to be worth a collective. The
    auto-FSDP pass then shards the largest remaining dim of every large
    param over ``fsdp`` (with scanned layers that is usually the [L, ...]
    leading dim — uniform and always divisible).

    ``pipeline=True`` (requires ``scan_layers``): the stacked [L, ...]
    leading dim of every decoder-layer param shards over ``pipe`` instead —
    each device then STORES only its own stages, making PP a param-memory
    partitioning like the reference's FSDP but along depth; auto-FSDP moves
    to the next-largest dim.
    """
    if pipeline and not cfg.scan_layers:
        raise ValueError("pipeline rules need scan_layers=True stacked params")
    lead = (("pipe",) if pipeline else (None,)) if cfg.scan_layers else ()
    rules = (
        (r"lora_", P(*lead) if pipeline else P()),
        (r"(wq|wk|wv)/base/kernel", P(*lead, None, "tensor", None)),
        (r"wo/base/kernel", P(*lead, "tensor", None, None)),
        (r"(gate|up)/base/kernel", P(*lead, None, "tensor")),
        (r"down/base/kernel", P(*lead, "tensor", None)),
        # int8 base (base_quant): kernels mirror their bf16 siblings'
        # layouts; per-out-channel scales follow the kernel's OUTPUT dims
        # (wo/down outputs are the psum'd hidden dim → replicated)
        # int8 kernels fold input axes: wq/wk/wv stay (in, heads, hd)
        # like their dense siblings, but wo folds (heads, hd) → one 2-D
        # (heads*hd, hidden) contracting-sharded kernel
        *(((r"(wq|wk|wv)/base_q8", P(*lead, None, "tensor", None)),
           (r"(wq|wk|wv)/base_scale", P(*lead, "tensor", None)),
           (r"wo/base_q8", P(*lead, "tensor", None)),
           (r"(gate|up)/base_q8", P(*lead, None, "tensor")),
           (r"(gate|up)/base_scale", P(*lead, "tensor")),
           (r"(wo|down)/base_scale", P(*lead, None)),
           (r"down/base_q8", P(*lead, "tensor", None)),
           ) if cfg.base_quant else ()),
        (r"token_embed/embedding", P("tensor", None)),
        (r"lm_head/kernel", P(None, "tensor")),
        # MoE expert bank: stacked expert kernels shard over `expert`
        # (+ FFN dims over `tensor`); the tiny router replicates
        *(((r"moe/(w_gate|w_up)", P(*lead, "expert", None, "tensor")),
           (r"moe/w_down", P(*lead, "expert", "tensor", None)),
           (r"moe/router", P(*lead) if pipeline else P()),
           ) if cfg.moe_experts else ()),
        # PP catch-all: any remaining stacked layer param (norm scales)
        # stores on its own stage's devices. (`(^|/)` anchor: TrainState
        # paths are prefixed, e.g. "params/layers/...".)
        *(((r"(^|/)layers/", P(*lead)),) if pipeline else ()),
    )
    return ShardingRules(rules=rules, fsdp=fsdp, fsdp_min_size=fsdp_min_size,
                         fsdp_exclude=(r"lora_",))
