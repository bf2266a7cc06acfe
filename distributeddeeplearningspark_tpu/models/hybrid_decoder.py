"""A decoder of three operators and two feed-forwards in a published pattern:
gated short convolutions (:mod:`..ops.short_conv`), causal grouped-query
attention layers and causal LATENT attention layers (low-rank query and
key-value paths, one rotary key shared by all heads, value heads narrower
than the query's and key's), a dense SwiGLU in the leading layers and
routed experts behind a sigmoid router (:class:`..models.moe.RoutedExperts`,
``score="sigmoid"``; optionally beside a shared expert and scaled) in the
rest, over PACKED documents that no operator crosses. The embedding is the
head unless ``tie_embeddings`` is off (it is then drawn N(0, 1), and the head
a kernel of its own). Norms and the rotary embedding are the
Llama path's (:mod:`.llama`), the fused head loss :mod:`..train.fused_ce`'s.
``mtp_layers = 1`` adds DeepSeek-V3's multi-token-prediction module (below).

Built from ``layer_types`` (``"conv"``, ``"full_attention"``,
``"sliding_attention"`` or ``"latent_attention"`` a layer) and
``num_dense_layers``. One block, ``x [B, S, hidden]``, ``seg[t]`` the
document of position ``t``, ``pos[t] = t -`` the first position of that
document in the window::

    h  = x + OP(RMSNorm_op(x));   x' = h + FFN(RMSNorm_ffn(h))
    OP conv:       (b, c, u) = split3(x Win);  v = b * u
                   z[t] = sum_j w[:, j] * v[t-K+1+j]  within t's document
                   OP = (c * z) Wout
    OP attention:  q, k = rotary(RMSNorm_head(x Wq), pos), rotary(RMSNorm_head(
                   x Wk), pos);  softmax over {s <= t, seg[s] = seg[t]} of
                   q.k / sqrt(head_dim);  OP = (p v) Wo
    OP sliding:    the same inside the band ``0 <= t - s < sliding_window``
                   (the flash kernels' band regime walks only its blocks),
                   with heads and rotary of its own; either kind may rotate
                   only the head's leading share (``partial_rotary_factor``),
                   at YaRN's frequencies (``yarn_factor``) with a factor on
                   cos and sin, skip the per-head norm (``qk_norm``) and gate
                   each head's output, ``o_h sigmoid(x Wg)_h``, before ``Wo``
                   (``attention_gate``)
    OP latent:     c_q = RMSNorm(x Wdq);  [q_nope | q_rot] = c_q Wuq  (a head)
                   [c | k_r] = x Wdkv;  [k_nope | v] = RMSNorm(c) Wukv (a head)
                   q_rot, k_r = rotary(., pos) over adjacent pairs; k_r is ONE
                   head that every query head reads; softmax as above of
                   (q_nope.k_nope + q_rot.k_r) / sqrt(nope + rot);  OP = (p v) Wo
    FFN dense:     W2(silu(W1 x) * W3 x)                 (the leading layers)
    FFN experts:   s = sigmoid(x Wg); top-k of s + b; weights s_e / (sum of
                   the chosen s + 1e-6) times ``routed_scaling_factor``;
                   plus shared(x), a SwiGLU every token passes, where
                   ``shared_expert_size`` is not 0. With ``router_score``
                   ``"softmax"``: softmax over all experts, the top k
                   renormalised, no bias, and Switch's balance loss times
                   ``router_aux_loss_coef`` as ``moe_aux``

Multi-token prediction, depth 1 (arXiv:2412.19437 eq. 21-25), with ``h`` the
last block's output BEFORE the final norm::

    u_t = W_eh [RMSNorm_e(Emb(x_{t+1})) ; RMSNorm_h(h_t)];  one more block of
    the last layer's kind over u;  ``mtp_hidden`` = RMSNorm_mtp(that)

``losses.latent_moe_lm`` takes ``mtp_hidden`` through the MAIN head against
``x_{t+2}`` and adds ``mtp_loss_weight`` times that term. The module runs
over all S rows (S - 1 divides by no kernel's block): the last row's "next
token" is id 0, and the loss leaves out the last TWO rows' targets; causal
attention keeps every other row what S - 1 rows would give.

Blocks of ONE sublayer (Nemotron-H's, arXiv:2504.03624: a pattern of Mamba-2
layers, expert layers and attention layers, each a block of its own), the
kinds ``"mamba2"``, ``"experts"`` and ``"bare_attention"`` of ``layer_types``::

    x' = x + SUB(RMSNorm(x))                       (one norm a block)
    SUB mamba2:  [z | xBC | dt] = x Win  (H P, H P + 2 G N and H wide)
                 xBC = silu(conv(xBC) + b): depthwise, causal, K taps,
                 inside t's document;  [x | B | C] = split(xBC)
                 dt = softplus(dt + dt_bias);  A = -exp(A_log)  (a head)
                 h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,  y_t = h_t C_t +
                 D x_t, a head of P with a state of P x N, head h reading
                 group h // (H / G) of B and C, h = 0 before a document's
                 first position (:func:`..ops.ssd.ssd_scan`, chunked)
                 y = RMSNorm_groups(y * silu(z)) * w over G groups (the gate
                 BEFORE the norm);  SUB = y Wout
    SUB experts: the routed experts (and shared expert) below, here
                 ``down(relu(up x)^2)`` of two matrices where ``expert_form``
                 is ``"relu2"``
    SUB bare_attention: the attention operator above WITHOUT rotary
                 embedding and without per-head norm (no positional
                 embedding at all: the state-space layers carry position)

``b`` is no parameter: it lives in the mutable collection
``moe.BIAS_COLLECTION`` and a training step moves it by its own load counts
(auxiliary-loss-free balancing), so the sigmoid router has no loss term.

Layout of the depth: the leading dense layers and whatever does not fill a
period at the end are unrolled; the whole periods between them are ONE
``nn.scan`` over a block of ``period`` layers with ``remat`` a layer, so the
compile time does not grow with depth (the published 40 layers: 2 leading,
9 periods of ``attention, conv, conv, conv``, 2 trailing).

Batch: ``input_ids [B, S]`` int32 and, optionally, ``segment_ids [B, S]``
int32 (``data/text.packed_token_windows(segment_ids=True)``), a document's
positions being consecutive; without them a row is one document.
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
    rotary_embedding,
    yarn_inv_freq,
)
from distributeddeeplearningspark_tpu.models.moe import (
    BIAS_COLLECTION,
    RoutedExperts,
)
from distributeddeeplearningspark_tpu.ops.attention import dot_product_attention
from distributeddeeplearningspark_tpu.ops.flash_attention import (
    FLASH_OUT_NAME,
    attn_blocks_masked_share,
    attn_blocks_walked_share,
    window_blocks_walked_share,
)
from distributeddeeplearningspark_tpu.ops.short_conv import (
    gated_short_conv,
    silu_short_conv,
)
from distributeddeeplearningspark_tpu.ops.ssd import (
    chunks_reset_share,
    ssd_scan,
)
from distributeddeeplearningspark_tpu.parallel.sharding import ShardingRules

CONV, ATTENTION, LATENT = "conv", "full_attention", "latent_attention"
#: causal attention inside a band of ``sliding_window`` positions, with heads
#: and rotary of its own (``sliding_num_heads``, ``sliding_rope_theta``)
SLIDING = "sliding_attention"
#: blocks of ONE sublayer, ``x + SUB(RMSNorm(x))`` (module docstring)
MAMBA, EXPERTS, BARE_ATTENTION = "mamba2", "experts", "bare_attention"
SOLO = (MAMBA, EXPERTS, BARE_ATTENTION)
#: the step's counters (docs/OBSERVABILITY.md): the first two are means over
#: the expert layers, the third the largest over them, the last three the
#: batch's; ``losses.hybrid_moe_lm`` carries them into the step's metrics
COUNTERS = ("moe_load_max_over_mean", "moe_rows_held_share",
            "router_bias_abs_max", "attn_pairs_share",
            "attn_blocks_walked_share", "attn_blocks_masked_share")
#: two more, only of a model with state-space layers: the share of the
#: batch's scan chunks that hold a document's first position, and the largest
#: magnitude of a state the LAST such layer's scan handed between chunks
SSM_COUNTERS = ("ssm_chunks_reset_share", "ssm_state_abs_max")
#: two more, only of a model with sliding-window layers: of the blocks on or
#: under the diagonal, the share the band kernels walk (from their own
#: predicate), and the in-document pairs inside the window over ``S x
#: window`` (what the band requires)
WINDOW_COUNTERS = ("window_blocks_walked_share", "window_pairs_share")


@dataclasses.dataclass(frozen=True)
class HybridDecoderConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: tuple[str, ...] = (
        (CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 9 + (ATTENTION, CONV))
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    # the full-attention layers' rotary beyond theta: the leading share of
    # the head that turns, YaRN's frequencies (factor 1: none) and the
    # factor on cos and sin
    partial_rotary_factor: float = 1.0
    yarn_factor: float = 1.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    rope_attention_factor: float = 1.0
    qk_norm: bool = True             # per-head RMSNorm on q and k
    # a per-head output gate on both attention kinds: o_h * sigmoid(x Wg)_h
    attention_gate: bool = False
    # layers of kind SLIDING: the band, their query heads (None: num_heads)
    # and their rotary (the whole head at a theta of its own)
    sliding_window: int | None = None
    sliding_num_heads: int | None = None
    sliding_rope_theta: float = 1e4
    rms_eps: float = 1e-5
    max_position: int = 128000
    conv_taps: int = 3               # conv_L_cache
    intermediate_size: int = 11776   # the dense layers' SwiGLU
    # routed experts (models/moe.py RoutedExperts, sigmoid router)
    num_experts: int = 64            # the router's width
    experts_per_token: int = 4
    # "sigmoid" (bias-balanced, no loss term) or "softmax" (Switch's balance
    # loss times router_aux_loss_coef, summed over the expert layers)
    router_score: str = "sigmoid"
    router_aux_loss_coef: float = 0.0
    expert_size: int = 1536
    experts_held: tuple[int, int] | None = None   # (first, count); None: all
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    bias_update_rate: float = 0.001
    # False for a share trained without its exchange (RoutedExperts)
    train_router: bool = True
    routed_scaling_factor: float = 1.0
    shared_expert_size: int = 0      # 0: no shared expert
    expert_form: str = "swiglu"      # or "relu2": down(relu(up x)^2)
    # state-space layers (kind MAMBA): heads x head_dim is the inner width
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8              # groups of B and C, and of the gated norm
    ssm_state_size: int = 128
    ssm_conv_taps: int = 4
    ssm_chunk: int = 128             # of the chunked scan: changes no value
    time_step_min: float = 0.001     # dt_bias is drawn so that softplus of it
    time_step_max: float = 0.1       # is log-uniform between these two,
    time_step_floor: float = 1e-4    # floored
    # latent attention (layers of kind LATENT; its rotary embedding is over
    # adjacent pairs, DeepSeek's ``rope_interleave``)
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    tie_embeddings: bool = True      # False: a head of its own, ``lm_head``
    mtp_layers: int = 0              # num_nextn_predict_layers: 0 or 1
    mtp_loss_weight: float = 0.1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.mtp_layers not in (0, 1):
            raise ValueError(f"mtp_layers {self.mtp_layers}: a module of "
                             f"depth 1 is built, or none")
        unknown = set(self.layer_types) - {CONV, ATTENTION, LATENT, SLIDING,
                                           *SOLO}
        if SLIDING in self.layer_types and not (self.sliding_window or 0) > 0:
            raise ValueError("sliding_attention layers need a positive "
                             "sliding_window")
        if self.router_score == "softmax" and self.use_expert_bias:
            raise ValueError("the softmax router has no selection bias: "
                             "use_expert_bias must be False")
        if set(self.layer_types[:self.num_dense_layers]) & set(SOLO):
            raise ValueError("a block of one sublayer has no feed-forward "
                             "to be dense: num_dense_layers counts none")
        if self.mtp_layers and self.layer_types[-1:] and (
                self.layer_types[-1] in SOLO):
            raise ValueError("the multi-token-prediction module is one more "
                             "block of the last layer's kind, which must be "
                             "an operator with its feed-forward")
        if unknown or not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError(f"layer_types {sorted(unknown)} unknown, or "
                             f"{self.num_dense_layers} dense layers of "
                             f"{len(self.layer_types)}")

    def layout(self) -> tuple[tuple[str, ...], tuple[str, ...], int,
                              tuple[str, ...]]:
        """``(leading dense layers, one period, whole periods, trailing
        layers)``: the period is the shortest the expert layers repeat
        with."""
        lead = self.layer_types[:self.num_dense_layers]
        rest = self.layer_types[self.num_dense_layers:]
        if not rest:
            return lead, (), 0, ()
        period = next(p for p in range(1, len(rest) + 1)
                      if all(rest[i] == rest[i - p]
                             for i in range(p, len(rest))))
        whole = len(rest) // period
        return lead, rest[:period], whole, rest[whole * period:]

    @staticmethod
    def tiny(**kw) -> "HybridDecoderConfig":
        """One dense layer and two periods of (attention, conv), 128 wide, 8
        experts of which 2 a token: CPU tests."""
        base = dict(vocab_size=256, hidden_size=128,
                    layer_types=(CONV, ATTENTION, CONV, ATTENTION, CONV),
                    num_dense_layers=1, num_heads=4, num_kv_heads=2,
                    head_dim=32, intermediate_size=192, num_experts=8,
                    experts_per_token=2, expert_size=64, max_position=4096,
                    dtype=jnp.float32)
        base.update(kw)
        return HybridDecoderConfig(**base)

    @staticmethod
    def tiny_latent(**kw) -> "HybridDecoderConfig":
        """DeepSeek-V3's shape at a CPU test's size: one dense and two expert
        layers of latent attention (4 heads of 16 + 8 / 16), 8 experts of
        which 2 a token beside a shared one, scaled 2.5, an untied head and
        the MTP module."""
        base = dict(layer_types=(LATENT,) * 3, num_dense_layers=1,
                    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, rms_eps=1e-6,
                    shared_expert_size=64, routed_scaling_factor=2.5,
                    tie_embeddings=False, mtp_layers=1)
        base.update(kw)
        return HybridDecoderConfig.tiny(**base)

    @staticmethod
    def tiny_window(**kw) -> "HybridDecoderConfig":
        """Laguna-XS.2's shape at a CPU test's size: a dense full-attention
        layer, then the period (sliding, sliding, sliding, full) of expert
        layers; window 16; 6 query heads on 2 in full layers (YaRN on the
        leading half of the head, with its attention factor), 8 on 2 in
        sliding ones (whole head at theta 1e4); per-head output gates, no q/k
        norm; a softmax router over 8 experts, 2 a token, with Switch's loss,
        beside an ungated shared expert, scaled 2.5; an untied head."""
        base = dict(layer_types=(ATTENTION, SLIDING, SLIDING, SLIDING,
                                 ATTENTION),
                    num_dense_layers=1, num_heads=6, num_kv_heads=2,
                    head_dim=16, rms_eps=1e-6, rope_theta=5e5,
                    partial_rotary_factor=0.5, yarn_factor=64.0,
                    yarn_original_max_position=4096, yarn_beta_fast=64.0,
                    yarn_beta_slow=1.0, rope_attention_factor=1.4158883,
                    qk_norm=False, attention_gate=True, sliding_window=16,
                    sliding_num_heads=8, sliding_rope_theta=1e4,
                    router_score="softmax", router_aux_loss_coef=0.001,
                    use_expert_bias=False, shared_expert_size=64,
                    routed_scaling_factor=2.5, tie_embeddings=False)
        base.update(kw)
        return HybridDecoderConfig.tiny(**base)

    @staticmethod
    def tiny_ssm(**kw) -> "HybridDecoderConfig":
        """Nemotron-H's shape at a CPU test's size: the period ``M E M E M *
        E`` of one-sublayer blocks; 4 state-space heads of 8 with a state of
        16 in 2 groups (so head ``h`` reads group ``h // 2``, not ``h % 2``),
        4 taps, chunks of 16; attention without positions; 8 relu² experts
        of which 2 a token beside a wider shared one, scaled 2.5; an untied
        head."""
        base = dict(layer_types=(MAMBA, EXPERTS, MAMBA, EXPERTS, MAMBA,
                                 BARE_ATTENTION, EXPERTS),
                    num_dense_layers=0, ssm_heads=4, ssm_head_dim=8,
                    ssm_groups=2, ssm_state_size=16, ssm_chunk=16,
                    expert_form="relu2", shared_expert_size=128,
                    routed_scaling_factor=2.5, tie_embeddings=False)
        base.update(kw)
        return HybridDecoderConfig.tiny(**base)


def _dense(cfg, feats, name, axis=-1):
    return nn.DenseGeneral(feats, axis=axis, use_bias=False, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name=name)


class ShortConv(nn.Module):
    """The gated short convolution between its two projections."""

    cfg: HybridDecoderConfig

    @nn.compact
    def __call__(self, x, seg):
        cfg = self.cfg
        bcx = _dense(cfg, 3 * cfg.hidden_size, "in_proj")(x)
        # taps of a depthwise convolution: fan-in is the number of taps
        taps = self.param(
            "taps", nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=1, out_axis=0),
            (cfg.hidden_size, cfg.conv_taps), jnp.float32)
        return _dense(cfg, cfg.hidden_size, "out_proj")(
            gated_short_conv(bcx, taps, seg))


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm_groups(y * silu(z)) * scale`` in float32: the gate BEFORE
    the norm, the norm over each of ``groups`` equal slices of the last
    axis."""
    f32 = jnp.float32
    gated = (y.astype(f32) * nn.silu(z.astype(f32))).reshape(
        *y.shape[:-1], groups, -1)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
    return gated.reshape(y.shape) * scale


class Mamba2Mixer(nn.Module):
    """The state-space sublayer (module docstring): ``W_in``, the ungated
    short convolution with its bias and SiLU, the chunked scan, the grouped
    gated RMSNorm (gate first), ``W_out``; ``seg`` reaches the convolution
    and the scan. ``(x, seg) -> (y, the largest magnitude of a state the
    scan handed between chunks)``."""

    cfg: HybridDecoderConfig

    @nn.compact
    def __call__(self, x, seg):
        cfg = self.cfg
        heads, p, g, n = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                          cfg.ssm_state_size)
        inner, f32 = heads * p, jnp.float32
        conv_width = inner + 2 * g * n                   # [x | B | C]
        z, xbc, dt = jnp.split(
            _dense(cfg, inner + conv_width + heads, "in_proj")(x),
            [inner, inner + conv_width], axis=-1)
        taps = self.param(
            "conv_taps", nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=1, out_axis=0),
            (conv_width, cfg.ssm_conv_taps), f32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros,
                               (conv_width,), f32)
        xs, bm, cm = jnp.split(silu_short_conv(xbc, taps, conv_bias, seg),
                               [inner, inner + g * n], axis=-1)

        def dt_bias_init(key, shape):
            # softplus(dt_bias) log-uniform in [time_step_min, time_step_max]
            lo, hi = jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max)
            step = jnp.maximum(jnp.exp(jax.random.uniform(
                key, shape, f32) * (hi - lo) + lo), cfg.time_step_floor)
            return step + jnp.log(-jnp.expm1(-step))

        a_log = self.param("A_log", lambda key, shape: jnp.log(
            jax.random.uniform(key, shape, f32, 1.0, 16.0)), (heads,))
        d_skip = self.param("D", nn.initializers.ones, (heads,), f32)
        dt_bias = self.param("dt_bias", dt_bias_init, (heads,))
        b, s = x.shape[:2]
        y, state_abs_max = ssd_scan(
            xs.reshape(b, s, heads, p), nn.softplus(dt.astype(f32) + dt_bias),
            -jnp.exp(a_log), bm.reshape(b, s, g, n), cm.reshape(b, s, g, n),
            d_skip, seg, chunk=cfg.ssm_chunk)
        # (written only where a caller asks for "intermediates" as mutable)
        self.sow("intermediates", "scan", y)
        scale = self.param("norm", nn.initializers.ones, (inner,), f32)
        y = gated_group_norm(y.reshape(b, s, inner), z, scale, g,
                             cfg.rms_eps).astype(cfg.dtype)
        # rescale_prenorm_residual: the output kernel over sqrt(layers)
        out = nn.DenseGeneral(
            cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="out_proj",
            kernel_init=nn.initializers.variance_scaling(
                1.0 / len(cfg.layer_types), "fan_in", "truncated_normal"))
        return out(y), state_abs_max


def rotary_of(cfg: HybridDecoderConfig, sliding: bool) -> dict:
    """``rotary_embedding``'s keyword arguments for a layer kind: a sliding
    layer's theta and share of the head, or a full layer's, with YaRN's
    frequencies over the share that turns where ``yarn_factor`` is not 1 and
    its factor on cos and sin. A sibling that sets none of these gets the
    whole head at ``rope_theta``, as before they were."""
    if sliding:
        return {"theta": cfg.sliding_rope_theta}
    rot = int(cfg.head_dim * cfg.partial_rotary_factor)
    out = {"theta": cfg.rope_theta, "rotary_dim": rot,
           "attention_factor": cfg.rope_attention_factor}
    if cfg.yarn_factor != 1.0:
        out["inv_freq"] = yarn_inv_freq(
            rot, cfg.rope_theta, factor=cfg.yarn_factor,
            original_max_position=cfg.yarn_original_max_position,
            beta_fast=cfg.yarn_beta_fast, beta_slow=cfg.yarn_beta_slow)
    return out


def head_gate(o, g):
    """``o [B, S, heads, D]`` times ``sigmoid(g)`` of its head (``g [B, S,
    heads]``), the sigmoid in float32: the per-head output gate."""
    return o * nn.sigmoid(g.astype(jnp.float32)).astype(o.dtype)[..., None]


class CausalAttention(nn.Module):
    """Grouped-query attention inside a document, per-head q/k RMSNorm
    (where ``qk_norm``) before the rotary embedding, positions restarting
    with the document; ``bare``: neither the norm nor the rotary embedding;
    ``sliding``: inside the band of ``sliding_window``, with the sliding
    layers' heads and rotary (:func:`rotary_of`). With ``attention_gate``
    each head's output is scaled by ``sigmoid(x Wg)`` of its own before
    ``wo`` (:func:`head_gate`)."""

    cfg: HybridDecoderConfig
    bare: bool = False
    sliding: bool = False

    @nn.compact
    def __call__(self, x, seg, pos):
        cfg = self.cfg
        heads = ((cfg.sliding_num_heads or cfg.num_heads) if self.sliding
                 else cfg.num_heads)
        q = _dense(cfg, (heads, cfg.head_dim), "wq")(x)
        k = _dense(cfg, (cfg.num_kv_heads, cfg.head_dim), "wk")(x)
        v = _dense(cfg, (cfg.num_kv_heads, cfg.head_dim), "wv")(x)
        if self.bare:
            o = dot_product_attention(q, k, v, causal=True, segment_ids=seg)
            return _dense(cfg, cfg.hidden_size, "wo", axis=(-2, -1))(o)
        rope = rotary_of(cfg, self.sliding)

        def positioned(t, norm):   # (q's norm and rotary, then k's)
            if cfg.qk_norm:
                t = RMSNorm(cfg.rms_eps, cfg.dtype, name=norm)(t)
            return rotary_embedding(t, pos, **rope)

        q, k = positioned(q, "q_norm"), positioned(k, "k_norm")
        o = dot_product_attention(
            q, k, v, causal=True, segment_ids=seg,
            window=cfg.sliding_window if self.sliding else None)
        if cfg.attention_gate:
            o = head_gate(o, _dense(cfg, heads, "gate")(x))
        return _dense(cfg, cfg.hidden_size, "wo", axis=(-2, -1))(o)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3) inside a document: q
    through a normed latent of ``q_lora_rank``, keys' position-free part and
    values through a normed latent of ``kv_lora_rank``, ONE rotary key of
    ``qk_rope_head_dim`` read by every query head; q and k ``qk_nope_head_dim
    + qk_rope_head_dim`` wide, v and the output ``v_head_dim``. The shared
    key is broadcast to the heads and k materialised ``[B, S, heads, nope +
    rot]`` before the attention call; nothing is padded to the wider size."""

    cfg: HybridDecoderConfig
    packed: bool   # the batch carries segment ids; else no mask is streamed

    @nn.compact
    def __call__(self, x, seg, pos):
        cfg = self.cfg
        heads, nope, rot = (cfg.num_heads, cfg.qk_nope_head_dim,
                            cfg.qk_rope_head_dim)
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.dtype, name=name)
        rotary = lambda t: rotary_embedding(t, pos, cfg.rope_theta,
                                            interleaved=True)
        c_q = norm("q_norm")(_dense(cfg, cfg.q_lora_rank, "wq_a")(x))
        c_kr = _dense(cfg, cfg.kv_lora_rank + rot, "wkv_a")(x)
        c_kv = norm("kv_norm")(c_kr[..., :cfg.kv_lora_rank])
        k_rot = c_kr[..., cfg.kv_lora_rank:]
        # (written only where a caller asks for "intermediates" as mutable)
        self.sow("intermediates", "latents", (c_q, c_kv))
        q = _dense(cfg, (heads, nope + rot), "wq_b")(c_q)
        kv = _dense(cfg, (heads, nope + cfg.v_head_dim), "wkv_b")(c_kv)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:])], axis=-1)
        k_rot = jnp.broadcast_to(rotary(k_rot[:, :, None, :]),
                                 (*kv.shape[:3], rot))
        k = jnp.concatenate([kv[..., :nope], k_rot], axis=-1)
        o = dot_product_attention(q, k, kv[..., nope:], causal=True,
                                  segment_ids=seg if self.packed else None)
        return _dense(cfg, cfg.hidden_size, "wo", axis=(-2, -1))(o)


class SwiGLU(nn.Module):
    cfg: HybridDecoderConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(cfg, cfg.intermediate_size, "w1")(x)
        up = _dense(cfg, cfg.intermediate_size, "w3")(x)
        return _dense(cfg, cfg.hidden_size, "w2")(nn.silu(gate) * up)


class HybridLayer(nn.Module):
    """``(x, seg, pos) -> (x, stats)``; ``stats`` is empty for a dense layer
    and holds the expert layer's counters otherwise (a block of one
    sublayer: its experts' counters, its scan's ``ssm_state_abs_max``, or
    nothing)."""

    cfg: HybridDecoderConfig
    kind: str
    dense: bool
    packed: bool = True   # the batch carries segment ids (LATENT reads it)

    def _experts(self, h):
        cfg = self.cfg
        y, moe = RoutedExperts(
            cfg.hidden_size, cfg.expert_size, cfg.num_experts,
            cfg.experts_per_token, held=cfg.experts_held,
            norm_topk=cfg.norm_topk_prob, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, score=cfg.router_score,
            select_bias=cfg.use_expert_bias,
            bias_update_rate=cfg.bias_update_rate,
            train_router=cfg.train_router,
            routed_scale=cfg.routed_scaling_factor,
            shared_size=cfg.shared_expert_size,
            expert_form=cfg.expert_form, name="moe")(h)
        stats = {"moe_load_max_over_mean": moe["load_max_over_mean"],
                 "moe_rows_held_share": moe["rows_held_share"],
                 "router_bias_abs_max": moe.get("bias_abs_max",
                                                jnp.float32(0.0))}
        if cfg.router_aux_loss_coef:
            stats["moe_aux"] = moe["aux"]
        return y, stats

    @nn.compact
    def __call__(self, x, seg, pos):
        cfg = self.cfg
        if self.kind in SOLO:
            h = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x)
            if self.kind == MAMBA:
                y, peak = Mamba2Mixer(cfg, name="mixer")(
                    h, seg if self.packed else None)
                return x + y, {"ssm_state_abs_max": peak}
            if self.kind == EXPERTS:
                y, stats = self._experts(h)
                return x + y, stats
            return x + CausalAttention(cfg, bare=True, name="self_attn")(
                h, seg, pos), {}
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="operator_norm")(x)
        if self.kind == CONV:
            x = x + ShortConv(cfg, name="conv")(h, seg)
        elif self.kind == LATENT:
            x = x + LatentAttention(cfg, self.packed, name="self_attn")(
                h, seg, pos)
        elif self.kind == SLIDING:
            x = x + CausalAttention(cfg, sliding=True, name="self_attn")(
                h, seg, pos)
        else:
            x = x + CausalAttention(cfg, name="self_attn")(h, seg, pos)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="ffn_norm")(x)
        if self.dense:
            return x + SwiGLU(cfg, name="mlp")(h), {}
        y, stats = self._experts(h)
        return x + y, stats


def _layer_cls():
    # remat a layer, keeping the flash kernel's output and log-sum-exp (bf16
    # [B, S, heads, head_dim] and a float a row and head): the backward
    # kernels need those and never the forward pass again. prevent_cse stays
    # on: the leading and trailing layers are unrolled, and a scan of ONE
    # period is unrolled by the compiler, where common-subexpression
    # elimination would merge the replay with the forward pass and keep every
    # layer's activations after all
    return nn.remat(
        HybridLayer,
        policy=jax.checkpoint_policies.save_only_these_names(FLASH_OUT_NAME))


class _Period(nn.Module):
    """One period of expert layers: the block ``nn.scan`` repeats."""

    cfg: HybridDecoderConfig
    kinds: tuple[str, ...]
    packed: bool = True

    @nn.compact
    def __call__(self, x, seg, pos):
        per_layer = []
        for j, kind in enumerate(self.kinds):
            x, stats = _layer_cls()(self.cfg, kind, False, self.packed,
                                    name=f"layer_{j}")(x, seg, pos)
            per_layer.append(stats)
        # a counter over the layers that have it (blocks of one sublayer
        # differ in theirs), in layer order
        return x, {name: jnp.stack([s[name] for s in per_layer if name in s])
                   for name in sorted(set().union(*per_layer))}


class MTPModule(nn.Module):
    """DeepSeek-V3's multi-token-prediction module of depth 1 (module
    docstring): ``(h [B, S, hidden] before the final norm, the NEXT tokens'
    embedding rows) -> (mtp_hidden, the block's expert counters)``. The
    embedding half comes first in ``eh_proj``'s input, as the released
    weights are laid out."""

    cfg: HybridDecoderConfig
    packed: bool

    @nn.compact
    def __call__(self, h, next_rows, seg, pos):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.dtype, name=name)
        u = _dense(cfg, cfg.hidden_size, "eh_proj")(jnp.concatenate(
            [norm("enorm")(next_rows), norm("hnorm")(h)], axis=-1))
        u, stats = _layer_cls()(cfg, cfg.layer_types[-1], False, self.packed,
                                name="block")(u, seg, pos)
        return norm("final_norm")(u), stats


def document_positions(seg: jax.Array) -> jax.Array:
    """``[B, S]`` segment ids -> each position's index inside its document
    (a document's positions being consecutive)."""
    s = seg.shape[1]
    idx = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), seg.shape)
    starts = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], jnp.bool_), seg[:, 1:] != seg[:, :-1]], 1)
    return idx - jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)


class HybridDecoderLM(nn.Module):
    """``{"hidden" [B, S, hidden], "lm_head" [hidden, vocab]`` (the
    embedding, transposed, or the head's own kernel), the counters
    :data:`COUNTERS` and, with the MTP module, ``"mtp_hidden" [B, S, hidden]``
    and ``"mtp_weight"`` ``}``."""

    cfg: HybridDecoderConfig

    @nn.compact
    def __call__(self, batch: dict[str, jax.Array], *, train: bool = False):
        del train  # no dropout; the router's bias moves when it is mutable
        cfg = self.cfg
        ids = batch["input_ids"]
        if ids.shape[1] > cfg.max_position:
            raise ValueError(f"sequence length {ids.shape[1]} exceeds "
                             f"max_position {cfg.max_position}")
        seg = batch.get("segment_ids")
        packed = seg is not None
        seg = (seg.astype(jnp.int32) if packed
               else jnp.zeros(ids.shape, jnp.int32))
        pos = document_positions(seg)
        # an embedding that is also the head keeps flax's 1 / sqrt(hidden); one
        # of its own is N(0, 1) (torch's default), so that a token's row, and
        # not what the first attention layer averages over its prefix, leads
        # the residual stream: at 1 / sqrt(hidden) every position of a long
        # window routes alike from the first step on
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="token_embed",
            **({} if cfg.tie_embeddings else
               {"embedding_init": nn.initializers.normal(1.0)}))
        x = embed(ids)
        lead, period, whole, trail = cfg.layout()
        collected = []
        for i, kind in enumerate(lead):
            x, _ = _layer_cls()(cfg, kind, True, packed,
                                name=f"lead_{i}")(x, seg, pos)
        if whole:
            # ("intermediates": ``apply(capture_intermediates=...)`` reaches
            # the layers inside the scan too)
            x, stats = nn.scan(
                _Period, variable_axes={"params": 0, BIAS_COLLECTION: 0,
                                        "intermediates": 0},
                split_rngs={"params": True}, in_axes=(nn.broadcast,
                                                      nn.broadcast),
                length=whole)(cfg, period, packed, name="periods")(x, seg, pos)
            collected.append(jax.tree.map(lambda a: a.reshape(-1), stats))
        for i, kind in enumerate(trail):
            x, stats = _layer_cls()(cfg, kind, False, packed,
                                    name=f"trail_{i}")(x, seg, pos)
            collected.append(jax.tree.map(lambda a: a.reshape(1), stats))
        out = {}
        if cfg.mtp_layers:
            next_ids = jnp.concatenate(
                [ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
            out["mtp_hidden"], stats = MTPModule(cfg, packed, name="mtp")(
                x, embed(next_ids), seg, pos)
            out["mtp_weight"] = jnp.float32(cfg.mtp_loss_weight)
            collected.append(jax.tree.map(lambda a: a.reshape(1), stats))
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="final_norm")(x)
        head = (embed.embedding.T if cfg.tie_embeddings else self.param(
            "lm_head", nn.initializers.lecun_normal(),
            (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype))
        out.update(hidden=x, lm_head=head)
        for name, over_layers in zip(COUNTERS, (jnp.mean, jnp.mean, jnp.max)):
            found = [c[name] for c in collected if name in c]
            out[name] = (over_layers(jnp.concatenate(found))
                         if found else jnp.float32(0.0))
        if cfg.router_aux_loss_coef:
            out["moe_aux"] = cfg.router_aux_loss_coef * jnp.sum(
                jnp.concatenate([c["moe_aux"] for c in collected
                                 if "moe_aux" in c]))
        if MAMBA in cfg.layer_types:
            out["ssm_chunks_reset_share"] = chunks_reset_share(
                seg, cfg.ssm_chunk)
            out["ssm_state_abs_max"] = jnp.concatenate(
                [c["ssm_state_abs_max"] for c in collected
                 if "ssm_state_abs_max" in c])[-1]
        s = ids.shape[1]
        out["attn_pairs_share"] = jnp.mean(
            jnp.sum(pos.astype(jnp.float32) + 1.0, axis=1)) / (s * (s + 1) / 2)
        # what the flash kernels walk of the triangle, by their own predicate
        out["attn_blocks_walked_share"] = attn_blocks_walked_share(seg)
        # and, of those, what needs its mask (dQ and dK/dV leave it out elsewhere)
        out["attn_blocks_masked_share"] = attn_blocks_masked_share(seg)
        if SLIDING in cfg.layer_types:
            w = cfg.sliding_window
            out["window_pairs_share"] = jnp.mean(jnp.sum(jnp.minimum(
                pos + 1, w).astype(jnp.float32), axis=1)) / (s * w)
            out["window_blocks_walked_share"] = window_blocks_walked_share(
                seg, w)
        return out


def hybrid_decoder_rules(cfg: HybridDecoderConfig, *, fsdp: bool = True,
                         fsdp_min_size: int = 2 ** 14) -> ShardingRules:
    """Batch-parallel layouts only: the tied embedding over ``tensor`` by its
    rows, auto-FSDP over the largest dim of what is left. The experts a
    module holds are ITS rank's; exchanging tokens between ranks over
    ``expert`` is not built (ROADMAP queue 2, A.1)."""
    del cfg
    rules = ((r"token_embed/embedding", P("tensor", None)),
             (r"lm_head", P(None, "tensor")))
    return ShardingRules(rules=rules, fsdp=fsdp, fsdp_min_size=fsdp_min_size)
