"""Fused 1×1-conv + BatchNorm-statistics Pallas kernel (ResNet byte diet).

VERDICT r2 missing-#2 / next-#2: ResNet-50 b=256 is HBM-bound on v5e — XLA
cost analysis shows 72.9 GiB accessed/step and the device trace puts 47.8%
of step time in BN-statistics reductions (whole-activation reads producing
[C] vectors). The byte-minimal schedule XLA can reach for a conv→BN pair is

    conv writes act (S bytes) → stats pass reads act (S) → apply pass
    reads act + writes out (2S)

because the statistics reduction is a *separate kernel* from the conv. The
only way below 4S is to compute the statistics while the conv output is
still in VMEM — a conv-epilogue fusion XLA does not perform. A competitive
general conv kernel is out of scope, but **two thirds of ResNet-50's
bottleneck convs are 1×1** — i.e. plain matmuls over a [B·H·W, Cin] view —
and their outputs (the 4×-width conv3 expansions) are the fattest
activations in the network. This module provides:

- :func:`matmul_stats` — a Pallas TPU matmul ``[M,K]@[K,N]`` that also
  emits per-column ``sum`` and ``sum of squares`` of the output from the
  epilogue, before the result ever leaves VMEM. The stats pass (S bytes of
  HBM read per fused pair) disappears: 4S → 3S on the forward.
- :class:`Conv1x1BN` — a drop-in flax module replacing the
  ``nn.Conv(1×1) → nn.BatchNorm`` pair (stride-1, train mode), with a
  reference XLA chain (``fused=False``) proving numerics identical.

Backward is intentionally plain XLA: the custom VJP folds the stats
cotangents into an effective dY (``dY + ds1 + 2·Y·ds2``, elementwise — XLA
fuses it into the dX/dW matmul reads) so autodiff through mean/var works
exactly; no behavior change vs the unfused chain beyond fp reassociation.

Mosaic tiling mirrors ops/flash_attention.py (verified rules: block dims
divisible by (8, 128) or equal to the full array dim; stats ride a
[num_m_blocks, N] partial-sum array reduced by one cheap XLA sum).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl

from distributeddeeplearningspark_tpu.utils.env import pallas_interpret


def _grid_params(*semantics: str):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics)


def _vmem():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM


def _mm_stats_kernel(x_ref, w_ref, y_ref, s1_ref, s2_ref, acc_ref,
                     *, nk: int, out_dtype):
    """Grid (mi, ni, ki), ki innermost sequential: accumulate the [bm, bn]
    product in VMEM; on the last K step write Y and its per-column partial
    sum / sum-of-squares — the epilogue reads the accumulator, not HBM."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # operands stay in their input dtype (bf16 feeds the MXU at full rate);
    # accumulation is f32 via preferred_element_type — casting the inputs
    # up would run the matmul at f32 MXU throughput and cancel the HBM win
    acc_ref[:] += jax.lax.dot_general(
        x_ref[:], w_ref[:],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _epilogue():
        y = acc_ref[:]
        y_ref[:] = y.astype(out_dtype)
        # stats from the f32 accumulator (flax BN upcasts stats to f32 too).
        # Partial sums travel [nm, 8, N] with the value replicated over the
        # size-8 sublane dim — the same Mosaic block-rule trick as
        # flash_attention's STAT_LANES: a (1, bn) block of an [nm, N] array
        # would put blocksize 1 in the sublane dim (1 ∤ 8, 1 ≠ nm → illegal).
        s1_ref[0] = jnp.broadcast_to(jnp.sum(y, axis=0)[None, :],
                                     s1_ref.shape[1:])
        s2_ref[0] = jnp.broadcast_to(jnp.sum(y * y, axis=0)[None, :],
                                     s2_ref.shape[1:])


def _matmul_stats_fwd(x, w, *, block_m, block_n, block_k, interpret):
    m, k = x.shape
    _, n = w.shape
    nm, nn_, nk = m // block_m, n // block_n, k // block_k
    y, ps1, ps2 = pl.pallas_call(
        functools.partial(_mm_stats_kernel, nk=nk, out_dtype=x.dtype),
        grid=(nm, nn_, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda mi, ni, ki: (mi, ni)),
            pl.BlockSpec((1, 8, block_n), lambda mi, ni, ki: (mi, 0, ni)),
            pl.BlockSpec((1, 8, block_n), lambda mi, ni, ki: (mi, 0, ni)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((nm, 8, n), jnp.float32),
            jax.ShapeDtypeStruct((nm, 8, n), jnp.float32),
        ],
        scratch_shapes=[_vmem()((block_m, block_n), jnp.float32)],
        compiler_params=_grid_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, w)
    # one tiny XLA reduce over the m-block partials: [nm, 8, N] → [N]
    return y, ps1[:, 0, :].sum(axis=0), ps2[:, 0, :].sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def matmul_stats(x, w, block_m=512, block_n=512, block_k=512,
                 interpret=None):
    """``y = x @ w`` plus per-column ``(sum(y), sum(y²))`` from the epilogue.

    x: [M, K], w: [K, N] (bf16 or f32); y in x.dtype, stats f32. M/K/N must
    divide by the (clamped) block sizes. Differentiable; the stats
    cotangents fold into dY exactly (see module docstring).
    """
    y, s1, s2 = _matmul_stats(x, w, block_m, block_n, block_k, interpret)
    return y, s1, s2


def _resolve_blocks(m, k, n, block_m, block_n, block_k):
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"matmul_stats needs M/N/K divisible by blocks: "
            f"{(m, n, k)} vs {(bm, bn, bk)}")
    return bm, bn, bk


def can_fuse(m: int, k: int, n: int,
             block_m: int = 512, block_n: int = 512, block_k: int = 512) -> bool:
    """True when :func:`matmul_stats` accepts this shape — the ONE gate
    Conv1x1BN uses, so eligibility can never drift from what the kernel
    actually raises on. Also requires the Mosaic sublane minimum (m % 8)."""
    if m % 8:
        return False
    try:
        _resolve_blocks(m, k, n, block_m, block_n, block_k)
    except ValueError:
        return False
    return True


def _matmul_stats(x, w, block_m, block_n, block_k, interpret):
    m, k = x.shape
    k2, n = w.shape
    if k2 != k:
        raise ValueError(f"shape mismatch: {x.shape} @ {w.shape}")
    bm, bn, bk = _resolve_blocks(m, k, n, block_m, block_n, block_k)
    return _matmul_stats_fwd(x, w, block_m=bm, block_n=bn, block_k=bk,
                             interpret=pallas_interpret(interpret))


def _matmul_stats_vjp_fwd(x, w, block_m, block_n, block_k, interpret):
    y, s1, s2 = _matmul_stats(x, w, block_m, block_n, block_k, interpret)
    return (y, s1, s2), (x, w, y)


def _matmul_stats_vjp_bwd(block_m, block_n, block_k, interpret, res, g):
    x, w, y = res
    dy, ds1, ds2 = g
    # d/dY of (Y, sum(Y), sum(Y²)) contributions, folded elementwise: XLA
    # fuses this into the two matmul reads below, so no extra HBM pass
    dy_eff = (dy.astype(jnp.float32)
              + ds1[None, :]
              + 2.0 * y.astype(jnp.float32) * ds2[None, :])
    dx = jnp.dot(dy_eff, w.astype(jnp.float32).T,
                 preferred_element_type=jnp.float32).astype(x.dtype)
    dw = jnp.dot(x.astype(jnp.float32).T, dy_eff,
                 preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw


matmul_stats.defvjp(_matmul_stats_vjp_fwd, _matmul_stats_vjp_bwd)


class Conv1x1BN(nn.Module):
    """Fused ``1×1 conv → BatchNorm`` (stride 1) for NHWC activations.

    Drop-in for the ``nn.Conv(features, (1,1), use_bias=False) →
    nn.BatchNorm`` pair in ResNet bottlenecks. ``fused=True`` computes the
    conv as a Pallas matmul whose epilogue also emits the BN statistics
    (saving the separate whole-activation stats read); ``fused=False`` is
    the reference XLA chain with identical parameters and RNG — the parity
    tests diff the two. Eval mode (``use_running_average``) has no stats
    pass to save and always takes the XLA chain.

    Params live under this module's own name (``kernel``, ``scale``,
    ``bias`` + ``batch_stats/{mean,var}``) — leaf names match the unfused
    pair's, so name-pattern sharding rules apply unchanged; checkpoints of
    the unfused layout need a one-level re-nest to import.
    """

    features: int
    dtype: Any = jnp.bfloat16
    norm_dtype: Any = None
    momentum: float = 0.9
    epsilon: float = 1e-5
    fused: bool = True
    scale_init: Any = nn.initializers.ones

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool) -> jax.Array:
        b, h, w_, cin = x.shape
        cout = self.features
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (1, 1, cin, cout), jnp.float32)
        scale = self.param("scale", self.scale_init, (cout,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (cout,), jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((cout,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((cout,), jnp.float32))

        m_total = b * h * w_
        w2d = kernel.reshape(cin, cout).astype(self.dtype)
        xf = x.astype(self.dtype)
        use_fused = self.fused and train and can_fuse(m_total, cin, cout)
        if train:
            if use_fused:
                y2d, s1, s2 = matmul_stats(xf.reshape(m_total, cin), w2d)
                y = y2d.reshape(b, h, w_, cout)
                mean = s1 / m_total
                # E[y²] − E[y]² (the one-pass form; matches flax to fp)
                var = jnp.maximum(s2 / m_total - mean * mean, 0.0)
            else:
                y = jnp.dot(xf.reshape(m_total, cin), w2d,
                            preferred_element_type=jnp.float32)
                y = y.astype(self.dtype).reshape(b, h, w_, cout)
                yf = y.astype(jnp.float32)
                mean = jnp.mean(yf, axis=(0, 1, 2))
                var = jnp.maximum(
                    jnp.mean(yf * yf, axis=(0, 1, 2)) - mean * mean, 0.0)
            if not self.is_initializing():
                ra_mean.value = (self.momentum * ra_mean.value
                                 + (1 - self.momentum) * mean)
                # biased batch variance, matching flax nn.BatchNorm's
                # running-var update (normalization.py: no Bessel term)
                ra_var.value = (self.momentum * ra_var.value
                                + (1 - self.momentum) * var)
        else:
            y = jnp.dot(xf.reshape(m_total, cin), w2d,
                        preferred_element_type=jnp.float32)
            y = y.astype(self.dtype).reshape(b, h, w_, cout)
            mean, var = ra_mean.value, ra_var.value

        ndtype = self.norm_dtype if self.norm_dtype is not None else self.dtype
        rstd = jax.lax.rsqrt(var + self.epsilon)
        g = (scale * rstd).astype(ndtype)
        b_ = (bias - mean * scale * rstd).astype(ndtype)
        return (y.astype(ndtype) * g + b_).astype(self.dtype)
