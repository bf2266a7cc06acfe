"""The gated short convolution: a depthwise causal convolution of a few taps
between two gates, the operator of a hybrid decoder's convolution layers
(:mod:`..models.hybrid_decoder`), with its taps cut at document boundaries;
and, beside it, the UNGATED form with a bias and a SiLU that a state-space
layer runs before its scan (:func:`silu_short_conv`, plain XLA).

``bcx [B, S, 3C]`` is the block's input projection, ``(b, c, u) =
split3(bcx)`` along the last axis, ``w [C, K]`` the taps (depthwise, no
bias), ``seg [B, S]`` the document of every position (``None``: one
document a row)::

    v    = b * u
    z[t] = sum_{j < K} w[:, j] * v[t - (K-1) + j]   over the terms with
           t - (K-1) + j >= 0  and  seg[t - (K-1) + j] == seg[t]
    y    = c * z                                     [B, S, C]

Two Pallas kernels, ``shortconv_fwd`` and ``shortconv_bwd`` (the names the
trace shows). Each is ONE pass: forward reads ``bcx`` (its three column
blocks through three block specs of the same array), the segment ids and the
taps, and writes ``y``; backward reads the same and ``dy``, recomputes ``z``,
and writes the cotangent of ``bcx`` and the taps' gradient. Memory-bound by
nature (six elements moved a multiply-add); it is a kernel so that XLA cannot
make four passes of it and so that a trace can find the operator by name.

A block of ``block_t`` positions needs the ``K - 1`` positions before it
(forward, and backward for ``z`` and ``dw``) and after it (backward, for
``dv``): they come as the 8-row block that ends where this one starts, or
starts where it ends, through block specs of their own, and a sublane
rotation (``pltpu.roll``) brings them into place. The segment ids travel in
the row-statistics layout of the flash kernel (``[B, S, 8]`` int32): lane 0
the position's id, lanes ``1 .. K-1`` the ids of the positions before it,
lanes ``K .. 2K-2`` of those after it, with a sentinel past the row's ends,
so a tap is read where two lanes are equal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from distributeddeeplearningspark_tpu.ops.flash_attention import (
    STAT_LANES,   # lanes of the segment-id layout: 2K - 1 of them are used
    _grid_params,
    _vmem,
)
from distributeddeeplearningspark_tpu.parallel.mesh import BATCH_AXES
from distributeddeeplearningspark_tpu.utils.env import on_tpu, pallas_interpret

DEFAULT_BLOCK_T = 512
DEFAULT_BLOCK_C = 512
HALO = 8          # rows of the neighbouring block a kernel is handed
MAX_TAPS = (STAT_LANES + 1) // 2
#: ids of no document: before a row's first position, after its last
_BEFORE, _AFTER = -(2 ** 31), -(2 ** 31) + 1


def _segment_lanes(seg, b: int, s: int, taps: int):
    """``[B, S, 8]`` int32: the id of a position, of the ``taps - 1`` before
    it and of the ``taps - 1`` after it (module docstring)."""
    seg = (jnp.zeros((b, s), jnp.int32) if seg is None
           else jnp.asarray(seg, jnp.int32))
    lanes = [seg]
    for d in range(1, taps):
        lanes.append(jnp.pad(seg, ((0, 0), (d, 0)),
                             constant_values=_BEFORE)[:, :s])
    for d in range(1, taps):
        lanes.append(jnp.pad(seg, ((0, 0), (0, d)),
                             constant_values=_AFTER)[:, d:])
    lanes += [jnp.zeros_like(seg)] * (STAT_LANES - len(lanes))
    return jnp.stack(lanes, axis=-1)


def _taps_xla(v, w, segment_ids):
    """``z[t] = sum_j w[:, j] v[t - (K-1) + j]`` over the terms inside ``t``'s
    document: ``v [B, S, C]`` float32, ``w [C, K]`` -> float32 ``[B, S, C]``
    (the tap loop of both operators' XLA paths)."""
    s, taps = v.shape[1], w.shape[1]
    pos = jnp.arange(s)
    z = w[:, taps - 1].astype(jnp.float32) * v
    for d in range(1, taps):
        ok = (pos >= d)[None, :]
        if segment_ids is not None:
            seg = jnp.asarray(segment_ids)
            ok = ok & (jnp.pad(seg, ((0, 0), (d, 0)))[:, :s] == seg)
        shifted = jnp.pad(v, ((0, 0), (d, 0), (0, 0)))[:, :s]
        z = z + w[:, taps - 1 - d].astype(jnp.float32) * jnp.where(
            ok[..., None], shifted, 0.0)
    return z


def gated_short_conv_xla(bcx, w, segment_ids=None):
    """The operator in plain ``jax.numpy`` (float32 inside, the input's dtype
    out): the path off the TPU and for shapes the kernels do not take."""
    gate_b, gate_c, u = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    return (gate_c * _taps_xla(gate_b * u, w, segment_ids)).astype(bcx.dtype)


def silu_short_conv(v, w, bias, segment_ids=None):
    """The UNGATED form, a state-space layer's (:mod:`..models.
    hybrid_decoder`): ``v [B, S, C], w [C, K], bias [C], segment_ids [B, S]
    | None -> y [B, S, C]``, ``y[t] = silu(bias + sum_j w[:, j] v[t - (K-1) +
    j])`` over the terms inside ``t``'s document; float32 inside, ``v``'s
    dtype out, differentiable in ``v``, ``w`` and ``bias``. Plain
    ``jax.numpy`` on the TPU too, and it costs what that costs: at 16,384 x
    6,144 the chip's trace shows seven passes over float32 arrays of 0.4 GB
    a layer and step, forward, replay and backward (47 ms a step where one
    read of ``v`` and one write of ``y`` and of each cotangent need 3:
    PERF.md sections 5 and 6, PR 37). A kernel beside the gated form's is
    the next step; it was left out of the PR that brought the layer."""
    if v.shape[-1] != w.shape[0] or bias.shape != w.shape[:1]:
        raise ValueError(f"v {v.shape} against taps {w.shape} and bias "
                         f"{bias.shape}: want [B, S, C], [C, K] and [C]")
    z = _taps_xla(v.astype(jnp.float32), w, segment_ids)
    return jax.nn.silu(z + bias.astype(jnp.float32)).astype(v.dtype)


def _roll(x, shift: int, interpret: bool):
    """``x`` rotated ``shift`` rows towards higher indices."""
    if interpret:
        return jnp.roll(x, shift, axis=0)
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift % x.shape[0], 0)


def _before(v, halo, d: int, row, interpret: bool):
    """``v[t - d]`` for the rows of a block: ``v`` rotated, its first ``d``
    rows from the last ``d`` of ``halo`` (the 8 rows before the block)."""
    out = _roll(v, d, interpret)
    for r in range(d):
        out = jnp.where(row == r, halo[HALO - d + r:HALO - d + r + 1, :], out)
    return out


def _after(v, halo, d: int, row, interpret: bool):
    """``v[t + d]``: the last ``d`` rows from the first ``d`` of ``halo``
    (the 8 rows after the block)."""
    n = v.shape[0]
    out = _roll(v, n - d, interpret)
    for r in range(d):
        out = jnp.where(row == n - d + r, halo[r:r + 1, :], out)
    return out


def _conv(v, halo_v, seg, w, row, *, taps: int, interpret: bool):
    """``(z, [v, v[t-1] masked, v[t-2] masked, ...])`` of one block."""
    terms = [v]
    z = w[taps - 1:taps, :] * v
    for d in range(1, taps):
        ok = seg[:, 0:1] == seg[:, d:d + 1]
        vd = jnp.where(ok, _before(v, halo_v, d, row, interpret), 0.0)
        terms.append(vd)
        z = z + w[taps - 1 - d:taps - d, :] * vd
    return z, terms


def _fwd_kernel(b_ref, c_ref, u_ref, hb_ref, hu_ref, seg_ref, w_ref, y_ref, *,
                taps: int, interpret: bool):
    f32 = jnp.float32
    v = b_ref[0].astype(f32) * u_ref[0].astype(f32)           # [T, C]
    halo_v = hb_ref[0].astype(f32) * hu_ref[0].astype(f32)    # [8, C]
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    z, _ = _conv(v, halo_v, seg_ref[0], w_ref[...], row, taps=taps,
                 interpret=interpret)
    y_ref[0] = (c_ref[0].astype(f32) * z).astype(y_ref.dtype)


def _bwd_kernel(b_ref, c_ref, u_ref, dy_ref, hb_ref, hu_ref, nc_ref, ndy_ref,
                seg_ref, w_ref, dbcx_ref, dw_ref, dc_keep, du_keep, *,
                taps: int, interpret: bool):
    """Grid ``(batch, channel block, position block, part)``: the last axis
    walks the three column blocks of ``dbcx`` (db, dc, du). Everything is
    computed at part 0, which writes db and keeps dc and du in VMEM for the
    two steps after it; the inputs' block indices do not depend on the part,
    so nothing is fetched again. ``dw`` stays in place over the position
    blocks of a (batch, channel block) and is summed over the batch outside.
    """
    f32 = jnp.float32
    i, part = pl.program_id(2), pl.program_id(3)

    @pl.when(part == 0)
    def _compute():
        gate_b, gate_c = b_ref[0].astype(f32), c_ref[0].astype(f32)
        u, dy = u_ref[0].astype(f32), dy_ref[0].astype(f32)
        seg, w = seg_ref[0], w_ref[...]
        v = gate_b * u
        row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        z, terms = _conv(v, hb_ref[0].astype(f32) * hu_ref[0].astype(f32),
                         seg, w, row, taps=taps, interpret=interpret)
        dz = dy * gate_c
        halo_dz = ndy_ref[0].astype(f32) * nc_ref[0].astype(f32)
        dv = w[taps - 1:taps, :] * dz
        for d in range(1, taps):
            ok = seg[:, 0:1] == seg[:, taps - 1 + d:taps + d]
            dv = dv + w[taps - 1 - d:taps - d, :] * jnp.where(
                ok, _after(dz, halo_dz, d, row, interpret), 0.0)
        dbcx_ref[0] = (dv * u).astype(dbcx_ref.dtype)
        dc_keep[...] = (dy * z).astype(dc_keep.dtype)
        du_keep[...] = (dv * gate_b).astype(du_keep.dtype)
        tap_row = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape[1:], 0)
        dw = jnp.zeros(dw_ref.shape[1:], f32)
        for d, term in enumerate(terms):
            dw = dw + jnp.where(tap_row == taps - 1 - d,
                                jnp.sum(dz * term, axis=0, keepdims=True), 0.0)

        @pl.when(i == 0)
        def _first():
            dw_ref[0] = dw

        @pl.when(i != 0)
        def _later():
            dw_ref[0] += dw

    @pl.when(part == 1)
    def _dc():
        dbcx_ref[0] = dc_keep[...]

    @pl.when(part == 2)
    def _du():
        dbcx_ref[0] = du_keep[...]


def _blocks(s: int, c: int, block_t: int, block_c: int) -> tuple[int, int]:
    return min(block_t, s), min(block_c, c)


def _check(bcx, w, block_t: int, block_c: int) -> None:
    _, s, c3 = bcx.shape
    c, taps = w.shape
    if c3 != 3 * c or not 2 <= taps <= MAX_TAPS:
        raise ValueError(f"bcx {bcx.shape} against taps {w.shape}: want "
                         f"[B, S, 3C] and [C, K], 2 <= K <= {MAX_TAPS}")
    if s % block_t or c % block_c or block_t % 16 or block_c % 128:
        raise ValueError(f"positions {s} and channels {c} must divide by the "
                         f"blocks ({block_t}, {block_c}), and those by "
                         f"(16, 128)")


def _padded_taps(w):
    """``[C, K]`` -> ``[8, C]`` float32, tap ``j`` in row ``j``."""
    return jnp.zeros((STAT_LANES, w.shape[0]), jnp.float32).at[
        :w.shape[1]].set(w.astype(jnp.float32).T)


def _shortconv_fwd(bcx, w, lanes, *, block_t, block_c, interpret):
    b, s, c3 = bcx.shape
    c, taps = w.shape
    nc, per = c // block_c, block_t // HALO
    blk = lambda part: pl.BlockSpec(
        (1, block_t, block_c), lambda bb, i, j: (bb, i, part * nc + j))
    before = lambda part: pl.BlockSpec(
        (1, HALO, block_c),
        lambda bb, i, j: (bb, jnp.maximum(i * per - 1, 0), part * nc + j))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, interpret=interpret),
        grid=(b, s // block_t, nc),
        in_specs=[blk(0), blk(1), blk(2), before(0), before(2),
                  pl.BlockSpec((1, block_t, STAT_LANES),
                               lambda bb, i, j: (bb, i, 0)),
                  pl.BlockSpec((STAT_LANES, block_c),
                               lambda bb, i, j: (0, j))],
        out_specs=pl.BlockSpec((1, block_t, block_c),
                               lambda bb, i, j: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, c), bcx.dtype),
        compiler_params=_grid_params("parallel", "parallel", "parallel"),
        interpret=interpret, name="shortconv_fwd",
    )(bcx, bcx, bcx, bcx, bcx, lanes, _padded_taps(w))


def _shortconv_bwd(bcx, w, lanes, dy, *, block_t, block_c, interpret):
    b, s, c3 = bcx.shape
    c, taps = w.shape
    nc, per, last = c // block_c, block_t // HALO, s // HALO - 1
    blk = lambda part: pl.BlockSpec(
        (1, block_t, block_c), lambda bb, j, i, p: (bb, i, part * nc + j))
    before = lambda part: pl.BlockSpec(
        (1, HALO, block_c),
        lambda bb, j, i, p: (bb, jnp.maximum(i * per - 1, 0), part * nc + j))
    after = lambda part: pl.BlockSpec(
        (1, HALO, block_c),
        lambda bb, j, i, p: (bb, jnp.minimum((i + 1) * per, last),
                             part * nc + j))
    vmem = _vmem()
    dbcx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, interpret=interpret),
        grid=(b, nc, s // block_t, 3),
        in_specs=[blk(0), blk(1), blk(2), blk(0),            # b, c, u, dy
                  before(0), before(2), after(1), after(0),  # halos
                  pl.BlockSpec((1, block_t, STAT_LANES),
                               lambda bb, j, i, p: (bb, i, 0)),
                  pl.BlockSpec((STAT_LANES, block_c),
                               lambda bb, j, i, p: (0, j))],
        out_specs=[
            pl.BlockSpec((1, block_t, block_c),
                         lambda bb, j, i, p: (bb, i, p * nc + j)),
            pl.BlockSpec((1, STAT_LANES, block_c),
                         lambda bb, j, i, p: (bb, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((b, s, c3), bcx.dtype),
                   jax.ShapeDtypeStruct((b, STAT_LANES, c), jnp.float32)],
        scratch_shapes=[vmem((block_t, block_c), bcx.dtype),
                        vmem((block_t, block_c), bcx.dtype)],
        compiler_params=_grid_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=interpret, name="shortconv_bwd",
    )(bcx, bcx, bcx, dy, bcx, bcx, bcx, dy, lanes, _padded_taps(w))
    return dbcx, jnp.sum(dw, axis=0)[:taps].T.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _shortconv(bcx, w, lanes, block_t, block_c, interpret):
    return _shortconv_fwd(bcx, w, lanes, block_t=block_t, block_c=block_c,
                          interpret=interpret)


def _shortconv_vjp_fwd(bcx, w, lanes, block_t, block_c, interpret):
    return _shortconv(bcx, w, lanes, block_t, block_c, interpret), (
        bcx, w, lanes)


def _shortconv_vjp_bwd(block_t, block_c, interpret, res, dy):
    bcx, w, lanes = res
    dbcx, dw = _shortconv_bwd(bcx, w, lanes, dy.astype(bcx.dtype),
                              block_t=block_t, block_c=block_c,
                              interpret=interpret)
    return dbcx, dw, None


_shortconv.defvjp(_shortconv_vjp_fwd, _shortconv_vjp_bwd)


def gated_short_conv_pallas(bcx, w, segment_ids=None, *,
                            block_t: int = DEFAULT_BLOCK_T,
                            block_c: int = DEFAULT_BLOCK_C,
                            interpret: bool | None = None):
    """The two kernels; differentiable in ``bcx`` and ``w`` (custom VJP)."""
    b, s, _ = bcx.shape
    block_t, block_c = _blocks(s, w.shape[0], block_t, block_c)
    _check(bcx, w, block_t, block_c)
    return _shortconv(bcx, w, _segment_lanes(segment_ids, b, s, w.shape[1]),
                      block_t, block_c, pallas_interpret(interpret))


def gated_short_conv(bcx, w, segment_ids=None, *, impl: str = "auto"):
    """``bcx [B, S, 3C], w [C, K], segment_ids [B, S] | None -> y [B, S, C]``
    (module docstring). ``impl``: ``"pallas"``, ``"xla"``, or ``"auto"``: the
    kernels on a TPU when the shape divides by their blocks, else XLA. On a
    mesh of more than one device the kernels run in a ``shard_map`` over the
    batch rows (GSPMD cannot partition a Mosaic call, as for the flash
    kernel); the taps' gradient is summed over the rows' ranks by the
    ``shard_map``'s transpose."""
    from distributeddeeplearningspark_tpu.ops.ring_attention import resolve_mesh

    b, s, _ = bcx.shape
    c, taps = w.shape
    if impl == "auto":
        block_t, block_c = _blocks(s, c, DEFAULT_BLOCK_T, DEFAULT_BLOCK_C)
        fits = not (s % block_t or c % block_c or block_t % 16
                    or block_c % 128 or taps > MAX_TAPS)
        impl = "pallas" if on_tpu() and fits else "xla"
    if impl == "xla":
        return gated_short_conv_xla(bcx, w, segment_ids)
    if impl != "pallas":
        raise ValueError(f"unknown short-convolution impl {impl!r}")
    mesh = resolve_mesh()
    if mesh is None or mesh.size == 1:
        return gated_short_conv_pallas(bcx, w, segment_ids)
    rows = mesh.shape[BATCH_AXES[0]] * mesh.shape[BATCH_AXES[1]]
    if b % rows:
        raise ValueError(f"short convolution on mesh {dict(mesh.shape)}: "
                         f"batch {b} must divide by data x fsdp")
    seg = (jnp.zeros((b, s), jnp.int32) if segment_ids is None
           else jnp.asarray(segment_ids, jnp.int32))
    return jax.shard_map(
        gated_short_conv_pallas, mesh=mesh,
        in_specs=(P(BATCH_AXES, None, None), P(), P(BATCH_AXES, None)),
        out_specs=P(BATCH_AXES, None, None), check_vma=False)(bcx, w, seg)
