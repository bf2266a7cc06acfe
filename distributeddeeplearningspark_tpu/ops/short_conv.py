"""The gated short convolution: a depthwise causal convolution of a few taps
between two gates, the operator of a hybrid decoder's convolution layers
(:mod:`..models.hybrid_decoder`), with its taps cut at document boundaries;
and, beside it, the UNGATED form with a bias and a SiLU that a state-space
layer runs before its scan (:func:`silu_short_conv`, two kernels of its own:
the end of this docstring).

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

The ungated form, ``y = silu(bias + z)`` with ``z`` over ``v`` itself, has
two kernels of its own, ``mamba_conv_fwd`` and ``mamba_conv_bwd``, that share
only the segment-id lanes and the rotation with the gated ones (two gates and
no bias there, a bias and a SiLU and no gate here). They run on ``v`` with
its POSITIONS MINOR, ``[B, C, S]``, blocks of ``block_c`` channels (sublanes)
by ``block_t`` positions (lanes): XLA lays out the state-space layer's input
projection, and every array of the layer around the convolution, with the
positions minor, so the transposes either side of the kernels are changes of
layout that cost nothing, where kernels on ``[B, S, C]`` made XLA lay the
whole layer out the other way and add relayouts of the scan's inputs and
outputs that cost what the kernels saved (PERF.md section 6). A shift
along the positions is a lane rotation, and a block's halos are the 128
positions before it and after it. Forward reads ``v`` and writes ``y`` in
``v``'s dtype, float32 inside. Backward reads ``v`` and ``dy``, recomputes
``z``, forms ``dz = dy silu'(z + bias)`` and writes ``dv``. Since ``dv[t]``
needs ``dz`` at ``t + 1 .. t + K-1``, its after-halo carries ``v`` AND ``dy``
of the 128 positions after the block, with those positions' segment ids:
``z`` there comes from them and the block's own last positions, under the
same mask. The segment ids travel as ``[B, 8, S]``, the gated form's lanes
turned to rows. The taps and the bias travel as one ``[C, 8]`` float32 array,
tap ``j`` in column ``j`` and the bias in column ``K`` (the bias lane, free
while ``K <= MAX_TAPS``); the taps' and the bias's gradients come back in the
same columns of a ``[B, C, 8]`` side output, summed over the position blocks
in place and over the batch outside.
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
LANES = 128       # positions of it, where the positions are the lanes
#: the ungated kernels' blocks, positions by channels: long and narrow, so a
#: block's halos of LANES positions are a small share of what it reads
#: (tools/mamba_conv_cost.py times others)
MAMBA_BLOCK_T, MAMBA_BLOCK_C = 4096, 64
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


def silu_short_conv_xla(v, w, bias, segment_ids=None):
    """The ungated form in plain ``jax.numpy`` (float32 inside, ``v``'s dtype
    out): the path off the TPU and for shapes the kernels do not take."""
    z = _taps_xla(v.astype(jnp.float32), w, segment_ids)
    return jax.nn.silu(z + bias.astype(jnp.float32)).astype(v.dtype)


def _roll(x, shift: int, interpret: bool, axis: int = 0):
    """``x`` rotated ``shift`` rows (or columns) towards higher indices."""
    if interpret:
        return jnp.roll(x, shift, axis=axis)
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift % x.shape[axis], axis)


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


def _route(impl: str, fits: bool) -> str:
    """``impl`` resolved: ``"auto"`` is the kernels on a TPU where the shape
    ``fits`` their blocks, else XLA."""
    if impl == "auto":
        impl = "pallas" if on_tpu() and fits else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown short-convolution impl {impl!r}")
    return impl


def _over_batch_rows(kernels, x, params, segment_ids):
    """``kernels(x, *params, segment_ids)``; on a mesh of more than one device
    in a ``shard_map`` over the batch rows (GSPMD cannot partition a Mosaic
    call, as for the flash kernel), ``params`` whole on every rank, their
    gradients summed over the rows' ranks by the ``shard_map``'s
    transpose."""
    from distributeddeeplearningspark_tpu.ops.ring_attention import resolve_mesh

    mesh = resolve_mesh()
    if mesh is None or mesh.size == 1:
        return kernels(x, *params, segment_ids)
    b, s = x.shape[:2]
    rows = mesh.shape[BATCH_AXES[0]] * mesh.shape[BATCH_AXES[1]]
    if b % rows:
        raise ValueError(f"short convolution on mesh {dict(mesh.shape)}: "
                         f"batch {b} must divide by data x fsdp")
    seg = (jnp.zeros((b, s), jnp.int32) if segment_ids is None
           else jnp.asarray(segment_ids, jnp.int32))
    return jax.shard_map(
        kernels, mesh=mesh,
        in_specs=(P(BATCH_AXES, None, None), *[P()] * len(params),
                  P(BATCH_AXES, None)),
        out_specs=P(BATCH_AXES, None, None), check_vma=False)(x, *params, seg)


def gated_short_conv(bcx, w, segment_ids=None, *, impl: str = "auto"):
    """``bcx [B, S, 3C], w [C, K], segment_ids [B, S] | None -> y [B, S, C]``
    (module docstring). ``impl``: ``"pallas"``, ``"xla"``, or ``"auto"``: the
    kernels on a TPU when the shape divides by their blocks, else XLA. On a
    mesh of more than one device the kernels run in a ``shard_map`` over the
    batch rows."""
    s, (c, taps) = bcx.shape[1], w.shape
    block_t, block_c = _blocks(s, c, DEFAULT_BLOCK_T, DEFAULT_BLOCK_C)
    fits = not (s % block_t or c % block_c or block_t % 16
                or block_c % 128 or taps > MAX_TAPS)
    if _route(impl, fits) == "xla":
        return gated_short_conv_xla(bcx, w, segment_ids)
    return _over_batch_rows(gated_short_conv_pallas, bcx, (w,), segment_ids)


# -- the ungated form: a bias and a SiLU, no gate -----------------------------

def _silu_grad(u):
    """``d silu(u) / du``, float32."""
    sig = jax.nn.sigmoid(u)
    return sig * (1.0 + u * (1.0 - sig))


def _conv_lanes(ext, seg, wb, *, taps: int, interpret: bool):
    """``(z, [v, v[t-1] masked, v[t-2] masked, ...])`` of one block ``v [C,
    T]``: ``ext [C, LANES + T]`` is the ``LANES`` positions before the block
    and then the block, so ``v[t - d]`` is ``ext`` rotated ``d`` lanes;
    ``seg [8, T]`` the block's segment-id rows, ``wb [C, 8]`` taps and
    bias."""
    v = ext[:, LANES:]
    terms = [v]
    z = wb[:, taps - 1:taps] * v
    for d in range(1, taps):
        ok = seg[0:1, :] == seg[d:d + 1, :]
        vd = jnp.where(ok, _roll(ext, d, interpret, axis=1)[:, LANES:], 0.0)
        terms.append(vd)
        z = z + wb[:, taps - 1 - d:taps - d] * vd
    return z, terms


def _silu_fwd_kernel(v_ref, hv_ref, seg_ref, wb_ref, y_ref, *, taps: int,
                     interpret: bool):
    f32 = jnp.float32
    wb = wb_ref[...]
    ext = jnp.concatenate([hv_ref[0], v_ref[0]], axis=1).astype(f32)
    z, _ = _conv_lanes(ext, seg_ref[0], wb, taps=taps, interpret=interpret)
    u = z + wb[:, taps:taps + 1]
    y_ref[0] = (u * jax.nn.sigmoid(u)).astype(y_ref.dtype)


def _silu_bwd_kernel(v_ref, dy_ref, hv_ref, nv_ref, ndy_ref, seg_ref,
                     nseg_ref, wb_ref, dv_ref, dwb_ref, *, taps: int,
                     interpret: bool):
    """Grid ``(batch, channel block, position block)``. ``dwb`` (the taps'
    and the bias's gradients, module docstring) stays in place over the
    position blocks of a (batch, channel block) and is summed over the batch
    outside."""
    f32 = jnp.float32
    i = pl.program_id(2)
    seg, wb = seg_ref[0], wb_ref[...]
    bias = wb[:, taps:taps + 1]
    ext = jnp.concatenate([hv_ref[0], v_ref[0]], axis=1).astype(f32)
    z, terms = _conv_lanes(ext, seg, wb, taps=taps, interpret=interpret)
    dz = dy_ref[0].astype(f32) * _silu_grad(z + bias)
    # dz of the LANES positions after the block: their z from them and the
    # block's own last positions
    n = dz.shape[1]
    z_after, _ = _conv_lanes(
        jnp.concatenate([ext[:, n:], nv_ref[0].astype(f32)], axis=1),
        nseg_ref[0], wb, taps=taps, interpret=interpret)
    dz_ext = jnp.concatenate(
        [dz, ndy_ref[0].astype(f32) * _silu_grad(z_after + bias)], axis=1)
    dv = wb[:, taps - 1:taps] * dz
    for d in range(1, taps):                  # dz[t + d]: dz_ext rotated back
        ok = seg[0:1, :] == seg[taps - 1 + d:taps + d, :]
        dv = dv + wb[:, taps - 1 - d:taps - d] * jnp.where(
            ok, _roll(dz_ext, n + LANES - d, interpret, axis=1)[:, :n], 0.0)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, dwb_ref.shape[1:], 1)
    dwb = jnp.where(lane == taps, jnp.sum(dz, axis=1, keepdims=True), 0.0)
    for d, term in enumerate(terms):
        dwb = dwb + jnp.where(lane == taps - 1 - d,
                              jnp.sum(dz * term, axis=1, keepdims=True), 0.0)

    @pl.when(i == 0)
    def _first():
        dwb_ref[0] = dwb

    @pl.when(i != 0)
    def _later():
        dwb_ref[0] += dwb


def _silu_misfit(s: int, c: int, taps: int, block_t: int,
                 block_c: int) -> str | None:
    """Why the kernels cannot take the shape, or None."""
    if taps > MAX_TAPS:
        return f"taps: want K <= {MAX_TAPS}, got {taps}"
    if s % block_t or c % block_c or block_t % LANES or block_c % 16:
        return (f"positions {s} and channels {c} must divide by the blocks "
                f"({block_t}, {block_c}), and those by ({LANES}, 16)")
    return None


def _mamba_conv_fwd(vt, wb, lanes, *, taps, block_t, block_c, interpret):
    b, c, s = vt.shape
    per = block_t // LANES
    return pl.pallas_call(
        functools.partial(_silu_fwd_kernel, taps=taps, interpret=interpret),
        grid=(b, s // block_t, c // block_c),
        in_specs=[pl.BlockSpec((1, block_c, block_t),
                               lambda bb, i, j: (bb, j, i)),
                  pl.BlockSpec((1, block_c, LANES),
                               lambda bb, i, j: (
                                   bb, j, jnp.maximum(i * per - 1, 0))),
                  pl.BlockSpec((1, STAT_LANES, block_t),
                               lambda bb, i, j: (bb, 0, i)),
                  pl.BlockSpec((block_c, STAT_LANES),
                               lambda bb, i, j: (j, 0))],
        out_specs=pl.BlockSpec((1, block_c, block_t),
                               lambda bb, i, j: (bb, j, i)),
        out_shape=jax.ShapeDtypeStruct((b, c, s), vt.dtype),
        compiler_params=_grid_params("parallel", "parallel", "parallel"),
        interpret=interpret, name="mamba_conv_fwd",
    )(vt, vt, lanes, wb)


def _mamba_conv_bwd(vt, wb, lanes, dyt, *, taps, block_t, block_c,
                    interpret):
    b, c, s = vt.shape
    per, last = block_t // LANES, s // LANES - 1
    nxt = lambda i: jnp.minimum((i + 1) * per, last)
    blk = pl.BlockSpec((1, block_c, block_t), lambda bb, j, i: (bb, j, i))
    before = pl.BlockSpec(
        (1, block_c, LANES),
        lambda bb, j, i: (bb, j, jnp.maximum(i * per - 1, 0)))
    after = pl.BlockSpec((1, block_c, LANES),
                         lambda bb, j, i: (bb, j, nxt(i)))
    dvt, dwb = pl.pallas_call(
        functools.partial(_silu_bwd_kernel, taps=taps, interpret=interpret),
        grid=(b, c // block_c, s // block_t),
        in_specs=[blk, blk, before, after, after,             # v, dy, halos
                  pl.BlockSpec((1, STAT_LANES, block_t),
                               lambda bb, j, i: (bb, 0, i)),
                  pl.BlockSpec((1, STAT_LANES, LANES),        # ids after
                               lambda bb, j, i: (bb, 0, nxt(i))),
                  pl.BlockSpec((block_c, STAT_LANES),
                               lambda bb, j, i: (j, 0))],
        out_specs=[blk, pl.BlockSpec((1, block_c, STAT_LANES),
                                     lambda bb, j, i: (bb, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, c, s), vt.dtype),
                   jax.ShapeDtypeStruct((b, c, STAT_LANES), jnp.float32)],
        compiler_params=_grid_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name="mamba_conv_bwd",
    )(vt, dyt, vt, vt, dyt, lanes, lanes, wb)
    return dvt, jnp.sum(dwb, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _mamba_conv(vt, wb, lanes, taps, block_t, block_c, interpret):
    return _mamba_conv_fwd(vt, wb, lanes, taps=taps, block_t=block_t,
                           block_c=block_c, interpret=interpret)


def _mamba_conv_vjp_fwd(vt, wb, lanes, taps, block_t, block_c, interpret):
    return _mamba_conv(vt, wb, lanes, taps, block_t, block_c, interpret), (
        vt, wb, lanes)


def _mamba_conv_vjp_bwd(taps, block_t, block_c, interpret, res, dyt):
    vt, wb, lanes = res
    dvt, dwb = _mamba_conv_bwd(vt, wb, lanes, dyt.astype(vt.dtype),
                               taps=taps, block_t=block_t, block_c=block_c,
                               interpret=interpret)
    return dvt, dwb, None


_mamba_conv.defvjp(_mamba_conv_vjp_fwd, _mamba_conv_vjp_bwd)


def silu_short_conv_pallas(v, w, bias, segment_ids=None, *,
                           block_t: int = MAMBA_BLOCK_T,
                           block_c: int = MAMBA_BLOCK_C,
                           interpret: bool | None = None):
    """The two kernels; differentiable in ``v``, ``w`` and ``bias`` (custom
    VJP of the kernels in ``v`` and the taps-and-bias array, whose own
    transpose hands each its columns). They run on ``v`` with its positions
    minor, the layout XLA gives the layer's input projection, so each
    ``swapaxes`` here is free (module docstring)."""
    b, s, c = v.shape
    taps = w.shape[1]
    block_t, block_c = _blocks(s, c, block_t, block_c)
    misfit = _silu_misfit(s, c, taps, block_t, block_c)
    if misfit:
        raise ValueError(misfit)
    wb = _padded_taps(w).at[taps].set(bias.astype(jnp.float32)).T
    lanes = jnp.swapaxes(_segment_lanes(segment_ids, b, s, taps), 1, 2)
    yt = _mamba_conv(jnp.swapaxes(v, 1, 2), wb, lanes, taps, block_t,
                     block_c, pallas_interpret(interpret))
    return jnp.swapaxes(yt, 1, 2)


def silu_short_conv(v, w, bias, segment_ids=None, *, impl: str = "auto"):
    """The UNGATED form, a state-space layer's (:mod:`..models.
    hybrid_decoder`): ``v [B, S, C], w [C, K], bias [C], segment_ids [B, S]
    | None -> y [B, S, C]``, ``y[t] = silu(bias + sum_j w[:, j] v[t - (K-1) +
    j])`` over the terms inside ``t``'s document; float32 inside, ``v``'s
    dtype out, differentiable in ``v``, ``w`` and ``bias``. ``impl`` as
    :func:`gated_short_conv`'s: ``"auto"`` is the kernels ``mamba_conv_fwd``
    and ``mamba_conv_bwd`` (module docstring) on a TPU when the shape divides
    by their blocks, else :func:`silu_short_conv_xla`; on a mesh of more
    than one device the kernels run in a ``shard_map`` over the batch
    rows."""
    if v.shape[-1] != w.shape[0] or bias.shape != w.shape[:1]:
        raise ValueError(f"v {v.shape} against taps {w.shape} and bias "
                         f"{bias.shape}: want [B, S, C], [C, K] and [C]")
    s, (c, taps) = v.shape[1], w.shape
    fits = _silu_misfit(s, c, taps, *_blocks(
        s, c, MAMBA_BLOCK_T, MAMBA_BLOCK_C)) is None
    if _route(impl, fits) == "xla":
        return silu_short_conv_xla(v, w, bias, segment_ids)
    return _over_batch_rows(silu_short_conv_pallas, v, (w, bias),
                            segment_ids)
