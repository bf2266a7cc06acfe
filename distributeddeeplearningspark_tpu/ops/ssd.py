"""The selective state-space scan of Mamba-2 (Dao and Gu, arXiv:2405.21060)
in its CHUNKED form (section 6 there), with the state RESET where a document
starts: the operator of a hybrid decoder's state-space layers
(:mod:`..models.hybrid_decoder`), forward and backward, plain ``jax.numpy``
on the CPU and on the TPU alike.

``x [B, S, H, P]`` the heads' inputs, ``dt [B, S, H]`` the step sizes (after
their softplus), ``A [H]`` (negative), ``Bm, Cm [B, S, G, N]`` in ``G``
groups, head ``h`` reading group ``h // (H / G)``, ``D [H]``, ``seg [B, S]``
the document of every position (``None``: one document a row). A head keeps
a state ``h [P, N]``::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T    (h_{t-1} = 0 where t is a
    y_t = h_t C_t + D x_t                           document's first position)

The chunked form, ``chunk`` positions at a time, ``cs`` the running sum of
``dt A`` inside a chunk: ``y = ((C B^T) o L)(dt x)`` with ``L[i, j] =
exp(cs_i - cs_j)`` where ``j <= i`` and both lie in one document; a chunk's
closing state from its own positions (those of its LAST document) and, where
the chunk holds no boundary, the state it was handed times ``exp(cs_last)``;
the states passed from chunk to chunk; the handed state's part of ``y`` at
the positions before the chunk's first boundary. A document's first position
takes nothing from before it: its factor is 0 in the mask and in the passing
of states, and the 0 is a MASK (no ``-inf`` goes under a running sum).

Decays, running sums and states are float32 whatever the activations' dtype;
the four products take the activations' dtype and accumulate in float32.

The chunks go through in GROUPS of :data:`GROUP` (one ``lax.scan`` step a
group: the products of a group's chunks are batched, its states passed by a
short inner scan), so what lives at once is a group's ``[group, heads,
chunk, chunk]`` masks, not the window's. :func:`ssd_scan` is a
``custom_vjp``: forward keeps the inputs and the state every group was
handed (``[groups, B, H, P, N]`` float32, 2 MB a group at the published
sizes); backward walks the groups from the last, makes a group's masks
again and takes that group's vector-Jacobian product. Nothing of size
``[chunks, heads, chunk, chunk]`` lives between forward and backward.

How a trace finds the stage (``benchmark/layer_metrics/ssd_ms_per_step.py``):
it has no kernel and so no name. Each pass is ONE ``while`` whose carried
tuple begins with the state, ``f32[B, G, H / G, P, N]``: the only five-
dimensional float32 array of that shape in a model (the forward pass, its
replay under remat, and the backward pass: three a layer and step); the
arrays after it are stacked ``[groups, B, chunks a group, chunk, ...]``,
and the finder reads neither count. A kernel that takes the scan's place,
or a part of it, is given a name that starts ``ssd_``: the same reader
finds it by that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DEFAULT_CHUNK = 128
#: chunks a step of the outer scan (module docstring)
GROUP = 8


def document_starts(segment_ids, b: int, s: int):
    """``[B, S]`` bool: the positions that are a document's first, other
    than the row's own first (whose state is zero anyway)."""
    if segment_ids is None:
        return jnp.zeros((b, s), jnp.bool_)
    seg = jnp.asarray(segment_ids, jnp.int32)
    return jnp.concatenate(
        [jnp.zeros((b, 1), jnp.bool_), seg[:, 1:] != seg[:, :-1]], axis=1)


def chunks_reset_share(segment_ids, chunk: int = DEFAULT_CHUNK):
    """Of a batch's ``chunk``-position chunks, the share that hold a
    document's first position other than the row's: where the scan masks
    inside the chunk and cuts the state it was handed."""
    b, s = segment_ids.shape
    starts = document_starts(segment_ids, b, s)
    starts = jnp.pad(starts, ((0, 0), (0, -s % chunk)))
    return jnp.mean(jnp.any(starts.reshape(b, -1, chunk), axis=-1)
                    .astype(jnp.float32))


def _group(state, x, dt, a, d, bm, cm, start):
    """One group of ``c`` chunks of ``l`` positions: ``(state [B, G, R, P,
    N] float32 handed to the group's first chunk, x [B, c, l, G, R, P], dt
    [B, c, l, G, R] float32, a, d [G, R] float32, bm, cm [B, c, l, G, N],
    start [B, c, l] bool) -> (the state the group's last chunk hands on, y
    like x, the largest magnitude of a state handed inside the group)``;
    ``R`` the heads of a group."""
    f32, dtype = jnp.float32, x.dtype
    l = x.shape[2]
    cs = jnp.cumsum(dt * a, axis=2)                       # [B, c, l, G, R]
    # the document of a position, counted inside its chunk: 0 until the
    # chunk's first boundary
    k = jnp.cumsum(start.astype(jnp.int32), axis=2)       # [B, c, l]
    reads = (k[..., :, None] == k[..., None, :]) & jnp.tril(
        jnp.ones((l, l), jnp.bool_))                      # [B, c, l, l]
    reads = reads[:, :, None, None]
    by_head = jnp.moveaxis(cs, 2, -1)                     # [B, c, G, R, l]
    diff = by_head[..., :, None] - by_head[..., None, :]
    decay = jnp.where(reads, jnp.exp(jnp.where(reads, diff, 0.0)), 0.0)
    cb = jnp.einsum("bcign,bcjgn->bcgij", cm, bm, preferred_element_type=f32)
    xdt = x.astype(f32) * dt[..., None]                   # [B, c, l, G, R, P]
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp",
                   (cb[:, :, :, None] * decay).astype(dtype),
                   xdt.astype(dtype), preferred_element_type=f32)
    # a chunk's own part of its closing state: its last document's positions
    last = (k == k[..., -1:])[..., None, None]            # [B, c, l, 1, 1]
    to_end = jnp.where(last, jnp.exp(jnp.where(
        last, cs[:, :, -1:] - cs, 0.0)), 0.0)
    own = jnp.einsum("bcjgrp,bcjgn->bcgrpn",
                     (xdt * to_end[..., None]).astype(dtype), bm,
                     preferred_element_type=f32)
    # what a position keeps of the state its chunk was handed (cs <= 0)
    first = (k == 0)[..., None, None]
    from_start = jnp.where(first, jnp.exp(cs), 0.0)       # [B, c, l, G, R]

    def hand(s, chunk):
        keeps, adds = chunk
        return keeps[..., None, None] * s + adds, s

    state, handed = jax.lax.scan(
        hand, state, (jnp.moveaxis(from_start[:, :, -1], 1, 0),
                      jnp.moveaxis(own, 1, 0)))
    handed = jnp.moveaxis(handed, 0, 1)                   # [B, c, G, R, P, N]
    y = y + from_start[..., None] * jnp.einsum(
        "bcign,bcgrpn->bcigrp", cm, handed.astype(dtype),
        preferred_element_type=f32)
    y = y + d[..., None] * x.astype(f32)
    return state, y.astype(dtype), jnp.max(jnp.abs(handed))


def _by_groups(a, groups: int, chunk: int):
    """``[B, S, ...] -> [groups, B, chunks a group, chunk, ...]``."""
    b = a.shape[0]
    a = a.reshape(b, groups, -1, chunk, *a.shape[2:])
    return jnp.moveaxis(a, 1, 0)


def _from_groups(a):
    """The inverse of :func:`_by_groups`."""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape(a.shape[0], -1, *a.shape[4:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _ssd(x, dt, a, d, bm, cm, start, chunk, groups):
    """``x [B, S, G, R, P], dt [B, S, G, R], a, d [G, R], bm, cm [B, S, G,
    N], start [B, S] -> (y like x, the largest magnitude of a handed
    state)``; ``S = groups x (chunks a group) x chunk``."""
    y, peak, _ = _ssd_forward(x, dt, a, d, bm, cm, start, chunk, groups)
    return y, peak


def _ssd_forward(x, dt, a, d, bm, cm, start, chunk, groups):
    cut = lambda t: _by_groups(t, groups, chunk)
    b, _, g, r, p = x.shape
    n = bm.shape[-1]

    def step(state, group):
        out, y, peak = _group(state, group[0], group[1], a, d, *group[2:])
        return out, (y, peak, state)

    _, (y, peak, handed) = jax.lax.scan(
        step, jnp.zeros((b, g, r, p, n), jnp.float32),
        (cut(x), cut(dt), cut(bm), cut(cm), cut(start)))
    return _from_groups(y), jnp.max(peak), handed


def _ssd_fwd(x, dt, a, d, bm, cm, start, chunk, groups):
    y, peak, handed = _ssd_forward(x, dt, a, d, bm, cm, start, chunk, groups)
    return (y, peak), (x, dt, a, d, bm, cm, start, handed)


def _ssd_bwd(chunk, groups, res, cts):
    x, dt, a, d, bm, cm, start, handed = res
    dy, _ = cts
    cut = lambda t: _by_groups(t, groups, chunk)

    def step(carry, group):
        dstate, da, dd = carry
        state, xg, dtg, bmg, cmg, startg, dyg = group
        # (the group's masks are made again here, and die with the step)
        _, vjp = jax.vjp(
            lambda s, xx, tt, aa, ddd, bb, cc: _group(
                s, xx, tt, aa, ddd, bb, cc, startg)[:2],
            state, xg, dtg, a, d, bmg, cmg)
        dstate, dx, ddt, da_g, dd_g, dbm, dcm = vjp((dstate, dyg))
        return (dstate, da + da_g, dd + dd_g), (dx, ddt, dbm, dcm)

    zero = jnp.zeros_like(a)
    (_, da, dd), (dx, ddt, dbm, dcm) = jax.lax.scan(
        step, (jnp.zeros_like(handed[0]), zero, zero),
        (handed, cut(x), cut(dt), cut(bm), cut(cm), cut(start),
         cut(dy.astype(x.dtype))), reverse=True)
    back = _from_groups
    return back(dx), back(ddt), da, dd, back(dbm), back(dcm), None


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, Bm, Cm, D, segment_ids=None, *,
             chunk: int = DEFAULT_CHUNK):
    """``x [B, S, H, P], dt [B, S, H], A [H], Bm, Cm [B, S, G, N], D [H],
    segment_ids [B, S] | None -> (y [B, S, H, P]`` in ``x``'s dtype (module
    docstring), differentiable in all six arrays, ``the largest magnitude of
    a state handed from chunk to chunk)`` (float32, no gradient: the state's
    health). ``chunk`` changes no value. A length that fills no whole number
    of groups of chunks is padded at the END with positions of step size 0
    (they add nothing and nothing before them reads them) and the padding
    cut off again."""
    b, s, h, p = x.shape
    g, n = Bm.shape[2:]
    if (dt.shape != (b, s, h) or Cm.shape != Bm.shape or h % g
            or A.shape != (h,) or D.shape != (h,) or chunk < 1):
        raise ValueError(
            f"ssd_scan: x {x.shape}, dt {dt.shape}, A {A.shape}, B {Bm.shape}"
            f", C {Cm.shape}, D {D.shape}: want [B, S, H, P], [B, S, H], [H],"
            f" [B, S, G, N] twice, [H], with G dividing H")
    f32 = jnp.float32
    chunks = -(-s // chunk)
    per_group = min(GROUP, chunks)
    groups = -(-chunks // per_group)
    pad = groups * per_group * chunk - s
    padded = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
    heads = lambda t: t.reshape(*t.shape[:-1], g, h // g)
    y, peak = _ssd(
        padded(x).reshape(b, s + pad, g, h // g, p),
        heads(padded(dt.astype(f32))), heads(A.astype(f32)),
        heads(D.astype(f32)), padded(Bm.astype(x.dtype)),
        padded(Cm.astype(x.dtype)),
        padded(document_starts(segment_ids, b, s)), chunk, groups)
    y = y.reshape(b, s + pad, h, p)[:, :s]
    return y, jax.lax.stop_gradient(peak)


def ssd_scan_sequential(x, dt, A, Bm, Cm, D, segment_ids=None):
    """The recurrence position by position in float32 (module docstring):
    what the chunked form is tested against."""
    b, s, h, p = x.shape
    g, n = Bm.shape[2:]
    f32 = jnp.float32
    start = document_starts(segment_ids, b, s)
    group_of = jnp.arange(h) // (h // g)
    bh, ch = (t.astype(f32)[:, :, group_of] for t in (Bm, Cm))   # [B, S, H, N]
    xf, dtf = x.astype(f32), dt.astype(f32)

    def step(state, at):
        xt, dtt, bt, ct, st = at
        keep = jnp.where(st[:, None], 0.0, jnp.exp(dtt * A.astype(f32)))
        state = keep[..., None, None] * state + (
            (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    t_major = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, n), f32),
                        tuple(t_major(t) for t in (xf, dtf, bh, ch, start)))
    return (t_major(y) + D.astype(f32)[:, None] * xf).astype(x.dtype)
