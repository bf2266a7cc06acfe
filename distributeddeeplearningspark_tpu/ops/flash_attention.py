"""Pallas blockwise flash attention for TPU — the long-sequence hot op.

The reference leans on cuDNN/torch SDPA CUDA kernels for attention; the
TPU-native equivalent is a Pallas (Mosaic) kernel tiled for the MXU and VMEM
(SURVEY.md §1 L2, pallas_guide.md). Standard FlashAttention-2 scheme:

- **Forward**: grid over (batch·heads, Q blocks, K blocks); the K dimension is
  innermost so VMEM accumulators (running max ``m``, denominator ``l``, output
  ``acc``) persist across K steps — O(S) memory, no [S, S] score matrix ever
  hits HBM. Also emits the log-sum-exp per row for the backward pass.
- **Backward**: recomputation-based, two kernels — dQ (grid K-innermost) and
  dK/dV (grid Q-innermost) — using the forward's LSE and the precomputed
  ``delta = rowsum(dO ∘ O)`` (FlashAttention-2, arXiv:2307.08691).
- Accumulation is f32 throughout; inputs may be bf16 (MXU-native).

Supported masking (BASELINE.json config 3 needs this — BERT always attends
under a key-padding mask):

- ``causal`` — per-block: blocks strictly above the diagonal are skipped
  (their grid steps no-op and, in every regime, copy nothing: the index maps
  clamp the streamed block to the row's last block on the diagonal), a block
  the diagonal crosses gets a positional mask, a block strictly under it
  none.
- ``mask`` — a *key-only* padding mask ([B, Sk] or the BERT-style
  [B, 1, 1, Sk]); streamed into the kernel one [block_k] slice at a time, so
  no [S, S] mask tensor is ever built. Q-dependent masks are not expressible
  blockwise without a full mask tensor — those fall back to the XLA path.
- ``segment_ids`` — packed documents: a pair is allowed only inside one
  document, by an element mask in every block that is walked. A block is
  walked only if a document can span it: the least and greatest id of its
  queries and of its keys (:func:`segment_block_walk`, computed once in XLA
  from ``q_segs`` and ``kv_segs``, which differ on a ring hop) must
  intersect, and with ``causal`` it must not lie above the diagonal. The
  bounds reach the kernels as two int32 tables in SMEM (scalar prefetch): the
  kernel branches on them, and the index maps clamp the streamed block to the
  hull of the walked ones, so a skipped step names the block already resident
  and copies nothing. A skipped block would have left every accumulator as it
  was, so the result has the bits of walking and masking it. Exact for
  running ids (documents packed one after another), a superset for ids in
  any order (pads at -1). :func:`attn_blocks_walked_share` counts, from the
  same predicate, what is walked of the triangle. Without segment ids the
  three calls take no table.
- ``window`` (with ``causal``) — the band ``0 <= i - j < window`` of a
  sliding-window layer: the element mask gains ``i - j < window``, and the
  two predicates of the walk and of the *whole* class gain the band's bound
  (:func:`_in_band`). The grid walks only the band: the key axis of the
  forward and dQ grids spans the band's blocks of a query block (from its
  first, moved back at the row's end so that every step names a block of the
  array), the query axis of dK/dV those of a key block (:func:`band_blocks`),
  so no step is spent above the diagonal or before the band. The calls are
  then ``flash_window_fwd``, ``flash_window_bwd_dq`` and
  ``flash_window_bwd_dkv``; they compose with segment ids (the tables are
  computed with the band) and GQA. :func:`window_blocks_walked_share` counts
  what they walk of the triangle.

**Three classes of grid step.** A step does only what its block needs, by
what the kernel can observe (the static regime, the step's ``(qb, kb)`` and,
under segment ids, the two tables): *nothing* (above the diagonal, or no
document spans it: no compute and no copy), *whole* (every pair allowed: it
lies strictly under the diagonal where ``causal`` and, under segment ids, its
queries and its keys are all of ONE document, :func:`_block_whole`), *edge*
(every other walked block: the diagonal, a document boundary). In dQ and
dK/dV a *whole* block runs the body without :func:`_block_mask` and without
the select on ``p``, both identities there, so the bits are those of
masking; an *edge* block the masking body. The forward kernel masks every
walked block: measured on the chip its mask is free at both head sizes
(something else binds its step) and a second body only made it slower. A
regime with a key mask has no *whole* class (what a key block's mask holds
is not known to the kernel) and one without any mask no *edge*: their bodies
are emitted once, as they always were. :func:`attn_blocks_masked_share`
counts, from the same predicate, the *edge* blocks among the walked.

Masked logits use a large *finite* negative (never -inf: running-max
subtraction would produce inf - inf = NaN on fully-masked blocks) and
probabilities are explicitly zeroed under the mask, so fully-padded key
blocks contribute exactly nothing.

**GQA** (grouped-query attention): K/V may carry ``Hkv < H`` heads with
``H % Hkv == 0``. The kernels map each Q head to its KV group via the
BlockSpec index maps (q row r reads kv row ``r // group``) — the grouped KV
is never materialized at Q-head width, which is the whole point (the
reference-style ``repeat_interleave`` would copy KV ``group``× in HBM).

**Two head sizes** (latent attention: q and k carry a position-free and a
rotary part, 128 + 64 = 192 wide, v and o 128): ``q, k [.., d_qk]`` and ``v,
o, do [.., d_v]`` go to the three kernels as they are. Nothing is padded to
the wider of the two: the P V product, its backward and the bytes of v, o and
do are the model's own. With ``d_qk == d_v`` the three calls are built as
they always were (same grid, specs, scratch and names).

Layout: [B, S, H, D] (BSHD) at the API, flattened to [B·H, S, D] /
[B·Hkv, S, D] for the kernels (head-major order, so consecutive q rows share
a kv row).

Mosaic tiling contract (verified on a real v5e chip — the interpret-mode
tests cannot catch this): the last two dims of every block must each be
divisible by (8, 128) or equal the full array dim. Row-statistics (LSE,
delta) therefore travel as [B·H, S, 8] — values replicated across a
trailing size-8 dim that equals the array dim (legal) while costing 16×
less HBM than the 128-lane layout the stock jax kernel uses — and the
key-padding mask travels lane-oriented as [B, 1, Sk] so a [block_k] slice
lands in the lane dim of the score block.

Shape contract (checked): S divisible by the block sizes; each head size a
multiple of 8. A head size is the LAST dim of its blocks and is always
blocked whole, so it is legal as "equal to the array dim" whatever it is:
BERT's 64 (half a lane tile) and latent attention's 192 (one and a half: no
multiple of the 128 lanes) both compile; Mosaic lays such an operand out in
whole 128-lane tiles in VMEM (192 takes the room of 256 there, and the MXU
passes of a 192-deep contraction those of 256), HBM holds and the copies
move the array's own bytes. 128-multiples waste nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from distributeddeeplearningspark_tpu.utils.env import pallas_interpret

# Large finite negative for masked logits. Finite so the online-softmax
# running max never hits -inf (exp(-inf - -inf) = NaN); small enough that
# exp(_MASK_VALUE - m) underflows to 0 for any real row max m.
_MASK_VALUE = -1e30
DEFAULT_BLOCK = 512
#: ``jax.ad_checkpoint.checkpoint_name`` of the forward kernel's output and
#: log-sum-exp: a ``remat`` policy that keeps them spares the replay the
#: forward kernel (the backward kernels need those two, never the forward
#: pass again); without such a policy the name does nothing
FLASH_OUT_NAME = "flash_out"
#: trailing dim for row-statistics (LSE/delta) arrays: the Mosaic block rule
#: ("divisible by (8, 128) or equal to the array dim") is satisfied by making
#: the minor dim exactly 8 and always blocking it whole.
STAT_LANES = 8


def _seg_stat(segs):
    """[B, S] segment ids → STAT layout [B, S, STAT_LANES] for sublane reads
    (same Mosaic-legal trick as the LSE/delta row stats)."""
    return jnp.broadcast_to(segs[..., None], (*segs.shape, STAT_LANES))


def _vmem():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM


def _grid_params(*semantics: str):
    """Mosaic dimension semantics: mark non-accumulating grid dims
    "parallel" so the pipeline can overlap DMA/compute across them (the
    innermost accumulator dim stays "arbitrary" = sequential). Measured on
    v5e: without this the grid serializes completely and per-step overhead
    dominates (~90µs/step — 10× slower than XLA attention at s=512)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics)


def _block_id_range(segs, block: int):
    """[B, S] segment ids -> (least, greatest) id of each block, [B, S/block]."""
    blocks = segs.reshape(segs.shape[0], -1, block)
    return jnp.min(blocks, axis=-1), jnp.max(blocks, axis=-1)


def _in_band(qb, kb, *, causal, block_q, block_k, window=None):
    """Whether score block ``(qb, kb)`` holds a pair the positions allow:
    with ``causal`` it does not lie strictly above the diagonal and, with a
    ``window``, its nearest pair (first query, last key) lies less than
    ``window`` apart. ``True`` without either."""
    ok = True
    if causal:
        ok = kb * block_k < (qb + 1) * block_q
    if window is not None:
        ok = jnp.logical_and(ok, qb * block_q - (kb + 1) * block_k + 1
                             < window)
    return ok


def _blocks_meet(q_lo, q_hi, k_lo, k_hi, qb, kb, *, causal, block_q, block_k,
                 window=None):
    """Whether score block ``(qb, kb)`` is walked: the id ranges of its
    queries and of its keys intersect (a superset of "holds an allowed pair"
    for ids in any order, exact for running ids) and the positions allow a
    pair in it (:func:`_in_band`: not strictly above the diagonal with
    ``causal``, inside the band with a ``window``). On scalars inside the
    kernels, on arrays in :func:`segment_block_walk`: one predicate for
    both."""
    meet = jnp.logical_and(k_lo <= q_hi, k_hi >= q_lo)
    if causal or window is not None:
        meet = jnp.logical_and(meet, _in_band(
            qb, kb, causal=causal, block_q=block_q, block_k=block_k,
            window=window))
    return meet


def _block_whole(q_lo, q_hi, k_lo, k_hi, qb, kb, *, causal, block_q, block_k,
                 window=None):
    """Whether a WALKED score block ``(qb, kb)`` allows every pair in it, so
    that masking it is the identity: its queries and its keys are all of one
    document (the four bounds equal; ``None`` without segment ids; exact for
    ids in any order and on a ring hop), with ``causal`` its last key does
    not come after its first query and, with a ``window``, its farthest pair
    (last query, first key) lies less than ``window`` apart. A key mask is
    not seen here: a regime with one has no such class. On scalars inside
    the kernels, on arrays in :func:`attn_blocks_masked_share`: one predicate
    for both."""
    whole = True
    if q_lo is not None:
        whole = jnp.logical_and(jnp.logical_and(q_lo == q_hi, k_lo == k_hi),
                                q_lo == k_lo)
    if causal:
        whole = jnp.logical_and(whole, (kb + 1) * block_k - 1 <= qb * block_q)
    if window is not None:
        whole = jnp.logical_and(
            whole, (qb + 1) * block_q - 1 - kb * block_k < window)
    return whole


def segment_block_walk(q_segs, kv_segs, *, causal, block_q, block_k,
                       window=None):
    """What the three kernels walk under segment ids, from ``q_segs`` and
    ``kv_segs`` [B, S] (they differ on a ring hop).

    Returns ``(walk, q_side, k_side)``: ``walk`` bool [B, S/block_q,
    S/block_k], the blocks computed; ``q_side`` int32 [4, B * S/block_q], per
    (row, query block) the least and greatest id and the first and last key
    block walked; ``k_side`` int32 [4, B * S/block_k], the same from the key
    blocks' side. The two tables are the kernels' scalar-prefetch operands:
    the id ranges decide (through :func:`_blocks_meet`) what is computed, the
    hull ``[first, last]`` what the index maps fetch. A row or column with no
    walked block gets the hull [0, 0]. With a ``window`` the band bounds the
    walk too (:func:`_in_band`)."""
    q_lo, q_hi = _block_id_range(q_segs, block_q)
    k_lo, k_hi = _block_id_range(kv_segs, block_k)
    nq, nk = q_lo.shape[1], k_lo.shape[1]
    qb = jnp.arange(nq, dtype=jnp.int32)[None, :, None]
    kb = jnp.arange(nk, dtype=jnp.int32)[None, None, :]
    walk = _blocks_meet(q_lo[:, :, None], q_hi[:, :, None], k_lo[:, None, :],
                        k_hi[:, None, :], qb, kb, causal=causal,
                        block_q=block_q, block_k=block_k, window=window)

    def hull(ix, n, axis):   # integer min / max: no boolean reduction
        last = jnp.maximum(jnp.max(jnp.where(walk, ix, -1), axis=axis), 0)
        first = jnp.minimum(jnp.min(jnp.where(walk, ix, n), axis=axis), last)
        return first, last

    k_first, k_last = hull(kb, nk, 2)
    q_first, q_last = hull(qb, nq, 1)
    pack = lambda *cols: jnp.stack(cols).astype(jnp.int32).reshape(4, -1)
    return (walk, pack(q_lo, q_hi, k_first, k_last),
            pack(k_lo, k_hi, q_first, q_last))


def segment_block_classes(q_segs, kv_segs, *, causal, block_q, block_k,
                          window=None):
    """``(walk, whole)`` bool [B, S/block_q, S/block_k]: the blocks the
    kernels compute under these ids and, of them, those that need no mask,
    by the kernels' own two predicates on the tables they are handed."""
    walk, q_side, k_side = segment_block_walk(
        q_segs, kv_segs, causal=causal, block_q=block_q, block_k=block_k,
        window=window)
    b, nq, nk = walk.shape
    whole = _block_whole(
        *q_side[:2].reshape(2, b, nq, 1), *k_side[:2].reshape(2, b, 1, nk),
        jnp.arange(nq, dtype=jnp.int32)[None, :, None],
        jnp.arange(nk, dtype=jnp.int32)[None, None, :],
        causal=causal, block_q=block_q, block_k=block_k, window=window)
    return walk, jnp.logical_and(walk, whole)


def attn_blocks_walked_share(segment_ids, *, block: int = DEFAULT_BLOCK):
    """Blocks the causal kernels walk under ``segment_ids`` [B, S] over the
    ``n (n + 1) / 2`` on or under the diagonal, mean over the rows: 1.0 for a
    window that is one document. From :func:`segment_block_walk`, which also
    hands the kernels their bounds. A length the kernels do not take (not a
    multiple of the block) goes to the XLA path, which computes every pair."""
    s = segment_ids.shape[1]
    block = min(block, s)
    if s % block:
        return jnp.float32(1.0)
    walk, _, _ = segment_block_walk(segment_ids, segment_ids, causal=True,
                                    block_q=block, block_k=block)
    n = s // block
    return (jnp.mean(jnp.sum(walk, axis=(1, 2)).astype(jnp.float32))
            / (n * (n + 1) / 2))


def attn_blocks_masked_share(segment_ids, *, block: int = DEFAULT_BLOCK):
    """Of the blocks the causal kernels walk under ``segment_ids`` [B, S],
    the share that needs its mask (*edge*: dQ and dK/dV run the masking body
    there and the body without a mask in the rest), over the batch: ``2 / (n
    + 1)`` for a window of ``n`` blocks that is one document (the diagonal
    alone), more with every document boundary. From :func:`_block_whole`,
    which the kernels branch on. A length the kernels do not take goes to the
    XLA path, which masks every pair."""
    s = segment_ids.shape[1]
    block = min(block, s)
    if s % block:
        return jnp.float32(1.0)
    walk, whole = segment_block_classes(
        segment_ids, segment_ids, causal=True, block_q=block, block_k=block)
    walked = jnp.sum(walk).astype(jnp.float32)
    return (walked - jnp.sum(whole)) / jnp.maximum(walked, 1.0)


def window_blocks_walked_share(segment_ids, window: int, *,
                               block: int = DEFAULT_BLOCK):
    """Blocks the band kernels (:func:`flash_attention` with ``window``)
    walk under ``segment_ids`` [B, S] over the ``n (n + 1) / 2`` on or under
    the diagonal, mean over the rows: ``2 n - 1`` blocks, about ``4 / (n +
    1)``, for a window of one block in a row that is one document, 1.0 for a
    ``window`` of the whole row. From :func:`segment_block_walk`, the
    predicate that also hands the kernels their bounds. A length the kernels
    do not take goes to the XLA path, which computes every pair: 1.0."""
    s = segment_ids.shape[1]
    block = min(block, s)
    if s % block:
        return jnp.float32(1.0)
    walk, _, _ = segment_block_walk(segment_ids, segment_ids, causal=True,
                                    block_q=block, block_k=block,
                                    window=window)
    n = s // block
    return (jnp.mean(jnp.sum(walk, axis=(1, 2)).astype(jnp.float32))
            / (n * (n + 1) / 2))


def band_blocks(window: int, *, block_q: int, block_k: int, num_qb: int,
                num_kb: int) -> tuple[int, int]:
    """``(key blocks, query blocks)``: the most key blocks the band of one
    query block meets, and the most query blocks that of one key block
    meets; the extents of the band kernels' inner grid axes (forward and dQ
    walk a query block's keys, dK/dV a key block's queries)."""
    keys = max((((i + 1) * block_q - 1) // block_k
                - max(i * block_q - window + 1, 0) // block_k + 1)
               for i in range(num_qb))
    queries = max((min(num_qb - 1, ((j + 1) * block_k + window - 2)
                       // block_q) - j * block_k // block_q + 1)
                  for j in range(num_kb))
    return min(keys, num_kb), min(queries, num_qb)


def _band_key_block(i, t, *, window, block_q, block_k, num_kb, span):
    """The key block at step ``t`` of query block ``i``'s band: the band's
    first, so moved back near the end of the row that every step names a
    block of the array (a step before the band or past the diagonal is
    walked by no predicate)."""
    first = jax.lax.div(jnp.maximum(i * block_q - (window - 1), 0), block_k)
    return jnp.minimum(first, num_kb - span) + t


def _band_query_block(i, t, *, block_q, block_k, num_qb, span):
    """The query block at step ``t`` of key block ``i``'s band, as
    :func:`_band_key_block`."""
    return jnp.minimum(jax.lax.div(i * block_k, block_q), num_qb - span) + t


def _within_hull(side, at, j):
    """Index-map helper: block ``j`` clamped to the hull of the walked blocks
    of table entry ``at``, so that a run of skipped steps names the block
    already resident and Pallas issues no copy."""
    return jnp.clip(j, side[2, at], side[3, at])


def _streamed_key_block(heads: int, num_qb: int, *, causal: bool,
                        block_q: int, block_k: int, band=None):
    """The key block the forward and dQ grids ``(b, i, j)`` fetch at a step.
    Index maps take the tables last (``*t``: none without segment ids). A step
    that computes nothing names the block already resident: under segment ids
    the hull of the walked ones, else with ``causal`` the row's last block on
    the diagonal (with a window: clamped to the band too), else (every block
    is walked) ``j``. ``band`` (a window's: ``j`` is a step of the band, not
    a key block) maps ``(i, j)`` to the key block."""
    def kv_blk(b, i, j, *t):
        if band is not None:
            j = band["key_block"](i, j)
        if t:
            return _within_hull(t[0], b // heads * num_qb + i, j)
        if band is not None:
            first = jax.lax.div(
                jnp.maximum(i * block_q - (band["window"] - 1), 0), block_k)
            return jnp.clip(j, first,
                            jax.lax.div((i + 1) * block_q - 1, block_k))
        if causal:
            return jnp.minimum(j, jax.lax.div((i + 1) * block_q - 1, block_k))
        return j
    return kv_blk


def _band_of(window, *, block_q, block_k, num_qb, num_kb):
    """What the grids, index maps and kernels of the band regime need of the
    ``window`` (``None`` without one): the extents of the inner grid axes
    and the maps from a step of the band to the block it names."""
    if window is None:
        return None
    keys, queries = band_blocks(window, block_q=block_q, block_k=block_k,
                                num_qb=num_qb, num_kb=num_kb)
    return {"window": window, "keys": keys, "queries": queries,
            "key_block": functools.partial(
                _band_key_block, window=window, block_q=block_q,
                block_k=block_k, num_kb=num_kb, span=keys),
            "query_block": functools.partial(
                _band_query_block, block_q=block_q, block_k=block_k,
                num_qb=num_qb, span=queries)}


def _pallas(kernel, *, name, grid, in_specs, out_specs, out_shape,
            scratch_shapes, tables, interpret):
    """One ``pallas_call`` of this file. ``tables`` (the two of
    :func:`segment_block_walk`, or none without segment ids) go ahead of the
    operands as scalar prefetch: the kernel's first refs, the index maps' last
    arguments."""
    params = dict(out_shape=out_shape, interpret=interpret, name=name,
                  compiler_params=_grid_params("parallel", "parallel",
                                               "arbitrary"))
    if not tables:
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_specs,
                              scratch_shapes=scratch_shapes, **params)
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call(kernel, grid_spec=pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes), **params)
    return functools.partial(call, *tables)


def _block_mask(qb, kb, s_blk, *, causal, mask_blk, block_q, block_k,
                q_seg_blk=None, k_seg_blk=None, window=None):
    """(masked logits, allowed bool | None) for one [Bq, Bk] score block.

    ``q_seg_blk`` [Bq] / ``k_seg_blk`` [Bk]: packed-sequence segment ids
    (VERDICT r2 #4) — attention is allowed only where ids match, so multiple
    documents packed into one row never attend across their boundaries.
    ``q_seg_blk`` arrives sublane-oriented (broadcasts over lanes),
    ``k_seg_blk`` lane-oriented (broadcasts over sublanes) — both broadcast
    directions are free on the VPU. A ``window`` (with ``causal``) allows
    ``0 <= q - k < window``.
    """
    allowed = None
    if causal:
        q_pos = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        allowed = q_pos >= k_pos
        if window is not None:
            allowed = jnp.logical_and(allowed, q_pos - k_pos < window)
    if mask_blk is not None:
        kv_ok = jnp.broadcast_to(mask_blk[None, :] != 0, (block_q, block_k))
        allowed = kv_ok if allowed is None else jnp.logical_and(allowed, kv_ok)
    if q_seg_blk is not None:
        same = q_seg_blk[:, None] == k_seg_blk[None, :]
        allowed = same if allowed is None else jnp.logical_and(allowed, same)
    if allowed is None:
        return s_blk, None
    return jnp.where(allowed, s_blk, _MASK_VALUE), allowed


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _split_refs(refs, fixed: int, has_mask: bool, has_segs: bool):
    """A kernel's refs in the order :func:`_pallas` hands them over:
    ``(tables, the fixed operands, mask, q ids, k ids, outputs + scratch)``.

    ``mask`` [1, 1, Bk] int32 (lane-major); the segment ids on the q side in
    STAT layout [1, Bq, STAT] (sublane read), on the k side lane-major
    [1, 1, Bk]; ``tables`` the two SMEM tables of :func:`segment_block_walk`.
    """
    tables, refs = (refs[:2], refs[2:]) if has_segs else ((), refs)
    i = fixed
    mask_ref = refs[i] if has_mask else None
    i += int(has_mask)
    qseg_ref, kseg_ref = (refs[i], refs[i + 1]) if has_segs else (None, None)
    i += 2 * int(has_segs)
    return tables, refs[:fixed], mask_ref, qseg_ref, kseg_ref, refs[i:]


def _by_class(compute, tables, b, qb, kb, *, heads, causal, classed, num_qb,
              num_kb, block_q, block_k, window=None):
    """Run ``compute(masked)`` as the class of block ``(qb, kb)`` needs
    (module docstring). *Nothing*: not at all; a skipped block would have
    left the accumulators as they are, to the bit. Walked are, under segment
    ids (``tables``; grid row ``b`` belongs to batch row ``b // heads``), the
    blocks :func:`_blocks_meet` names, else those on or under the diagonal
    (``causal``; inside the band with a ``window``, :func:`_in_band`), else
    all. With ``classed`` a walked block that is *whole* by
    :func:`_block_whole` runs ``compute(False)``, the body without the mask;
    every other one, and without ``classed`` every walked one,
    ``compute(True)``."""
    bounds = dict(causal=causal, block_q=block_q, block_k=block_k)
    if window is not None:
        bounds["window"] = window
    ids = (None,) * 4
    walked = True
    if tables:
        q_side, k_side = tables
        qi, ki = b // heads * num_qb + qb, b // heads * num_kb + kb
        ids = (q_side[0, qi], q_side[1, qi], k_side[0, ki], k_side[1, ki])
        walked = _blocks_meet(*ids, qb, kb, **bounds)
    elif causal:
        # blocks strictly above the diagonal (or outside the band) contribute
        # nothing
        walked = _in_band(qb, kb, **bounds)
    if not classed or not (tables or causal):   # (no mask at all: no edge)
        pl.when(walked)(lambda: compute(True))
        return

    @pl.when(walked)    # the second test inside: a skipped step pays for one
    def _():
        jax.lax.cond(_block_whole(*ids, qb, kb, **bounds),
                     lambda: compute(False), lambda: compute(True))


def _fwd_kernel(*refs, scale: float, causal: bool, has_mask: bool,
                has_segs: bool, heads: int, num_qb: int, num_kb: int,
                block_q: int, block_k: int, band=None):
    tables, (q_ref, k_ref, v_ref), mask_ref, qseg_ref, kseg_ref, rest = (
        _split_refs(refs, 3, has_mask, has_segs))  # [1, Bq, D], [1, Bk, D]
    o_ref, lse_ref = rest[:2]                 # [1, Bq, D], [1, Bq, STAT]
    acc_ref, m_ref, l_ref = rest[2:]          # VMEM scratch
    b, qb, step = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    # (the grid's last axis walks a window's band, or every key block)
    kb, steps, window = ((step, num_kb, None) if band is None else
                         (band["key_block"](qb, step), band["keys"],
                          band["window"]))

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)

    def compute(masked):
        q = q_ref[0].astype(jnp.float32) * scale          # [Bq, D]
        k = k_ref[0].astype(jnp.float32)                  # [Bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Bq, Bk]
        allowed = None
        if masked:
            s, allowed = _block_mask(
                qb, kb, s, causal=causal,
                mask_blk=mask_ref[0, 0] if has_mask else None,
                block_q=block_q, block_k=block_k,
                q_seg_blk=qseg_ref[0, :, 0] if has_segs else None,
                k_seg_blk=kseg_ref[0, 0] if has_segs else None,
                window=window)
        m_prev = m_ref[:, 0]                              # [Bq]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        if allowed is not None:
            # exact zero under the mask (exp may give 1.0 on rows whose
            # running max is still _MASK_VALUE)
            p = jnp.where(allowed, p, 0.0)
        corr = jnp.exp(m_prev - m_cur)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_cur
        pv = jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                     preferred_element_type=jnp.float32)  # [Bq, D]
        acc_ref[:] = acc_ref[:] * corr[:, None] + pv

    # one body here: the mask is free in this kernel (its statistics bind it;
    # PERF.md section 5), and a second body only lengthened its steps
    _by_class(compute, tables, b, qb, kb, heads=heads, causal=causal,
              classed=False, num_qb=num_qb, num_kb=num_kb, block_q=block_q,
              block_k=block_k, window=window)

    @pl.when(step == steps - 1)
    def _finalize():
        l = l_ref[:, 0]
        # fully-masked rows (all keys padded): emit 0 output, and an LSE of
        # _MASK_VALUE — the backward kernels re-zero p under the mask anyway
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse = m_ref[:, 0] + jnp.log(l_safe)
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


def _flash_fwd(q, k, v, kv_mask, *, scale, causal, group, block_q, block_k,
               interpret, q_segs=None, kv_segs=None, window=None):
    bh, s, d = q.shape
    dv = v.shape[-1]   # the value heads' width: o's too, d (q's and k's) or not
    num_qb, num_kb = s // block_q, s // block_k
    has_mask = kv_mask is not None
    has_segs = q_segs is not None
    if has_segs != (kv_segs is not None):
        raise ValueError("q_segs and kv_segs must be passed together")
    heads = (bh // kv_mask.shape[0] if has_mask
             else bh // q_segs.shape[0] if has_segs else 0)
    tables = (segment_block_walk(q_segs, kv_segs, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 window=window)[1:]
              if has_segs else ())
    band = _band_of(window, block_q=block_q, block_k=block_k, num_qb=num_qb,
                    num_kb=num_kb)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, has_mask=has_mask,
        has_segs=has_segs, heads=heads, num_qb=num_qb, num_kb=num_kb,
        block_q=block_q, block_k=block_k, band=band,
    )

    kv_blk = _streamed_key_block(heads, num_qb, causal=causal,
                                 block_q=block_q, block_k=block_k, band=band)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j, *t: (b, i, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j, *t: (b // group, kv_blk(b, i, j, *t), 0)),
        pl.BlockSpec((1, block_k, dv),
                     lambda b, i, j, *t: (b // group, kv_blk(b, i, j, *t), 0)),
    ]
    operands = [q, k, v]
    # lane-oriented [B, 1, Sk]: a [block_k] slice lands in the lane dim
    lane_spec = pl.BlockSpec(
        (1, 1, block_k),
        lambda b, i, j, *t: (b // heads, 0, kv_blk(b, i, j, *t)))
    if has_mask:
        in_specs.append(lane_spec)
        operands.append(kv_mask[:, None, :])
    if has_segs:
        in_specs.append(pl.BlockSpec((1, block_q, STAT_LANES),
                                     lambda b, i, j, *t: (b // heads, i, 0)))
        operands.append(_seg_stat(q_segs))
        in_specs.append(lane_spec)
        operands.append(kv_segs[:, None, :])
    vmem = _vmem()
    o, lse = _pallas(
        kernel,
        name="flash_fwd" if band is None else "flash_window_fwd",
        grid=(bh, num_qb, num_kb if band is None else band["keys"]),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j, *t: (b, i, 0)),
            pl.BlockSpec((1, block_q, STAT_LANES),
                         lambda b, i, j, *t: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, s, STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            vmem((block_q, dv), jnp.float32),   # acc
            vmem((block_q, 128), jnp.float32),  # m (col 0 used)
            vmem((block_q, 128), jnp.float32),  # l (col 0 used)
        ],
        tables=tables,
        interpret=interpret,
    )(*operands)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward (recomputation, FlashAttention-2)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale: float, causal: bool, has_mask: bool,
                   has_segs: bool, heads: int, num_qb: int, num_kb: int,
                   block_q: int, block_k: int, band=None):
    tables, fixed, mask_ref, qseg_ref, kseg_ref, (dq_ref, acc_ref) = (
        _split_refs(refs, 6, has_mask, has_segs))
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = fixed
    b, qb, step = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kb, steps, window = ((step, num_kb, None) if band is None else
                         (band["key_block"](qb, step), band["keys"],
                          band["window"]))

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def compute(masked):
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        allowed = None
        if masked:
            s, allowed = _block_mask(
                qb, kb, s, causal=causal,
                mask_blk=mask_ref[0, 0] if has_mask else None,
                block_q=block_q, block_k=block_k,
                q_seg_blk=qseg_ref[0, :, 0] if has_segs else None,
                k_seg_blk=kseg_ref[0, 0] if has_segs else None,
                window=window)
        p = jnp.exp(s - lse_ref[0, :, 0][:, None])                 # [Bq, Bk]
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, 0][:, None])                # [Bq, Bk]
        acc_ref[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    # (what a key block's mask holds is not known here: no *whole* class)
    _by_class(compute, tables, b, qb, kb, heads=heads, causal=causal,
              classed=not has_mask, num_qb=num_qb, num_kb=num_kb,
              block_q=block_q, block_k=block_k, window=window)

    @pl.when(step == steps - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale: float, causal: bool, has_mask: bool,
                    has_segs: bool, heads: int, num_qb: int, num_kb: int,
                    group: int, block_q: int, block_k: int, band=None):
    """dK/dV for ONE kv head, accumulating over its `group` q heads × q blocks.

    Grid: (B·Hkv, num_kb, group·num_qb) — the innermost index j interleaves
    (q head in group, q block); the index maps select q row b·group + j//num_qb.
    With a window's ``band`` the q blocks are the band's: ``num_qb`` there is
    ``band["queries"]``, the steps of one head. ``heads`` here counts the KV
    heads of a batch row.
    """
    tables, fixed, mask_ref, qseg_ref, kseg_ref, rest = (
        _split_refs(refs, 6, has_mask, has_segs))
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = fixed
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    b, kb, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    if band is None:
        steps, window = num_qb, None
        qb = j % num_qb
    else:
        steps, window = band["queries"], band["window"]
        qb = band["query_block"](kb, j % steps)

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute(masked):
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Bq, Bk]
        allowed = None
        if masked:
            s, allowed = _block_mask(
                qb, kb, s, causal=causal,
                mask_blk=mask_ref[0, 0] if has_mask else None,
                block_q=block_q, block_k=block_k,
                q_seg_blk=qseg_ref[0, :, 0] if has_segs else None,
                k_seg_blk=kseg_ref[0, 0] if has_segs else None,
                window=window)
        p = jnp.exp(s - lse_ref[0, :, 0][:, None])                 # [Bq, Bk]
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        do = do_ref[0].astype(jnp.float32)
        # dV += Pᵀ dO
        dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, 0][:, None])
        # dK += dSᵀ (Q·scale); the extra `scale` belongs to dQ only, and
        # q here already carries it — exactly the dK of s = scale·q·kᵀ
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    # (what a key block's mask holds is not known here: no *whole* class)
    _by_class(compute, tables, b, qb, kb, heads=heads, causal=causal,
              classed=not has_mask, num_qb=num_qb, num_kb=num_kb,
              block_q=block_q, block_k=block_k, window=window)

    @pl.when(j == group * steps - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(res, g, *, scale, causal, group, block_q, block_k, interpret,
               window=None):
    q, k, v, kv_mask, o, lse = res[:6]
    q_segs = res[6] if len(res) > 6 else None
    kv_segs = res[7] if len(res) > 7 else None
    do = g
    bh, s, d = q.shape
    bhkv, dv = k.shape[0], v.shape[-1]
    num_qb, num_kb = s // block_q, s // block_k
    has_mask = kv_mask is not None
    has_segs = q_segs is not None
    heads = (bh // kv_mask.shape[0] if has_mask
             else bh // q_segs.shape[0] if has_segs else 0)
    tables = (segment_block_walk(q_segs, kv_segs, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 window=window)[1:]
              if has_segs else ())
    band = _band_of(window, block_q=block_q, block_k=block_k, num_qb=num_qb,
                    num_kb=num_kb)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # row stats travel as [bh, s, STAT_LANES] (Mosaic block rule — see module
    # docstring); the replication is a cheap transient, the residual is 2-D
    stat = lambda x: jnp.broadcast_to(x[..., None], (*x.shape, STAT_LANES))
    lse3, delta3 = stat(lse), stat(delta)
    stat_spec = lambda ix: pl.BlockSpec((1, block_q, STAT_LANES), ix)
    mask3 = kv_mask[:, None, :] if has_mask else None
    shared = dict(scale=scale, causal=causal, has_mask=has_mask,
                  has_segs=has_segs, num_qb=num_qb, num_kb=num_kb,
                  block_q=block_q, block_k=block_k, band=band)
    vmem = _vmem()

    kv_blk = _streamed_key_block(heads, num_qb, causal=causal,
                                 block_q=block_q, block_k=block_k, band=band)
    q_row = lambda b, i, j, *t: (b, i, 0)
    kv_row = lambda b, i, j, *t: (b // group, kv_blk(b, i, j, *t), 0)
    lane_spec = pl.BlockSpec(
        (1, 1, block_k),
        lambda b, i, j, *t: (b // heads, 0, kv_blk(b, i, j, *t)))
    in_specs_q = [
        pl.BlockSpec((1, block_q, d), q_row),    # q
        pl.BlockSpec((1, block_k, d), kv_row),   # k
        pl.BlockSpec((1, block_k, dv), kv_row),  # v
        pl.BlockSpec((1, block_q, dv), q_row),   # do
        stat_spec(q_row),                        # lse
        stat_spec(q_row),                        # delta
    ]
    operands = [q, k, v, do, lse3, delta3]
    if has_mask:
        in_specs_q.append(lane_spec)
        operands.append(mask3)
    if has_segs:
        in_specs_q.append(stat_spec(lambda b, i, j, *t: (b // heads, i, 0)))
        operands.append(_seg_stat(q_segs))
        in_specs_q.append(lane_spec)
        operands.append(kv_segs[:, None, :])
    dq = _pallas(
        functools.partial(_bwd_dq_kernel, heads=heads, **shared),
        name="flash_bwd_dq" if band is None else "flash_window_bwd_dq",
        grid=(bh, num_qb, num_kb if band is None else band["keys"]),
        in_specs=in_specs_q,
        out_specs=[pl.BlockSpec((1, block_q, d), q_row)],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q.dtype)],
        scratch_shapes=[vmem((block_q, d), jnp.float32)],
        tables=tables,
        interpret=interpret,
    )(*operands)[0]

    # dK/dV: grid batch dim is B·Hkv; inner dim sweeps (group, q block) so the
    # accumulators fold every q head of the group into one kv-head gradient.
    kvheads = (bhkv // kv_mask.shape[0] if has_mask
               else bhkv // kv_segs.shape[0] if has_segs else 0)

    # the query blocks one head's steps walk: every one, or a window's band
    steps_q = num_qb if band is None else band["queries"]

    def q_blk(b, i, j, *t):  # the query block streamed at this step
        qb = (j % num_qb if band is None
              else band["query_block"](i, j % steps_q))
        if t:
            return _within_hull(t[1], b // kvheads * num_kb + i, qb)
        if band is not None:   # clamped to the band of key block i
            return jnp.clip(qb, jax.lax.div(i * block_k, block_q), jnp.minimum(
                num_qb - 1, jax.lax.div((i + 1) * block_k + window - 2,
                                        block_q)))
        if causal:  # each head's first query block at or under the key block
            return jnp.maximum(qb, jax.lax.div(i * block_k, block_q))
        return qb

    q_head = lambda b, j: b * group + j // steps_q
    q_stream = lambda b, i, j, *t: (q_head(b, j), q_blk(b, i, j, *t), 0)
    kv_own = lambda b, i, j, *t: (b, i, 0)
    in_specs_kv = [
        pl.BlockSpec((1, block_q, d), q_stream),  # q
        pl.BlockSpec((1, block_k, d), kv_own),    # k
        pl.BlockSpec((1, block_k, dv), kv_own),   # v
        pl.BlockSpec((1, block_q, dv), q_stream), # do
        stat_spec(q_stream),                      # lse
        stat_spec(q_stream),                      # delta
    ]
    operands_kv = [q, k, v, do, lse3, delta3]
    lane_own = pl.BlockSpec((1, 1, block_k),
                            lambda b, i, j, *t: (b // kvheads, 0, i))
    if has_mask:
        in_specs_kv.append(lane_own)
        operands_kv.append(mask3)
    if has_segs:
        in_specs_kv.append(stat_spec(
            lambda b, i, j, *t: (q_head(b, j) // heads,
                                 q_blk(b, i, j, *t), 0)))
        operands_kv.append(_seg_stat(q_segs))
        in_specs_kv.append(lane_own)
        operands_kv.append(kv_segs[:, None, :])
    dk, dv = _pallas(
        functools.partial(_bwd_dkv_kernel, heads=kvheads, group=group,
                          **shared),
        name="flash_bwd_dkv" if band is None else "flash_window_bwd_dkv",
        grid=(bhkv, num_kb, group * steps_q),
        in_specs=in_specs_kv,
        out_specs=[pl.BlockSpec((1, block_k, d), kv_own),
                   pl.BlockSpec((1, block_k, dv), kv_own)],
        out_shape=[
            jax.ShapeDtypeStruct((bhkv, s, d), k.dtype),
            jax.ShapeDtypeStruct((bhkv, s, dv), v.dtype),
        ],
        scratch_shapes=[
            vmem((block_k, d), jnp.float32),
            vmem((block_k, dv), jnp.float32),
        ],
        tables=tables,
        interpret=interpret,
    )(*operands_kv)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, kv_mask, q_segs, kv_segs, scale, causal, group, block_q,
           block_k, interpret, window):
    o, _ = _flash_fwd(q, k, v, kv_mask, scale=scale, causal=causal,
                      group=group, block_q=block_q, block_k=block_k,
                      interpret=interpret, q_segs=q_segs, kv_segs=kv_segs,
                      window=window)
    return o


def _flash_vjp_fwd(q, k, v, kv_mask, q_segs, kv_segs, scale, causal, group,
                   block_q, block_k, interpret, window):
    o, lse = _flash_fwd(q, k, v, kv_mask, scale=scale, causal=causal,
                        group=group, block_q=block_q, block_k=block_k,
                        interpret=interpret, q_segs=q_segs, kv_segs=kv_segs,
                        window=window)
    o, lse = (checkpoint_name(o, FLASH_OUT_NAME),
              checkpoint_name(lse, FLASH_OUT_NAME))
    return o, (q, k, v, kv_mask, o, lse, q_segs, kv_segs)


def _flash_vjp_bwd(scale, causal, group, block_q, block_k, interpret, window,
                   res, g):
    dq, dk, dv = _flash_bwd(res, g, scale=scale, causal=causal, group=group,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret, window=window)
    return dq, dk, dv, None, None, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def as_kv_mask(mask, batch: int, sk: int):
    """Reduce a broadcastable attend-mask to key-only [B, Sk] form, or raise.

    Accepts [B, Sk], [Sk], and the BERT-style [B, 1, 1, Sk] / [B, 1, Sk]
    (any unit middle dims). A mask that varies along the query axis cannot be
    streamed key-blockwise — callers should use impl='xla' for those.
    """
    m = jnp.asarray(mask)
    if m.ndim == 1:
        m = m[None, :]
    while m.ndim > 2:
        if m.shape[1] != 1:
            raise NotImplementedError(
                f"flash kernel supports key-only (padding) masks; got a mask "
                f"of shape {jnp.shape(mask)} that varies over queries/heads — "
                f"use impl='xla'")
        m = m[:, 0]
    if m.shape[-1] != sk:
        raise ValueError(f"mask key dim {m.shape[-1]} != seq {sk}")
    if m.shape[0] == 1 and batch > 1:
        m = jnp.broadcast_to(m, (batch, sk))
    # int32: native VPU lane width — int8 would hit the (32, 128) tile rule
    return m.astype(jnp.int32)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    bias=None,
    mask=None,
    causal: bool = False,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
    window: int | None = None,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    """BSHD flash attention (Pallas). Differentiable (custom VJP).

    ``mask`` may be a key-only padding mask (see :func:`as_kv_mask`); ``k``/
    ``v`` may carry fewer (grouped) heads than ``q`` (GQA). ``v``'s head size
    may differ from ``q``'s and ``k``'s (module docstring); the output has
    ``v``'s, and the default ``scale`` is ``q``'s ``d_qk ** -0.5``.
    ``segment_ids`` ([B, S] int32, VERDICT r2 #4): packed-sequence document
    ids — position i may attend to j only when ``segment_ids[b, i] ==
    segment_ids[b, j]``, so multiple short documents packed into one row
    never attend across boundaries; streamed blockwise (q side sublane-
    oriented, k side lane-oriented), composes with ``mask`` and ``causal``.
    ``window`` (with ``causal``; a static positive int): query ``i`` sees key
    ``j`` only where ``i - j < window`` (``window`` keys, itself among them);
    the three calls are then ``flash_window_fwd``, ``flash_window_bwd_dq``
    and ``flash_window_bwd_dkv``, whose grids span the band's blocks
    (:func:`band_blocks`) and not the row's, and compose with segment ids,
    GQA and a key mask. ``interpret=None`` auto-selects interpreter mode
    off-TPU so tests run on CPU; on TPU the kernel compiles via Mosaic.
    """
    if bias is not None:
        raise NotImplementedError(
            "flash kernel does not take additive bias; use impl='xla'")
    b, sq, h, d = q.shape
    if k.shape[:3] != v.shape[:3]:
        raise ValueError(f"k/v shapes must match but for the head size: "
                         f"{k.shape} vs {v.shape}")
    bk, sk, hkv, dk = k.shape
    if (bk, dk) != (b, d) or sk != sq:
        raise ValueError(f"q/k shape mismatch: {q.shape} vs {k.shape}")
    if h % hkv:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {hkv}")
    group = h // hkv
    if window is not None and (not causal or isinstance(window, bool)
                               or not isinstance(window, int) or window < 1):
        raise ValueError(f"window {window!r}: a positive int, with causal "
                         f"(got causal={causal})")
    kv_mask = as_kv_mask(mask, b, sk) if mask is not None else None
    segs = None
    if segment_ids is not None:
        segs = jnp.asarray(segment_ids)
        if segs.shape != (b, sq):
            raise ValueError(
                f"segment_ids must be [batch, seq] = {(b, sq)}, got {segs.shape}")
        segs = segs.astype(jnp.int32)
    block_q = min(block_q, sq)
    block_k = min(block_k, sq)
    if sq % block_q or sq % block_k:
        raise ValueError(f"seq len {sq} must divide by blocks ({block_q}, {block_k})")
    interpret = pallas_interpret(interpret)
    if not interpret:
        # Mosaic block rule: second-to-minor dim divisible by 8 (or whole),
        # minor (lane) dim divisible by 128 (or whole). block_q/block_k sit in
        # the sublane dim of the q/k/v blocks; block_k additionally lands in
        # the LANE dim of the mask block [1, 1, block_k] when a mask is given.
        if block_q % 8 and block_q != sq:
            raise ValueError(f"TPU requires block_q % 8 == 0, got {block_q}")
        if block_k % 8 and block_k != sq:
            raise ValueError(f"TPU requires block_k % 8 == 0, got {block_k}")
        if ((kv_mask is not None or segs is not None)
                and block_k % 128 and block_k != sq):
            raise ValueError(
                f"TPU requires block_k % 128 == 0 with a mask/segment ids, "
                f"got {block_k}")
    scale = scale if scale is not None else d**-0.5

    # BSHD → [B·H, S, D] for the kernels (head-major: q row r ↔ kv row r//group)
    def flat(x):
        bb, ss, hh, dd = x.shape
        return x.transpose(0, 2, 1, 3).reshape(bb * hh, ss, dd)

    o = _flash(flat(q), flat(k), flat(v), kv_mask, segs, segs,
               scale, causal, group, block_q, block_k, interpret, window)
    return o.reshape(b, h, sq, v.shape[-1]).transpose(0, 2, 1, 3)
