"""Attention over a learned per-query selection of keys (DeepSeek sparse
attention): the indexer's scores, the exact top-k selection, softmax
attention over the selected keys only, and the indexer's training loss.

The mathematics, for one sequence (``t`` a query position, ``s`` a key)::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])      s <= t, float32
    S_t     = the min(t + 1, topk) keys s <= t of largest I[t, s],
              ties to the lower s (``jax.lax.top_k``'s rule)
    o[t]    = sum_{s in S_t} softmax_{s in S_t}(q[t] . k[s] * scale) v[s]
    target  = sum_h p_h[t, s] over the query heads, L1-normalised over S_t
    L_I[t]  = KL(target[t, .] || softmax_{s in S_t} I[t, s])

The selection carries no gradient. ``o`` trains q, k, v; ``L_I`` trains the
indexer's inputs (qI, kI, w) and nothing else (the main attention's
distribution is a constant of it).

How it is built for the chip (S = 8192, topk = 2048, 32 heads):

* nothing of size ``[B, H, S, S]`` exists. Three ``[B, S, S]`` arrays do: the
  scores (f32), the selection as an int8 mask, and the loss's cotangent on the
  scores (f32);
* the selection is kept as a mask and the attention is a blockwise
  masked-dense pass over ``block x block`` tiles (the flash scheme of
  :mod:`.flash_attention` with one more operand). A uniform-looking
  selection of a quarter of the keys leaves no tile empty, so a gather
  would move 2048 keys x 4 heads x 256 values a query to save nothing;
* exactness: the mask is ``I > threshold``, plus the keys tied AT the
  threshold up to a cut index, lowest ``s`` first: the same set as
  ``lax.top_k``'s indices, always. Threshold and cut of every query come
  from a radix select (``dsa_index_select``), not from a sort. Ties at the
  threshold are no corner case: among 16,384 rows of 8,192 float32 scores
  some row has one in most steps;
* seven Pallas kernels, each named for the trace: ``dsa_index_fwd``,
  ``dsa_index_select``,
  ``dsa_index_bwd``, ``dsa_attend_fwd``, ``dsa_attend_bwd_dq``,
  ``dsa_attend_bwd_dkv``, ``dsa_kl_target``. MXU operands are the inputs'
  dtype (bf16 in training), accumulation and softmax are float32.

:func:`indexed_attention_xla` is the same mathematics in plain ``jax.numpy``
over dense ``[B, H, S, S]`` arrays with ``jax.grad`` for its backward pass:
what runs off the TPU and at shapes the kernels do not take, and what the
tests hold the kernels against.

Layout at the API: q ``[B, S, H, D]``, k and v ``[B, S, Hkv, D]``, the
indexer's queries ``[B, S, Hi, Di]``, its one key head ``[B, S, Di]`` and
its head weights ``[B, S, Hi]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from distributeddeeplearningspark_tpu.ops.flash_attention import (
    _MASK_VALUE,
    STAT_LANES,
    _vmem,
)
from distributeddeeplearningspark_tpu.utils.env import pallas_interpret

DEFAULT_BLOCK = 512
#: ``jax.ad_checkpoint.checkpoint_name`` of the selection (mask and the
#: log-sum-exp of the selected scores)
SELECTION_NAME = "dsa_selection"
#: ... and of the attention's output and log-sum-exp
ATTEND_NAME = "dsa_attend_out"
_NEG_INF = float("-inf")


#: scoped VMEM a kernel may use. The default, 16 MiB, is a seventh of a
#: v5e's 128; the loss's kernel holds eight heads' queries and two float32
#: tiles, double-buffered, and needs 17
_VMEM_LIMIT = 64 * 2 ** 20


def _grid_params(*semantics: str):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _stat(x):
    """[.., S] row statistics -> [.., S, STAT_LANES] (Mosaic's block rule:
    see :mod:`.flash_attention`)."""
    return jnp.broadcast_to(x[..., None], (*x.shape, STAT_LANES))


# The kernels' products take their operands as they come (bf16 in training)
# and accumulate in float32. The precision is pinned: under a caller's
# ``jax.default_matmul_precision("highest")`` Mosaic is asked for an fp32
# contraction of bf16 operands, and refuses ("Bad lhs type").
_PRECISION = jax.lax.Precision.DEFAULT


def _dot(a, b):
    """a [M, K] . b [K, N] -> [M, N] in float32."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=_PRECISION)


def _dot_t(a, b):
    """a [M, K] . b [N, K]^T -> [M, N] in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_PRECISION)


def _tdot(a, b):
    """a [K, M]^T . b [K, N] -> [M, N] in float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_PRECISION)


def _kl_terms(target, logq):
    """``target * (log target - logq)`` elementwise, 0 where the target is
    (and so where ``logq`` may be -inf: outside the selection)."""
    on = target > 0.0
    return jnp.where(on, target * (jnp.log(jnp.where(on, target, 1.0))
                                   - jnp.where(on, logq, 0.0)), 0.0)


def _diag_allowed(qb, kb, block):
    q_pos = qb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    k_pos = kb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return q_pos >= k_pos


# ---------------------------------------------------------------------------
# the indexer's scores
# ---------------------------------------------------------------------------

def _index_fwd_kernel(q_ref, k_ref, w_ref, o_ref, *, heads: int, block: int):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb > qb)
    def _above():
        o_ref[0] = jnp.full((block, block), _NEG_INF, jnp.float32)

    @pl.when(kb <= qb)
    def _compute():
        k = k_ref[0]
        w = w_ref[0]
        acc = jnp.zeros((block, block), jnp.float32)
        for j in range(heads):
            acc += w[:, j:j + 1] * jnp.maximum(_dot_t(q_ref[0, j], k), 0.0)
        o_ref[0] = jnp.where(_diag_allowed(qb, kb, block), acc, _NEG_INF)


def _index_scores_kernel(qi, ki, wi, *, block, interpret):
    """qi [B, Hi, S, Di], ki [B, S, Di], wi [B, S, Hi] f32 -> [B, S, S] f32,
    ``-inf`` above the diagonal."""
    b, hi, s, di = qi.shape
    n = s // block
    return pl.pallas_call(
        functools.partial(_index_fwd_kernel, heads=hi, block=block),
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, hi, block, di), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block, di),
                         lambda b, i, j: (b, jnp.minimum(j, i), 0)),
            pl.BlockSpec((1, block, hi), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, block), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=_grid_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_index_fwd",
    )(qi, ki, wi)


def _index_bwd_kernel(q_ref, k_ref, w_ref, di_ref, g_ref, dq_ref, dk_ref,
                      dw_ref, *, heads: int, block: int):
    """One pass for all three gradients: dq and dw accumulate over the key
    blocks of a query block (their output blocks stay put meanwhile), dk is
    the whole ``[S, Di]`` array, resident for the batch row, and takes each
    key block's part as it comes."""
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when((qb == 0) & (kb == 0))
    def _init_dk():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(kb == 0)
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(kb <= qb)
    def _compute():
        k = k_ref[0]
        w = w_ref[0]
        # the cotangent on I[t, s]: zero wherever s is not selected, the
        # diagonal block's upper triangle included
        di = di_ref[0] * g_ref[0, :, 0][:, None]
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        dw = jnp.zeros(w.shape, jnp.float32)
        dk = jnp.zeros(k.shape, jnp.float32)
        for j in range(heads):
            q = q_ref[0, j]
            s = _dot_t(q, k)
            dw = dw + jnp.where(
                lane == j,
                jnp.sum(di * jnp.maximum(s, 0.0), axis=1, keepdims=True), 0.0)
            g = jnp.where(s > 0.0, di * w[:, j:j + 1], 0.0).astype(k.dtype)
            dq_ref[0, j] += _dot(g, k)
            dk = dk + _tdot(g, q)
        dw_ref[0] += dw
        rows = pl.ds(pl.multiple_of(kb * block, block), block)
        dk_ref[0, rows, :] += dk


def _index_bwd(qi, ki, wi, dscores, g_rows, *, block, interpret):
    """Gradients of ``sum_t g[t] * sum_s dscores[t, s] * I[t, s]`` in float32:
    (dqi [B, Hi, S, Di], dki [B, S, Di], dwi [B, S, Hi])."""
    b, hi, s, di = qi.shape
    n = s // block
    prev = lambda b, i, j: (b, i, jnp.minimum(j, i))
    return pl.pallas_call(
        functools.partial(_index_bwd_kernel, heads=hi, block=block),
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, hi, block, di), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block, di),
                         lambda b, i, j: (b, jnp.minimum(j, i), 0)),
            pl.BlockSpec((1, block, hi), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block, block), prev),
            pl.BlockSpec((1, block, STAT_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hi, block, di), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, s, di), lambda b, i, j: (b, 0, 0)),
            pl.BlockSpec((1, block, hi), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hi, s, di), jnp.float32),
            jax.ShapeDtypeStruct((b, s, di), jnp.float32),
            jax.ShapeDtypeStruct((b, s, hi), jnp.float32),
        ],
        compiler_params=_grid_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="dsa_index_bwd",
    )(qi, ki, wi, dscores, _stat(g_rows))


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------

_SIGN_FLIP = 0x7FFFFFFF
SELECT_ROWS = 128


def _ordered_bits(x):
    """float32 <-> int32 whose signed order is the floats' order (its own
    inverse): a negative float's magnitude bits are flipped."""
    return jnp.where(x < 0, x ^ jnp.int32(_SIGN_FLIP), x)


def _select_kernel(x_ref, thr_ref, cut_ref, key_ref, *, k: int):
    """For every query of a block of rows, exactly and with no sort: the
    threshold (the ``min(t + 1, k)``-th largest score, a radix select: its
    32 bits are found from the top, each by counting how many of the row's
    scores reach the candidate) and, for the scores tied AT the threshold,
    the key index up to which they are taken (``lax.top_k`` takes ties
    lowest index first; the index's bits are found the same way). 32 + 13
    passes of compare and add over rows that stay in VMEM."""
    rows, s = key_ref.shape
    bits = jax.lax.bitcast_convert_type(x_ref[0], jnp.int32)
    key_ref[:] = _ordered_bits(bits)

    def count(hit):
        return jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)

    zero = jnp.zeros((rows, 1), jnp.int32)
    lowest = jnp.full((rows, 1), jnp.iinfo(jnp.int32).min, jnp.int32)
    prefix = jnp.where(count(key_ref[:] >= zero) >= k, zero, lowest)

    def value_bit(b, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), 30 - b)
        return jnp.where(count(key_ref[:] >= cand) >= k, cand, prefix)

    thr = jax.lax.fori_loop(0, 31, value_bit, prefix)
    # a query with t + 1 <= k keys takes them all (its k-th largest is one
    # of the -inf above the diagonal, and nothing tied with that is taken)
    t = pl.program_id(1) * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0)
    room = jnp.minimum(t + 1, k) - count(key_ref[:] > thr)
    index_bits = max(1, (s - 1).bit_length())

    def index_bit(b, cut):
        cand = cut | jnp.left_shift(jnp.int32(1), index_bits - 1 - b)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, s), 1)
        before = count((key_ref[:] == thr) & (col < cand))
        return jnp.where(before < room, cand, cut)

    cut = jax.lax.fori_loop(0, index_bits, index_bit, zero)
    cut = jnp.where(room > 0, cut, -1)
    thr_ref[0] = jnp.broadcast_to(
        jax.lax.bitcast_convert_type(_ordered_bits(thr), jnp.float32),
        thr_ref.shape[1:])
    cut_ref[0] = jnp.broadcast_to(cut, cut_ref.shape[1:])


def select_topk(scores: jax.Array, topk: int, *,
                interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """``scores`` [B, S, S] float32 with ``-inf`` above the diagonal ->
    ``(mask [B, S, S] int8, lse [B, S])``: 1 on the ``min(t + 1, topk)``
    causal keys of largest score of every query, exactly ``lax.top_k``'s set
    (ties to the lower key; a score of -0.0 counts as +0.0), and the
    log-sum-exp of the selected scores. A sort of 8,192-wide rows
    (``lax.top_k``) took 94 ms a layer on the v5e, three times the attention
    it selects for; the kernel reads the scores once."""
    b, s, _ = scores.shape
    k = min(topk, s)
    rows = min(SELECT_ROWS, s)
    if s % rows:
        raise ValueError(f"sequence {s} must divide by {rows}")
    # relu's zeros times weights of either sign leave zeros of either sign;
    # ``lax.top_k`` sorts in the total order, -0.0 below +0.0, and ``>=``
    # does not tell them apart: a zero is +0.0 here, and in the reference
    scores = jnp.where(scores == 0.0, 0.0, scores)
    stat = pl.BlockSpec((1, rows, STAT_LANES), lambda b, i: (b, i, 0))
    thr, cut = pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid=(b, s // rows),
        in_specs=[pl.BlockSpec((1, rows, s), lambda b, i: (b, i, 0))],
        out_specs=[stat, stat],
        out_shape=[jax.ShapeDtypeStruct((b, s, STAT_LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, STAT_LANES), jnp.int32)],
        scratch_shapes=[_vmem()((rows, s), jnp.int32)],
        compiler_params=_grid_params("parallel", "parallel"),
        interpret=pallas_interpret(interpret),
        name="dsa_index_select",
    )(scores)
    thr, cut = thr[..., :1], cut[..., :1]
    col = jnp.arange(s, dtype=jnp.int32)
    causal = col[None, :] <= col[:, None]
    sel = causal & ((scores > thr) | ((scores == thr) & (col <= cut)))
    lse = jax.nn.logsumexp(jnp.where(sel, scores, _NEG_INF), axis=-1)
    # named so that a remat policy can KEEP the selection: a replay that
    # recomputes the scores gets them an ulp off wherever XLA fuses their
    # inputs differently, the key at the threshold flips, and the backward
    # pass differentiates another function than the forward pass computed
    # (1% of the gradient at a toy size). Kept, the replay selects nothing.
    return (checkpoint_name(sel.astype(jnp.int8), SELECTION_NAME),
            checkpoint_name(lse, SELECTION_NAME))


# ---------------------------------------------------------------------------
# attention over the selection
# ---------------------------------------------------------------------------

def _masked_probs(q_ref, k_ref, m_ref, row_max, scale):
    """(s, allowed) of one tile; ``row_max`` None -> raw masked logits."""
    s = _dot_t(q_ref, k_ref) * scale
    allowed = m_ref != 0
    if row_max is None:
        return jnp.where(allowed, s, _MASK_VALUE), allowed
    return jnp.where(allowed, jnp.exp(s - row_max), 0.0), allowed


def _attend_fwd_kernel(q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref, acc_ref,
                       mx_ref, l_ref, *, scale: float, num_kb: int):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        mx_ref[:] = jnp.full_like(mx_ref, _MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(kb <= qb)
    def _compute():
        s, allowed = _masked_probs(q_ref[0], k_ref[0], m_ref[0], None, scale)
        m_prev = mx_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(allowed, jnp.exp(s - m_cur[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_cur)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        mx_ref[:, 0] = m_cur
        acc_ref[:] = acc_ref[:] * corr[:, None] + _dot(
            p.astype(v_ref.dtype), v_ref[0])

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l = l_ref[:, 0]  # never 0: every query selects itself or better
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to((mx_ref[:, 0] + jnp.log(l))[:, None],
                                      lse_ref.shape[1:])


def _attend_fwd(q, k, v, mask, *, scale, group, block, interpret):
    """q [B*H, S, D], k and v [B*Hkv, S, D], mask [B, S, S] int8 ->
    (o [B*H, S, D], lse [B*H, S] f32)."""
    bh, s, d = q.shape
    heads = bh // mask.shape[0]
    n = s // block
    kv = lambda b, i, j: (b // group, jnp.minimum(j, i), 0)
    o, lse = pl.pallas_call(
        functools.partial(_attend_fwd_kernel, scale=scale, num_kb=n),
        grid=(bh, n, n),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block, d), kv),
            pl.BlockSpec((1, block, d), kv),
            pl.BlockSpec((1, block, block),
                         lambda b, i, j: (b // heads, i, jnp.minimum(j, i))),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block, STAT_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            _vmem()((block, d), jnp.float32),
            _vmem()((block, 128), jnp.float32),
            _vmem()((block, 128), jnp.float32),
        ],
        compiler_params=_grid_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_attend_fwd",
    )(q, k, v, mask)
    return o, lse[..., 0]


def _attend_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          m_ref, dq_ref, acc_ref, *, scale: float,
                          num_kb: int):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(kb <= qb)
    def _compute():
        k = k_ref[0]
        p, _ = _masked_probs(q_ref[0], k, m_ref[0],
                             lse_ref[0, :, 0][:, None], scale)
        dp = _dot_t(do_ref[0], v_ref[0])
        ds = p * (dp - delta_ref[0, :, 0][:, None])
        acc_ref[:] += _dot(ds.astype(k.dtype), k)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _attend_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           m_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                           scale: float, num_qb: int, group: int):
    """dK and dV of ONE kv head: the innermost grid index sweeps (query head
    of the group, query block), as in ``flash_bwd_dkv``."""
    kb, j = pl.program_id(1), pl.program_id(2)
    qb = j % num_qb

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(kb <= qb)
    def _compute():
        q, do = q_ref[0], do_ref[0]
        p, _ = _masked_probs(q, k_ref[0], m_ref[0],
                             lse_ref[0, :, 0][:, None], scale)
        dv_acc[:] += _tdot(p.astype(do.dtype), do)
        dp = _dot_t(do, v_ref[0])
        ds = p * (dp - delta_ref[0, :, 0][:, None])
        dk_acc[:] += _tdot(ds.astype(q.dtype), q)

    @pl.when(j == group * num_qb - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _attend_bwd(q, k, v, mask, o, lse, do, *, scale, group, block, interpret):
    bh, s, d = q.shape
    bhkv = k.shape[0]
    heads = bh // mask.shape[0]
    kvheads = bhkv // mask.shape[0]
    n = s // block
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse3, delta3 = _stat(lse), _stat(delta)
    row = lambda b, i, j: (b, i, 0)
    kv = lambda b, i, j: (b // group, jnp.minimum(j, i), 0)
    stat_spec = lambda ix: pl.BlockSpec((1, block, STAT_LANES), ix)
    dq = pl.pallas_call(
        functools.partial(_attend_bwd_dq_kernel, scale=scale, num_kb=n),
        grid=(bh, n, n),
        in_specs=[
            pl.BlockSpec((1, block, d), row), pl.BlockSpec((1, block, d), kv),
            pl.BlockSpec((1, block, d), kv), pl.BlockSpec((1, block, d), row),
            stat_spec(row), stat_spec(row),
            pl.BlockSpec((1, block, block),
                         lambda b, i, j: (b // heads, i, jnp.minimum(j, i))),
        ],
        out_specs=pl.BlockSpec((1, block, d), row),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[_vmem()((block, d), jnp.float32)],
        compiler_params=_grid_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_attend_bwd_dq",
    )(q, k, v, do, lse3, delta3, mask)

    # key block i, inner index j = (query head in the group) * n + query
    # block; query blocks above the diagonal (qb < i) re-read the diagonal's
    qrow = lambda b, i, j: (b * group + j // n, jnp.maximum(j % n, i), 0)
    krow = lambda b, i, j: (b, i, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_attend_bwd_dkv_kernel, scale=scale, num_qb=n,
                          group=group),
        grid=(bhkv, n, group * n),
        in_specs=[
            pl.BlockSpec((1, block, d), qrow),
            pl.BlockSpec((1, block, d), krow),
            pl.BlockSpec((1, block, d), krow),
            pl.BlockSpec((1, block, d), qrow),
            stat_spec(qrow), stat_spec(qrow),
            pl.BlockSpec((1, block, block),
                         lambda b, i, j: (b // kvheads,
                                          jnp.maximum(j % n, i), i)),
        ],
        out_specs=[pl.BlockSpec((1, block, d), krow),
                   pl.BlockSpec((1, block, d), krow)],
        out_shape=[jax.ShapeDtypeStruct((bhkv, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bhkv, s, d), v.dtype)],
        scratch_shapes=[_vmem()((block, d), jnp.float32),
                        _vmem()((block, d), jnp.float32)],
        compiler_params=_grid_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_attend_bwd_dkv",
    )(q, k, v, do, lse3, delta3, mask)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _attend(q, k, v, mask, scale, group, block, interpret):
    return _attend_fwd(q, k, v, mask, scale=scale, group=group, block=block,
                       interpret=interpret)


def _attend_vjp_fwd(q, k, v, mask, scale, group, block, interpret):
    o, lse = _attend_fwd(q, k, v, mask, scale=scale, group=group, block=block,
                         interpret=interpret)
    # named HERE, so that outputs and residuals are the named values: a remat
    # policy that keeps them spares the replay this kernel (the backward
    # kernels need o and the log-sum-exp, never the forward pass again)
    o, lse = (checkpoint_name(o, ATTEND_NAME), checkpoint_name(lse, ATTEND_NAME))
    return (o, lse), (q, k, v, mask, o, lse)


def _attend_vjp_bwd(scale, group, block, interpret, res, g):
    do, _ = g  # the log-sum-exp feeds the loss's constant target only
    dq, dk, dv = _attend_bwd(*res, do, scale=scale, group=group, block=block,
                             interpret=interpret)
    return dq, dk, dv, None


_attend.defvjp(_attend_vjp_fwd, _attend_vjp_bwd)


# ---------------------------------------------------------------------------
# the indexer's loss
# ---------------------------------------------------------------------------

def _kl_kernel(q_ref, k_ref, lse_ref, m_ref, sc_ref, slse_ref, kl_ref, d_ref,
               psum_ref, *, scale: float, group: int, heads: int):
    """Grid (B, query block, key block, kv head): the attention
    distribution of the ``group`` query heads of one kv head is recomputed
    from the forward's log-sum-exp and summed into ``psum``; after the last
    kv head the tile's share of the KL and the cotangent on the scores,
    ``softmax_S(I) - target``, are written."""
    qb, kb, hk = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    last = pl.num_programs(3) - 1

    @pl.when((kb == 0) & (hk == 0))
    def _init_kl():
        kl_ref[...] = jnp.zeros_like(kl_ref)

    @pl.when(kb <= qb)
    def _compute():
        @pl.when(hk == 0)
        def _init():
            psum_ref[:] = jnp.zeros_like(psum_ref)

        k, m = k_ref[0], m_ref[0]

        def one_head(g, acc):
            p, _ = _masked_probs(q_ref[g], k, m, lse_ref[g, :, 0][:, None],
                                 scale)
            return acc + p

        psum_ref[:] += jax.lax.fori_loop(
            0, group, one_head, jnp.zeros(psum_ref.shape, jnp.float32))

        @pl.when(hk == last)
        def _emit():
            allowed = m != 0
            target = psum_ref[:] * (1.0 / heads)
            logq = sc_ref[0] - slse_ref[0, :, 0][:, None]
            soft = jnp.where(allowed, jnp.exp(logq), 0.0)
            d_ref[0] = soft - target
            kl_ref[0] += jnp.broadcast_to(
                jnp.sum(_kl_terms(target, logq), axis=1, keepdims=True),
                kl_ref.shape[1:])


def _kl_target(q, k, lse, mask, scores, scores_lse, *, scale, group, block,
               interpret):
    """(kl [B, S], dscores [B, S, S]): the loss of every query, and its
    cotangent on the scores (left unwritten above the diagonal, where
    :func:`_index_bwd` does not read)."""
    bh, s, d = q.shape
    b = mask.shape[0]
    heads = bh // b
    kvheads = heads // group
    n = s // block
    tile = lambda b, i, j, h: (b, i, jnp.minimum(j, i))
    qrow = lambda b, i, j, h: (b * kvheads + h, i, 0)
    kl, dscores = pl.pallas_call(
        functools.partial(_kl_kernel, scale=scale, group=group, heads=heads),
        grid=(b, n, n, kvheads),
        in_specs=[
            pl.BlockSpec((group, block, d), qrow),
            pl.BlockSpec((1, block, d),
                         lambda b, i, j, h: (b * kvheads + h,
                                             jnp.minimum(j, i), 0)),
            pl.BlockSpec((group, block, STAT_LANES), qrow),
            pl.BlockSpec((1, block, block), tile),
            pl.BlockSpec((1, block, block), tile),
            pl.BlockSpec((1, block, STAT_LANES), lambda b, i, j, h: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, STAT_LANES), lambda b, i, j, h: (b, i, 0)),
            pl.BlockSpec((1, block, block), tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        ],
        scratch_shapes=[_vmem()((block, block), jnp.float32)],
        compiler_params=_grid_params("parallel", "parallel", "arbitrary",
                                     "arbitrary"),
        interpret=interpret,
        name="dsa_kl_target",
    )(q, k, _stat(lse), mask, scores, _stat(scores_lse))
    return kl[..., 0], dscores


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12))
def _index_kl(qi, ki, wi, scores, scores_lse, mask, q, k, lse, scale, group,
              block, interpret):
    return _kl_target(q, k, lse, mask, scores, scores_lse, scale=scale,
                      group=group, block=block, interpret=interpret)[0]


def _index_kl_fwd(qi, ki, wi, scores, scores_lse, mask, q, k, lse, scale,
                  group, block, interpret):
    kl, dscores = _kl_target(q, k, lse, mask, scores, scores_lse, scale=scale,
                             group=group, block=block, interpret=interpret)
    return kl, (qi, ki, wi, dscores)


def _index_kl_bwd(scale, group, block, interpret, res, g):
    qi, ki, wi, dscores = res
    dqi, dki, dwi = _index_bwd(qi, ki, wi, dscores, g.astype(jnp.float32),
                               block=block, interpret=interpret)
    return (dqi.astype(qi.dtype), dki.astype(ki.dtype), dwi.astype(wi.dtype),
            None, None, None, None, None, None)


_index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def indexed_attention(q, k, v, index_q, index_k, index_w, *, topk: int,
                      scale: float | None = None,
                      interpret: bool | None = None
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The kernels' path: ``(o [B, S, H, D], kl [B, S] f32, selected [B] f32)``
    where ``kl`` is the indexer's loss of every query and ``selected`` the
    number of (query, key) pairs the selection kept in each row of the batch.
    Causal over the whole sequence; S a multiple of the kernels' block,
    ``min(DEFAULT_BLOCK, S)``."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    hi, di = index_q.shape[2:]
    if h % hkv:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {hkv}")
    block = min(DEFAULT_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} must divide by the block {block}")
    interpret = pallas_interpret(interpret)
    if not interpret and (block % 128 or d % 8 or di % 8):
        raise ValueError(
            f"on the TPU the block ({block}) must be a multiple of 128 (it is "
            f"a lane dim of the mask's tile) and head sizes ({d}, {di}) of 8")
    scale = d ** -0.5 if scale is None else scale
    group = h // hkv

    def flat(x):  # [B, S, N, D] -> [B*N, S, D]
        return x.transpose(0, 2, 1, 3).reshape(-1, s, x.shape[-1])

    sg = jax.lax.stop_gradient
    qi = index_q.transpose(0, 2, 1, 3)
    wi = index_w.astype(jnp.float32)
    scores = _index_scores_kernel(sg(qi), sg(index_k), sg(wi), block=block,
                                  interpret=interpret)
    mask, scores_lse = select_topk(scores, topk, interpret=interpret)
    qf, kf, vf = flat(q), flat(k), flat(v)
    o, lse = _attend(qf, kf, vf, mask, scale, group, block, interpret)
    kl = _index_kl(qi, index_k, wi, scores, scores_lse, mask, sg(qf), sg(kf),
                   sg(lse), scale, group, block, interpret)
    selected = jnp.sum(mask, axis=(1, 2), dtype=jnp.float32)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3), kl, selected


def indexed_attention_xla(q, k, v, index_q, index_k, index_w, *, topk: int,
                          scale: float | None = None
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The same function in dense ``jax.numpy`` (``[B, H, S, S]`` arrays and
    ``jax.grad``): off the TPU, and at shapes the kernels do not take."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    scale = d ** -0.5 if scale is None else scale
    sg = jax.lax.stop_gradient
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))

    def index_scores(qi, ki, wi):
        dots = jnp.einsum("bthd,bsd->bhts", qi, ki,
                          preferred_element_type=jnp.float32)
        sc = jnp.einsum("bhts,bth->bts", jnp.maximum(dots, 0.0),
                        wi.astype(jnp.float32))
        return jnp.where(causal, sc, _NEG_INF)

    scores = index_scores(index_q, index_k, index_w)
    mask, _ = select_topk(sg(scores), topk)
    sel = mask != 0
    qg = q.reshape(b, s, hkv, group, d)
    logits = jnp.einsum("btngd,bsnd->bngts", qg, k,
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(
        jnp.where(sel[:, None, None], logits, _MASK_VALUE), axis=-1)
    probs = jnp.where(sel[:, None, None], probs, 0.0)
    o = jnp.einsum("bngts,bsnd->btngd", probs.astype(v.dtype), v)
    target = sg(probs).sum(axis=(1, 2))
    target = target / target.sum(-1, keepdims=True)
    logq = jax.nn.log_softmax(jnp.where(sel, scores, _NEG_INF), axis=-1)
    kl = jnp.sum(_kl_terms(target, logq), axis=-1)
    selected = jnp.sum(mask, axis=(1, 2), dtype=jnp.float32)
    return o.reshape(b, s, h, d), kl, selected
