"""Attention ops: one call site, pluggable implementations.

Models call :func:`dot_product_attention`; the implementation is chosen by
``impl``:

- ``"xla"`` — plain einsum softmax attention. XLA fuses the scale/mask/softmax
  chain into the matmuls well enough for short sequences (BERT's 512).
- ``"flash"`` — Pallas blockwise flash attention (O(seq) memory, HBM-tiled);
  the long-sequence hot op (see :mod:`.flash_attention`). Handles key-padding
  masks and grouped (GQA) K/V natively.
- ``"ring"`` — context-parallel exact attention over the mesh ``seq`` axis
  (see :mod:`.ring_attention`); use when sequences are sharded across chips.
- ``"ulysses"`` — context-parallel exact attention via all-to-all head
  scatter (see :mod:`.ulysses`): 2 collectives per call and full-sequence
  local flash, but heads must divide by the ``seq`` degree; the ring has
  no head constraint and O(S/n) memory.
- ``"auto"`` — flash on TPU when the shape qualifies (seq multiple of the
  block size, head_dim lane-friendly, mask expressible key-only), else xla.

:func:`indexed_attention` is the second call site: causal attention over a
learned per-query selection of keys (:mod:`.indexed_attention`), with the
same choice of a kernel path and an XLA path and the same ``shard_map``.

``v`` (and the output) may have another head size than ``q`` and ``k``
(latent attention's 192 / 128): every implementation but the ring and ulysses
paths takes the two as they are, and the default scale is ``q``'s.

All implementations take/return ``[batch, seq, heads, head_dim]`` (BSHD
layout — batch and sequence leading so (data, fsdp) batch sharding and
``seq``-axis context parallelism shard the first two dims without transposes).
K/V may carry fewer heads than Q (GQA; ``num_heads % num_kv_heads == 0``) —
the flash kernel and the ring path index/compute grouped heads directly;
only the xla fallback broadcasts KV up (an O(group) HBM copy).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributeddeeplearningspark_tpu.parallel.mesh import AXIS_TENSOR, BATCH_AXES
from distributeddeeplearningspark_tpu.utils.env import on_tpu


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    bias: jax.Array | None = None,
    mask: jax.Array | None = None,
    causal: bool = False,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
    impl: str = "auto",
) -> jax.Array:
    """Softmax attention over BSHD tensors.

    ``mask``: bool, True = attend, broadcastable to [B, H, Sq, Sk].
    ``bias``: additive, broadcastable to [B, H, Sq, Sk].
    ``segment_ids``: [B, S] int32 packed-sequence ids — attention is blocked
    across different ids (VERDICT r2 #4 sequence packing); the flash kernel
    streams them blockwise, the XLA path expands them into the mask.
    """
    if impl == "auto":
        impl = _pick_impl(q, k, bias, mask, v)
    if impl == "flash":
        return _flash_on_mesh(q, k, v, bias=bias, mask=mask, causal=causal,
                              scale=scale, segment_ids=segment_ids)
    if impl == "ring":
        from distributeddeeplearningspark_tpu.ops.ring_attention import ring_attention

        # GQA-native: grouped KV rides the ring at Hkv width, no repeat;
        # segment ids shard over seq and ride the ring like the mask
        return ring_attention(q, k, v, bias=bias, mask=mask, causal=causal,
                              scale=scale, segment_ids=segment_ids)
    if impl == "ulysses":
        from distributeddeeplearningspark_tpu.ops.ulysses import ulysses_attention

        # all-to-all CP: head-scatter/seq-gather, full-sequence local flash
        # (2 collectives vs the ring's n−1 hops; heads must divide by seq)
        return ulysses_attention(q, k, v, bias=bias, mask=mask, causal=causal,
                                 scale=scale, segment_ids=segment_ids)
    k, v = _expand_gqa(q, k, v)
    if segment_ids is not None:
        seg_mask = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = seg_mask if mask is None else jnp.logical_and(mask, seg_mask)
    return _xla_attention(q, k, v, bias=bias, mask=mask, causal=causal, scale=scale)


def _flash_on_mesh(q, k, v, *, bias, mask, causal, scale, segment_ids):
    """The flash kernel, laid out on the mesh the step is compiled for.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned", first seen on the four-chip host), so under
    a multi-device mesh the kernel runs inside a ``shard_map``: batch rows
    over (data, fsdp), heads over ``tensor``, sequence whole (sequences
    sharded over ``seq`` take the ring/ulysses paths instead). Attention
    never mixes batch rows or heads, so each shard's kernel is the whole
    computation for its slice and no collective is needed.
    """
    from distributeddeeplearningspark_tpu.ops.flash_attention import (
        as_kv_mask,
        flash_attention,
    )
    from distributeddeeplearningspark_tpu.ops.ring_attention import resolve_mesh

    mesh = resolve_mesh()
    if mesh is None or mesh.size == 1 or bias is not None:
        return flash_attention(q, k, v, bias=bias, mask=mask, causal=causal,
                               scale=scale, segment_ids=segment_ids)
    if not _shards_evenly(q, k, mesh):
        raise ValueError(
            f"flash attention on mesh {dict(mesh.shape)}: batch {q.shape[0]} "
            f"must divide by data x fsdp and heads {q.shape[2]}/{k.shape[2]} "
            f"by tensor — use impl='xla' (impl='auto' does) for this shape")
    b, s = q.shape[:2]
    qkv = P(BATCH_AXES, None, AXIS_TENSOR, None)
    extras = []
    if mask is not None:
        extras.append(as_kv_mask(mask, b, s))
    if segment_ids is not None:
        extras.append(jnp.asarray(segment_ids, jnp.int32))
    has_mask = mask is not None

    def local(qq, kk, vv, *ex):
        return flash_attention(
            qq, kk, vv, mask=ex[0] if has_mask else None, causal=causal,
            scale=scale, segment_ids=ex[-1] if segment_ids is not None else None)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(qkv, qkv, qkv, *([P(BATCH_AXES, None)] * len(extras))),
        out_specs=qkv, check_vma=False)(q, k, v, *extras)


def indexed_attention(q, k, v, index_q, index_k, index_w, *, topk: int,
                      scale: float | None = None, impl: str = "auto"
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Causal softmax attention of every query over the ``topk`` keys its
    indexer scores highest, and the indexer's loss: ``(o [B, S, H, D],
    kl [B, S], selected [B])``; see :mod:`.indexed_attention` for the
    mathematics and the shapes.

    ``impl``: ``"pallas"`` (the kernels), ``"xla"`` (dense ``jax.numpy``),
    or ``"auto"``: the kernels on a TPU when the sequence divides by their
    block, else XLA. On a mesh of more than one device the kernels run in a
    ``shard_map`` over the batch rows, like the flash kernel and for the same
    reason; every head stays on every device (the indexer's selection is one
    per query, shared by all heads, so splitting heads would repeat it).
    """
    from distributeddeeplearningspark_tpu.ops import indexed_attention as ia
    from distributeddeeplearningspark_tpu.ops.ring_attention import resolve_mesh

    s, d = q.shape[1], q.shape[3]
    if impl == "auto":
        block = min(ia.DEFAULT_BLOCK, s)
        fits = not (s % block or block % 128 or d % 8 or index_q.shape[3] % 8)
        impl = "pallas" if on_tpu() and fits else "xla"
    if impl == "xla":
        return ia.indexed_attention_xla(q, k, v, index_q, index_k, index_w,
                                        topk=topk, scale=scale)
    if impl != "pallas":
        raise ValueError(f"unknown indexed-attention impl {impl!r}")
    fn = functools.partial(ia.indexed_attention, topk=topk, scale=scale)
    mesh = resolve_mesh()
    if mesh is None or mesh.size == 1:
        return fn(q, k, v, index_q, index_k, index_w)
    rows = mesh.shape[BATCH_AXES[0]] * mesh.shape[BATCH_AXES[1]]
    if q.shape[0] % rows:
        raise ValueError(f"indexed attention on mesh {dict(mesh.shape)}: "
                         f"batch {q.shape[0]} must divide by data x fsdp")
    by_row = lambda x: P(BATCH_AXES, *([None] * (x.ndim - 1)))
    args = (q, k, v, index_q, index_k, index_w)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(by_row(a) for a in args),
        out_specs=(P(BATCH_AXES, None, None, None), P(BATCH_AXES, None),
                   P(BATCH_AXES)), check_vma=False)(*args)


def _shards_evenly(q, k, mesh) -> bool:
    """Can [B, S, H, D] q and [B, S, Hkv, D] k/v split over ``mesh``'s
    batch and tensor axes without remainder?"""
    rows = mesh.shape[BATCH_AXES[0]] * mesh.shape[BATCH_AXES[1]]
    heads = mesh.shape[AXIS_TENSOR]
    return not (q.shape[0] % rows or q.shape[2] % heads or k.shape[2] % heads)


def _expand_gqa(q, k, v):
    """Broadcast grouped KV heads up to the query head count (xla/ring paths)."""
    h, hkv = q.shape[2], k.shape[2]
    if h == hkv:
        return k, v
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    return (jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2))


def _key_only_mask(mask, sq: int) -> bool:
    """True if ``mask`` is expressible as a key-padding mask [B, Sk].

    [Sk] and [B, Sk] qualify outright; higher ranks ([B, 1, 1, Sk] BERT
    style) qualify when every middle (head/query) dim is 1.
    """
    del sq
    shape = jnp.shape(mask)
    if len(shape) > 4:
        return False
    if len(shape) <= 2:
        return True
    return all(s == 1 for s in shape[1:-1])


#: Below this sequence length "auto" prefers XLA attention. The kernel
#: already requires s % 512 == 0, so the default keeps flash for every
#: kernel-qualifying shape; the July 2026 in-model figures behind that choice
#: (BERT-base b=32 s=512 full train step, flash against xla) are summarized in
#: ROADMAP "Recent" and have not been re-measured on today's code. Override
#: with DLS_FLASH_MIN_SEQ (e.g. 100000 to force the XLA path for A/B timing).
FLASH_MIN_SEQ = 512


def _flash_min_seq() -> int:
    import os

    try:
        return int(os.environ.get("DLS_FLASH_MIN_SEQ", FLASH_MIN_SEQ))
    except ValueError:
        return FLASH_MIN_SEQ


def _pick_impl(q: jax.Array, k: jax.Array, bias, mask, v=None) -> str:
    # Flash kernel requires TPU, block-divisible seq, lane-divisible head_dim,
    # a mask (if any) in key-only padding form — and a sequence long enough
    # that blockwise beats XLA's fused softmax (see FLASH_MIN_SEQ).
    if not on_tpu():
        return "xla"
    b, s, h, d = q.shape
    if bias is not None:
        return "xla"
    if mask is not None and not _key_only_mask(mask, s):
        return "xla"
    if s < _flash_min_seq():
        return "xla"
    # (each head size by itself: latent attention's 192 / 128 qualifies)
    if s % 512 or d % 8 or (v is not None and v.shape[-1] % 8) \
            or h % k.shape[2]:
        return "xla"
    from distributeddeeplearningspark_tpu.ops.ring_attention import resolve_mesh

    mesh = resolve_mesh()
    if mesh is not None and not _shards_evenly(q, k, mesh):
        return "xla"
    return "flash"


def _xla_attention(q, k, v, *, bias, mask, causal, scale) -> jax.Array:
    depth = q.shape[-1]
    scale = scale if scale is not None else depth**-0.5
    # accumulate logits/softmax in f32 regardless of input dtype (bf16-safe)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * jnp.float32(scale)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(cmask, logits, jnp.float32(-1e30))
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def padding_mask(attention_mask: jax.Array) -> jax.Array:
    """[B, S] 1/0 pad mask → [B, 1, 1, S] bool attend-mask (BERT style)."""
    return (attention_mask > 0)[:, None, None, :]
