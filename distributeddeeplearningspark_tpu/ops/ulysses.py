"""Ulysses attention — all-to-all context parallelism over the ``seq`` axis.

The second of the two long-context strategies this framework ships (the
reference has neither — SURVEY.md §2 marks SP/CP "unknown — unlikely"; the
rebuild treats long context as first-class). Where :mod:`.ring_attention`
keeps queries home and rotates K/V blocks around the ring (n−1 ``ppermute``
hops), the Ulysses layout (DeepSpeed-Ulysses, arXiv:2309.14509 — PAPERS.md)
swaps the SHARDING instead: one ``all_to_all`` converts sequence-sharded
[B, S/n, H, D] into head-sharded [B, S, H/n, D], each chip runs ordinary
attention over the FULL sequence for its subset of heads, and a second
``all_to_all`` swaps back.

When to prefer which (both are exact attention; pick by geometry):

- **Ulysses**: 2 collectives per call (+2 reversed in backward) regardless
  of the CP degree, and the local attention sees the whole sequence — the
  Pallas flash kernel runs at its native tiling with no per-hop overhead.
  Constraint: heads must divide by the CP degree (32-head Llama caps the
  ``seq`` axis at 32; GQA KV heads additionally at their own count unless
  they are expanded), and each chip holds O(S) activations for its head
  slice — the sequence itself is not memory-sharded during attention.
- **Ring**: O(S/n) memory per chip always (the point of blockwise
  accumulation), no head-divisibility constraint, n−1 neighbor hops that
  overlap with compute on the ICI torus. Wins at extreme context lengths
  where even one full-sequence head-slice is too large.

TPU-first notes: the all_to_all pair rides the ICI all-to-all fabric (a
v4/v5 pod's native strength); per-position extras (key-padding masks,
packed-document segment ids) are small int/bool [B, S/n] shards and travel
by ``all_gather`` since the local attention needs them at full length.

Same global-view contract as :func:`.ring_attention.ring_attention`: call
from inside jit with logically-unsharded arrays; ``shard_map`` splits
[batch→(data, fsdp), seq→seq, heads→tensor] and degree-1 meshes degenerate
to plain local attention.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distributeddeeplearningspark_tpu.parallel.mesh import (
    AXIS_SEQ,
    AXIS_TENSOR,
    BATCH_AXES,
)
from distributeddeeplearningspark_tpu.utils.env import on_tpu


def _local_attention(q, k, v, kv_mask, segs, *, causal, scale, use_flash,
                     interpret):
    """Full-sequence attention on the local head slice (post all-to-all)."""
    if use_flash:
        from distributeddeeplearningspark_tpu.ops.flash_attention import (
            flash_attention)

        return flash_attention(q, k, v, mask=kv_mask, causal=causal,
                               scale=scale, segment_ids=segs,
                               interpret=interpret)
    # einsum fallback (CPU tests / shapes outside the kernel's tiling rules)
    b, s, h, d = q.shape
    hkv = k.shape[2]
    if h != hkv:                                  # GQA → full heads
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    allowed = jnp.ones((b, 1, s, s), bool)
    if causal:
        allowed = allowed & (lax.broadcasted_iota(jnp.int32, (s, s), 0)
                             >= lax.broadcasted_iota(jnp.int32, (s, s), 1))
    if kv_mask is not None:
        allowed = allowed & kv_mask[:, None, None, :]
    if segs is not None:
        allowed = allowed & (segs[:, None, :, None] == segs[:, None, None, :])
    logits = jnp.where(allowed, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked query rows (padding under a kv mask) emit zeros — the
    # flash kernel's convention, so the two paths agree exactly
    any_allowed = jnp.any(allowed, axis=-1, keepdims=True)
    probs = jnp.where(any_allowed, probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh | None = None,
    causal: bool = True,
    scale: float | None = None,
    mask: Any = None,
    bias: Any = None,
    segment_ids: jax.Array | None = None,
    use_flash: bool | None = None,
) -> jax.Array:
    """Exact attention over sequence-sharded BSHD tensors via all-to-all.

    Arguments mirror :func:`.ring_attention.ring_attention` (global view,
    key-only ``mask``, packed ``segment_ids``, ``mesh=None`` → active
    Session / ring's default-mesh fallback). Differences:

    - local (post-TP) q heads AND kv heads must divide by the ``seq``
      degree — the head scatter is the mechanism; a clear error names the
      ring as the fallback when they don't;
    - ``use_flash`` gates on the FULL sequence length (the local attention
      sees all of S), so flash qualifies in exactly the shapes the
      single-chip path would accept.
    """
    if bias is not None:
        raise NotImplementedError(
            "ulysses attention does not take additive bias; use impl='xla'")
    from distributeddeeplearningspark_tpu.ops.ring_attention import (
        _flash_hop_qualifies,
        resolve_mesh,
    )

    mesh = resolve_mesh(mesh)  # the ring's order: explicit > Session > default
    if mesh is None:
        raise RuntimeError(
            "ulysses_attention needs a mesh: pass mesh=, create a "
            "Session, or call ops.ring_attention.set_default_mesh(mesh)")
    if k.shape != v.shape:
        raise ValueError(f"k/v shapes must match: {k.shape} vs {v.shape}")
    b, s, h, d = q.shape
    bk, sk, hkv, dk = k.shape
    if (bk, sk, dk) != (b, s, d):
        raise ValueError(f"q/k shape mismatch: {q.shape} vs {k.shape}")
    if h % hkv:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {hkv}")
    seq_deg = mesh.shape.get(AXIS_SEQ, 1)
    tensor_deg = mesh.shape.get(AXIS_TENSOR, 1)
    if h % tensor_deg or hkv % tensor_deg:
        raise ValueError(
            f"heads ({h} q / {hkv} kv) must divide by the tensor degree "
            f"({tensor_deg})")
    h_loc, hkv_loc = h // tensor_deg, hkv // tensor_deg
    if h_loc % seq_deg or hkv_loc % seq_deg:
        raise ValueError(
            f"ulysses scatters heads over '{AXIS_SEQ}': local q/kv heads "
            f"({h_loc}/{hkv_loc} after tensor={tensor_deg}) must divide by "
            f"the seq degree ({seq_deg}) — lower mesh.seq or use "
            f"impl='ring' (no head constraint)")
    if s % seq_deg:
        raise ValueError(f"seq len {s} must divide by seq degree {seq_deg}")
    scale = scale if scale is not None else d ** -0.5

    tpu = on_tpu()
    qualifies = _flash_hop_qualifies(s, d, on_tpu=tpu)
    if use_flash and not qualifies:
        raise ValueError(
            f"use_flash=True but the full-sequence local shapes don't "
            f"satisfy the kernel tiling rules (s={s}, d={d}); pad the "
            f"sequence or pass use_flash=None/False")
    if use_flash is None:
        use_flash = tpu and qualifies
    interpret = not tpu

    has_mask, has_segs = mask is not None, segment_ids is not None
    extras: list = []
    if has_mask:
        from distributeddeeplearningspark_tpu.ops.flash_attention import (
            as_kv_mask)

        extras.append(as_kv_mask(mask, b, s))
    if has_segs:
        segs = jnp.asarray(segment_ids)
        if segs.shape != (b, s):
            raise ValueError(
                f"segment_ids must be [batch, seq] = {(b, s)}, "
                f"got {segs.shape}")
        extras.append(segs.astype(jnp.int32))

    def local(qq, kk, vv, *ex):
        # [B, S/n, H', D] → (scatter heads, gather seq) → [B, S, H'/n, D]
        a2a = lambda x: lax.all_to_all(                     # noqa: E731
            x, AXIS_SEQ, split_axis=2, concat_axis=1, tiled=True)
        qq, kk, vv = a2a(qq), a2a(kk), a2a(vv)
        ex = [lax.all_gather(e, AXIS_SEQ, axis=1, tiled=True) for e in ex]
        mm = ex[0] if has_mask else None
        ss = ex[-1] if has_segs else None
        out = _local_attention(qq, kk, vv, mm, ss, causal=causal,
                               scale=scale, use_flash=use_flash,
                               interpret=interpret)
        # [B, S, H'/n, D] → (scatter seq, gather heads) → [B, S/n, H', D]
        return lax.all_to_all(out, AXIS_SEQ, split_axis=1, concat_axis=2,
                              tiled=True)

    spec = P(BATCH_AXES, AXIS_SEQ, AXIS_TENSOR, None)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec,
                  *([P(BATCH_AXES, AXIS_SEQ)] * len(extras))),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v, *extras)
