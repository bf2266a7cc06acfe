"""Ring attention — context parallelism over the mesh ``seq`` axis.

The reference has no sequence parallelism (BERT-512/Llama-4096 fit one GPU;
SURVEY.md §2 marks SP/CP "unknown — unlikely"), but long-context is first-class
in this rebuild, so the ``seq`` mesh axis reserved in :mod:`..parallel.mesh`
gets a real implementation: blockwise ring attention (Liu et al., "Ring
Attention with Blockwise Transformers", arXiv:2310.01889 — PAPERS.md).

Design (TPU-first):

- Sequences are sharded over ``seq``: each chip holds Q/K/V blocks of
  ``S/seq_degree`` positions (BSHD layout, so batch stays on (data, fsdp) and
  heads on ``tensor`` — CP composes with DP/FSDP/TP).
- Inside :func:`jax.shard_map`, K/V blocks rotate around the ring via
  ``lax.ppermute`` (neighbor exchange rides the ICI torus; each hop overlaps
  with the local block's attention compute in XLA's schedule).
- The softmax is accumulated *online* (flash-style running max/denominator in
  f32), so no chip ever materializes the full [S, S] score matrix — memory is
  O(S/seq_degree) per chip and exact (not approximate) attention.
- **Blockwise backward (custom VJP)**: the forward saves only (q, k, v, o,
  lse) — per-hop attention probabilities are recomputed in a second ring
  pass, with the dK/dV accumulators riding the ring alongside their K/V
  blocks so every chip folds in its contribution and the gradients arrive
  back at their home chip after a full revolution. Without this, autodiff
  through the forward scan checkpoints an [B,H,Sq,Sk] probability block per
  hop — O(S²/ring) — exactly the memory wall ring attention exists to avoid
  (VERDICT r1 missing-#6).
- Causal masking is positional: block ``j`` of K/V against local Q block
  ``i`` is fully attended when ``j < i``, diagonal-masked when ``j == i``,
  and contributes zero when ``j > i`` (computed-and-masked; SPMD lockstep
  means skipping would not save wall-clock on the critical path).

Key-padding masks (VERDICT r2 #6): a key-only mask ([B, Sk] or the BERT
[B, 1, 1, Sk] broadcast form) is sharded over ``seq`` like K/V and **rides the
ring with its K/V block** — each hop masks its local logits (einsum path) or
streams the mask block into the flash kernel (which takes key-only masks
natively), so padded-batch models (BERT-style) can use CP. Q-dependent masks
remain unsupported (use ``impl='xla'``); fully-masked rows emit zero output,
matching the flash kernel's convention.
"""

from __future__ import annotations

import functools
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

_NEG_INF = jnp.float32(-1e30)

# Fallback mesh for calls that originate inside a model (which has no mesh
# handle): models call dot_product_attention(impl="ring") → ring_attention
# with mesh=None. Resolution order: explicit arg > active Session >
# set_default_mesh. The mesh is a trace-time constant, so a module global is
# safe under jit (it is read while tracing, not while executing).
_default_mesh: Mesh | None = None


def set_default_mesh(mesh: Mesh | None) -> None:
    global _default_mesh
    _default_mesh = mesh


def resolve_mesh(mesh: Mesh | None = None) -> Mesh | None:
    """The mesh an attention op should lay itself out on: explicit arg >
    active Session > :func:`set_default_mesh` (which the compile path sets
    while it traces a step); ``None`` when none is known."""
    if mesh is not None:
        return mesh
    from distributeddeeplearningspark_tpu.session import Session

    if Session._active is not None and not Session._active._stopped:
        return Session._active.mesh
    return _default_mesh


def _causal_allowed(my_idx, blk, sq, sk):
    """[Sq, Sk] bool: may local q row attend to position in block ``blk``?"""
    q_pos = my_idx * sq + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    k_pos = blk * sk + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return q_pos >= k_pos


def _hop_allowed(my_idx, blk, sq, sk, causal, mask_cur, q_seg=None,
                 kseg_cur=None):
    """Combined attend-permission for one hop, broadcastable over
    [B, Hkv, G, Sq, Sk] logits, or None when nothing is masked.

    ``mask_cur``: this hop's key-padding block [B, Sk] (int, 0 = pad) — the
    mask shard that arrived with the K/V block riding the ring.
    ``q_seg``/``kseg_cur``: packed-sequence segment ids — the LOCAL query
    shard's ids [B, Sq] and this hop's key ids [B, Sk] (riding the ring
    like the mask); attention allowed only where they match.
    """
    allowed = None
    if causal:
        allowed = _causal_allowed(my_idx, blk, sq, sk)        # [Sq, Sk]
    if mask_cur is not None:
        pad_ok = (mask_cur != 0)[:, None, None, None, :]      # [B,1,1,1,Sk]
        allowed = pad_ok if allowed is None else jnp.logical_and(allowed, pad_ok)
    if kseg_cur is not None:
        same = (q_seg[:, None, None, :, None]
                == kseg_cur[:, None, None, None, :])          # [B,1,1,Sq,Sk]
        allowed = same if allowed is None else jnp.logical_and(allowed, same)
    return allowed


def _unpack_extras(extras, has_mask, has_segs):
    """(mask_cur, kseg_cur) out of the riding-extras tuple (fixed order)."""
    mask_cur = extras[0] if has_mask else None
    kseg_cur = extras[int(has_mask)] if has_segs else None
    return mask_cur, kseg_cur


def _ring_fwd_local(q, k, v, mask, segs, *, axis_name, causal, scale):
    """One ring revolution of online softmax; returns (o, lse).

    o: [B, Sq, H, D] in q.dtype; lse: [B, Hkv, G, Sq] f32 (log-sum-exp of
    the scaled logits — the only residual the backward needs beyond
    q/k/v/o). **GQA-native**: K/V may carry Hkv ≤ H heads; Q reshapes to
    [B, Sq, Hkv, G, D] (contiguous head groups, same convention as the
    flash kernel) and every einsum runs grouped — the KV blocks riding the
    ring are never copied up to Q-head width.
    """
    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, d) * jnp.float32(scale)

    # receive from right neighbor: after i hops this chip holds block my+i
    perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
    has_mask, has_segs = mask is not None, segs is not None
    ride0 = tuple(x for x in (mask, segs) if x is not None)

    def accumulate(acc, i, k_cur, v_cur, extras):
        """Online-softmax update of (o, l, m) with K/V block (my_idx+i)."""
        o, l, m = acc
        mask_cur, kseg_cur = _unpack_extras(extras, has_mask, has_segs)
        blk = (my_idx + i) % axis_size
        logits = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qf, k_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )                                                     # [B,Hkv,G,Sq,Sk]
        allowed = _hop_allowed(my_idx, blk, sq, sk, causal, mask_cur,
                               segs, kseg_cur)
        if allowed is not None:
            logits = jnp.where(allowed, logits, _NEG_INF)
            # a fully-masked row's max IS the mask value, so exp(s - m) = 1
            # there — the explicit re-zero below is load-bearing, not belt
            # and braces
        m_new = jnp.maximum(m, logits.max(axis=-1))           # [B,Hkv,G,Sq]
        p = jnp.exp(logits - m_new[..., None])
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cur.astype(jnp.float32))
        o_new = o * corr.transpose(0, 3, 1, 2)[..., None] + pv  # [B,Sq,Hkv,G,D]
        return o_new, l_new, m_new

    def block(carry, i):
        o, l, m, k_cur, v_cur = carry[:5]
        extras = carry[5:]
        acc = accumulate((o, l, m), i, k_cur, v_cur, extras)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        extras_nxt = tuple(lax.ppermute(e, axis_name, perm) for e in extras)
        return (*acc, k_nxt, v_nxt, *extras_nxt), None

    init_acc = (
        jnp.zeros((b, sq, hkv, g, d), jnp.float32),
        jnp.zeros((b, hkv, g, sq), jnp.float32),
        jnp.full((b, hkv, g, sq), _NEG_INF),
    )
    if axis_size > 1:
        # scan the first N-1 blocks (each ends with the neighbor exchange)...
        carry, _ = lax.scan(block, (*init_acc, k, v, *ride0),
                            jnp.arange(axis_size - 1))
        o, l, m, k_last, v_last = carry[:5]
        # ...and fold in the final block WITHOUT the (discarded) last rotation
        o, l, m = accumulate((o, l, m), axis_size - 1, k_last, v_last,
                             carry[5:])
    else:
        o, l, m = accumulate(init_acc, 0, k, v, ride0)
    # causal ⇒ every query attends at least to itself ⇒ l > 0; under a
    # padding mask a row may have NO valid keys anywhere — emit zero output
    # and a finite mask-value LSE (the flash kernel's convention), never NaN
    l_safe = jnp.where(l > 0, l, 1.0)
    out = o / l_safe.transpose(0, 3, 1, 2)[..., None]
    lse = jnp.where(l > 0, m + jnp.log(l_safe), _NEG_INF)
    return out.reshape(b, sq, h, d).astype(q.dtype), lse


def _ring_bwd_local(q, k, v, mask, segs, o, lse, do, *, axis_name, causal,
                    scale):
    """Reverse ring pass: recompute per-block probabilities from the saved
    LSE, accumulate dQ locally and ride (K, V, dK, dV) around the ring so
    each block's gradient returns home after a full revolution.

    Per-hop live memory is one [B,H,Sq,Sk] probability block (recomputed,
    never stored across hops) — O(S/ring) residuals, per the Ring Attention
    paper's blockwise backward.
    """
    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, d) * jnp.float32(scale)
    dof = do.astype(jnp.float32).reshape(b, sq, hkv, g, d)
    of = o.astype(jnp.float32).reshape(b, sq, hkv, g, d)
    # delta_i = Σ_d dO_i · O_i (FlashAttention-2's backward shortcut)
    delta = jnp.einsum("bqhgd,bqhgd->bhgq", dof, of)

    perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
    has_mask, has_segs = mask is not None, segs is not None
    ride0 = tuple(x for x in (mask, segs) if x is not None)

    def hop(carry, i):
        dq, k_cur, v_cur, dk, dv = carry[:5]
        extras = carry[5:]
        mask_cur, kseg_cur = _unpack_extras(extras, has_mask, has_segs)
        blk = (my_idx + i) % axis_size
        kf = k_cur.astype(jnp.float32)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf,
                            preferred_element_type=jnp.float32)
        allowed = _hop_allowed(my_idx, blk, sq, sk, causal, mask_cur,
                               segs, kseg_cur)
        if allowed is not None:
            logits = jnp.where(allowed, logits, _NEG_INF)
        p = jnp.exp(logits - lse[..., None])                 # [B,Hkv,G,Sq,Sk]
        if allowed is not None:
            # fully-masked rows carry the finite sentinel LSE, so exp() gives
            # 1.0 under the mask there — the re-zero is load-bearing
            p = jnp.where(allowed, p, 0.0)
        # dV_blk += Pᵀ dO ; dP = dO Vᵀ ; dS = P ∘ (dP - delta)
        # (einsums sum over G, folding every q head of the group into the
        # shared KV gradient — no repeated-KV copies anywhere)
        dv = dv + jnp.einsum("bhgqk,bqhgd->bkhd", p, dof)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", dof, v_cur.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])
        # qf already carries `scale`, so dK needs no extra factor; dQ does.
        dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kf) * jnp.float32(scale)
        dk = dk + jnp.einsum("bhgqk,bqhgd->bkhd", ds, qf)
        # rotate the whole (K, V, dK, dV) bundle — after axis_size hops each
        # block's accumulated gradient is back on its home chip
        k_cur, v_cur, dk, dv = (
            lax.ppermute(x, axis_name, perm) for x in (k_cur, v_cur, dk, dv)
        )
        extras_nxt = tuple(lax.ppermute(e, axis_name, perm) for e in extras)
        return (dq, k_cur, v_cur, dk, dv, *extras_nxt), None

    init = (
        jnp.zeros((b, sq, hkv, g, d), jnp.float32),
        k, v,
        jnp.zeros((b, sk, hkv, d), jnp.float32),
        jnp.zeros((b, sk, hkv, d), jnp.float32),
        *ride0,
    )
    carry, _ = lax.scan(hop, init, jnp.arange(axis_size))
    dq, _, _, dk, dv = carry[:5]
    return (dq.reshape(b, sq, h, d).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


# ---------------------------------------------------------------------------
# flash-backed hop compute (Pallas kernel per ring hop)
# ---------------------------------------------------------------------------
#
# The einsum path above materializes one [B,Hkv,G,Sq,Sk] logits block per hop
# — O(s_local²) live memory, which becomes the per-chip context ceiling on
# real pods (s_local is still thousands of positions per chip). These
# variants run the blockwise flash kernel (ops/flash_attention) for each
# hop's local compute instead, so per-hop live memory drops to the kernel's
# O(s_local·block) tiles and the MXU sees the same tuned kernel as the
# single-chip path.
#
# Why the composition is clean: in a causal ring, hop 0 is exactly the
# diagonal block (same global offsets for q and k → the kernel's local
# ``causal=True`` mask is the correct global mask), and every hop i ≥ 1
# holds block (my+i) mod N, which is either *entirely* allowed
# (my + i ≥ N, i.e. a lower block) or *entirely* masked — a scalar gate
# applied after a ``causal=False`` kernel call, never a per-position mask.


def _flat_heads(x):
    """[B, S, H, D] → [B·H, S, D] (head-major, the flash kernels' layout)."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unflat_heads(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _hop_active(my_idx, i, axis_size, causal):
    """Does hop i's K/V block contribute at all? (f32 0/1 scalar.)"""
    if not causal:
        return jnp.float32(1.0)
    return (my_idx + i >= axis_size).astype(jnp.float32)


def _ring_fwd_flash(q, k, v, mask, segs, *, axis_name, causal, scale,
                    interpret):
    """Ring revolution with the flash kernel per hop; returns (o, lse).

    lse: [B·H, Sq] f32 — flat-head layout (the backward consumes it as-is).
    Partial outputs are merged online in f32 via the standard normalized
    combine: lse' = logaddexp(lse, lse_i), o' = o·e^{lse−lse'} + o_i·e^{lse_i−lse'}.
    ``mask`` ([B, Sk] key-padding block, or None) rides the ring with K/V and
    streams into the kernel per hop; a hop whose block is fully padded emits
    zero output with a finite mask-value LSE, so the merge needs no extra
    gating.
    """
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qf, kf, vf = _flat_heads(q), _flat_heads(k), _flat_heads(v)
    block = min(fa.DEFAULT_BLOCK, sq)
    run = functools.partial(fa._flash_fwd, scale=scale, group=group,
                            block_q=block, block_k=block, interpret=interpret)

    o0, lse0 = run(qf, kf, vf, mask, causal=causal,  # hop 0 = diagonal
                   q_segs=segs, kv_segs=segs)
    o0 = o0.astype(jnp.float32)

    perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
    has_mask, has_segs = mask is not None, segs is not None
    ride0 = tuple(x for x in (mask, segs) if x is not None)

    def hop(carry, i):
        o, lse, k_cur, v_cur = carry[:4]
        extras = tuple(lax.ppermute(e, axis_name, perm) for e in carry[4:])
        mask_cur, kseg_cur = _unpack_extras(extras, has_mask, has_segs)
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        oi, lsei = run(qf, k_cur, v_cur, mask_cur, causal=False,
                       q_segs=segs, kv_segs=kseg_cur)
        active = _hop_active(my_idx, i, axis_size, causal)
        # inactive hop: SELECT the contribution away (never scale by 0 — an
        # unmasked kernel output can carry inf/NaN for fully-masked future
        # blocks, and inf × 0 = NaN), and send lse_i → -inf so the merge is
        # a no-op (lse stays finite — hop 0 always contributed)
        oi = jnp.where(active > 0, oi.astype(jnp.float32), 0.0)
        lsei = jnp.where(active > 0, lsei, _NEG_INF)
        new_lse = jnp.logaddexp(lse, lsei)
        o = (o * jnp.exp(lse - new_lse)[..., None]
             + oi * jnp.exp(lsei - new_lse)[..., None])
        return (o, new_lse, k_cur, v_cur, *extras), None

    o, lse = o0, lse0
    if axis_size > 1:
        carry, _ = lax.scan(hop, (o0, lse0, kf, vf, *ride0),
                            jnp.arange(1, axis_size))
        o, lse = carry[:2]
    return _unflat_heads(o, b, h).astype(q.dtype), lse


def _ring_bwd_flash(q, k, v, mask, segs, o, lse, do, *, axis_name, causal,
                    scale, interpret):
    """Reverse revolution with the flash backward kernels per hop.

    Mirrors :func:`_ring_bwd_local`'s rotation bookkeeping: hop 0 handles the
    local (diagonal) block with the causal kernels, then (K, V, dK, dV)
    rotate together so each block's accumulated gradient is home after a
    full revolution. Per-hop dK/dV contributions use the FULL output's LSE
    (FlashAttention-2 backward), gated by the same all-or-nothing scalar as
    the forward.
    """
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qf, kf, vf = _flat_heads(q), _flat_heads(k), _flat_heads(v)
    of, dof = _flat_heads(o), _flat_heads(do)
    block = min(fa.DEFAULT_BLOCK, sq)
    run = functools.partial(fa._flash_bwd, scale=scale, group=group,
                            block_q=block, block_k=block, interpret=interpret)

    dq0, dk0, dv0 = run((qf, kf, vf, mask, of, lse, segs, segs), dof,
                        causal=causal)
    if axis_size == 1:
        return (_unflat_heads(dq0.astype(jnp.float32), b, h).astype(q.dtype),
                _unflat_heads(dk0.astype(jnp.float32), b, hkv).astype(k.dtype),
                _unflat_heads(dv0.astype(jnp.float32), b, hkv).astype(v.dtype))

    perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]

    def rotate(*xs):
        return tuple(lax.ppermute(x, axis_name, perm) for x in xs)

    has_mask, has_segs = mask is not None, segs is not None
    ride0 = tuple(x for x in (mask, segs) if x is not None)

    def hop(carry, i):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry[:5]
        extras = rotate(*carry[5:]) if len(carry) > 5 else ()
        mask_cur, kseg_cur = _unpack_extras(extras, has_mask, has_segs)
        k_cur, v_cur, dk_cur, dv_cur = rotate(k_cur, v_cur, dk_cur, dv_cur)
        dqi, dki, dvi = run((qf, k_cur, v_cur, mask_cur, of, lse,
                             segs, kseg_cur), dof, causal=False)
        active = _hop_active(my_idx, i, axis_size, causal)
        # SELECT, never multiply: an inactive (fully-masked future) hop runs
        # the kernel unmasked, where a large future logit makes
        # p = exp(s − lse) overflow to inf — and inf × 0 is NaN. where()
        # discards the poisoned contribution outright.
        gate = lambda x: jnp.where(active > 0, x.astype(jnp.float32), 0.0)
        dq = dq + gate(dqi)
        dk_cur = dk_cur + gate(dki)
        dv_cur = dv_cur + gate(dvi)
        return (dq, k_cur, v_cur, dk_cur, dv_cur, *extras), None

    init = (dq0.astype(jnp.float32), kf, vf,
            dk0.astype(jnp.float32), dv0.astype(jnp.float32), *ride0)
    carry, _ = lax.scan(hop, init, jnp.arange(1, axis_size))
    dq, _, _, dk, dv = carry[:5]
    # one final rotation brings each block's gradient back to its home chip
    dk, dv = rotate(dk, dv)
    return (_unflat_heads(dq, b, h).astype(q.dtype),
            _unflat_heads(dk, b, hkv).astype(k.dtype),
            _unflat_heads(dv, b, hkv).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _ring_attention_local(q, k, v, mask, segs, axis_name, causal, scale, impl):
    """Per-shard ring attention (inside shard_map); blockwise custom VJP.

    ``mask``: this shard's key-padding block [B, Sk] int32, or None.
    ``segs``: this shard's packed-sequence segment ids [B, S] int32, or
    None — the q side reads them locally, the kv side rides the ring.
    Both are regular (non-static) arguments with None cotangents — the
    same pattern the flash kernel's VJP uses.
    ``impl``: ("einsum",) — XLA per-hop compute — or ("flash", interpret) —
    Pallas kernel per hop (static tuple so it can ride nondiff_argnums).
    """
    o, _ = _ring_fwd(q, k, v, mask, segs, axis_name=axis_name, causal=causal,
                     scale=scale, impl=impl)
    return o


def _ring_fwd(q, k, v, mask, segs, *, axis_name, causal, scale, impl):
    if impl[0] == "flash":
        return _ring_fwd_flash(q, k, v, mask, segs, axis_name=axis_name,
                               causal=causal, scale=scale, interpret=impl[1])
    return _ring_fwd_local(q, k, v, mask, segs, axis_name=axis_name,
                           causal=causal, scale=scale)


def _ring_vjp_fwd(q, k, v, mask, segs, axis_name, causal, scale, impl):
    o, lse = _ring_fwd(q, k, v, mask, segs, axis_name=axis_name,
                       causal=causal, scale=scale, impl=impl)
    return o, (q, k, v, mask, segs, o, lse)


def _ring_vjp_bwd(axis_name, causal, scale, impl, res, g):
    q, k, v, mask, segs, o, lse = res
    if impl[0] == "flash":
        dq, dk, dv = _ring_bwd_flash(
            q, k, v, mask, segs, o, lse, g, axis_name=axis_name,
            causal=causal, scale=scale, interpret=impl[1])
    else:
        dq, dk, dv = _ring_bwd_local(
            q, k, v, mask, segs, o, lse, g, axis_name=axis_name,
            causal=causal, scale=scale)
    return dq, dk, dv, None, None


_ring_attention_local.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def _flash_hop_qualifies(s_local: int, d: int, *, on_tpu: bool) -> bool:
    """May the per-hop compute use the Pallas kernel for these local shapes?

    The gate must use the SAME block choice as the runtime paths
    (min(DEFAULT_BLOCK, s_local)) — the kernels have no divisibility check
    of their own, so a gate/kernel divergence would silently drop positions.
    On real TPU the head dim must additionally be sublane-aligned (d % 8;
    the block itself is always either whole or DEFAULT_BLOCK, both legal).
    """
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    if s_local < 1:
        return False
    block = min(fa.DEFAULT_BLOCK, s_local)
    if s_local % block:
        return False
    if on_tpu and d % 8:
        return False
    return True


def ring_attention(
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
    """Exact attention over sequence-sharded BSHD tensors (global view).

    Call from inside a jitted step with GLOBAL (logically unsharded) arrays;
    the shard_map below splits them [batch→(data,fsdp), seq→seq,
    heads→tensor] and runs the ring exchange. With ``seq`` degree 1 this
    degenerates to one local block — same math, no collectives — so models
    can use ``impl="ring"`` unconditionally.

    ``mesh=None`` resolves to the active :class:`~...session.Session`'s mesh.

    ``use_flash``: run each hop's local attention through the Pallas flash
    kernel instead of XLA einsums — per-hop live memory drops from one
    [B,H,Sq,Sk] logits block (the per-chip context ceiling at pod scale) to
    the kernel's O(Sq·block) tiles. ``None`` = auto: on TPU whenever the
    local shapes satisfy the kernel's tiling rules; off-TPU the einsum path
    (tests opt in explicitly and get interpret-mode kernels).

    ``mask``: key-only padding mask ([B, Sk], [Sk], or the broadcastable
    BERT [B, 1, 1, Sk] form — :func:`..flash_attention.as_kv_mask`). It is
    sharded over ``seq`` exactly like K and rides the ring with its K/V
    block, so padded-batch (BERT-style) models can context-parallelize
    (VERDICT r2 #6). Masks that vary over queries/heads are rejected — use
    ``impl='xla'``.

    ``segment_ids``: [B, S] int32 packed-sequence document ids (VERDICT r2
    #4 × CP): sharded over ``seq``; each shard's q side reads its local ids
    while the kv-side ids ride the ring with their K/V block, so packed
    batches train under context parallelism with cross-document attention
    blocked. Composes with ``mask`` and ``causal`` on both hop
    implementations.
    """
    if bias is not None:
        raise NotImplementedError(
            "ring attention does not take additive bias; use impl='xla'")
    mesh = resolve_mesh(mesh)
    if mesh is None:
        raise RuntimeError(
            "ring_attention needs a mesh: pass mesh=, create a Session, "
            "or call ops.ring_attention.set_default_mesh(mesh)")
    if k.shape != v.shape:
        raise ValueError(f"k/v shapes must match: {k.shape} vs {v.shape}")
    b, s, h, d = q.shape
    bk, sk, hkv, dk = k.shape
    if (bk, sk, dk) != (b, s, d):
        raise ValueError(f"q/k shape mismatch: {q.shape} vs {k.shape}")
    if h % hkv:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {hkv}")
    tensor_deg = mesh.shape.get(AXIS_TENSOR, 1)
    if hkv % tensor_deg:
        raise ValueError(
            f"GQA-native ring shards K/V heads over '{AXIS_TENSOR}': kv heads "
            f"({hkv}) must divide by the tensor degree ({tensor_deg}) — "
            f"reduce mesh.tensor or repeat KV heads before calling")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    seq_deg = mesh.shape.get(AXIS_SEQ, 1)
    tpu = on_tpu()
    qualifies = (s % seq_deg == 0
                 and _flash_hop_qualifies(s // seq_deg, d, on_tpu=tpu))
    if use_flash and not qualifies:
        # explicit opt-in must not silently downgrade: the user asked for
        # flash exactly to avoid the einsum path's O(s_local²) logits block
        raise ValueError(
            f"use_flash=True but local shapes don't satisfy the kernel "
            f"tiling rules (s={s} over seq degree {seq_deg} → s_local="
            f"{s // seq_deg if s % seq_deg == 0 else f'{s}/{seq_deg} uneven'}, "
            f"d={d}); pad the sequence or pass use_flash=None/False")
    if use_flash is None:
        use_flash = tpu and qualifies
    impl = ("flash", not tpu) if use_flash else ("einsum",)
    spec = P(BATCH_AXES, AXIS_SEQ, AXIS_TENSOR, None)
    # Optional per-position operands ([B, S], sharded like K's batch/seq
    # dims so each chip's block rides the ring with its K/V block):
    extras: list = []
    has_mask, has_segs = mask is not None, segment_ids is not None
    if has_mask:
        from distributeddeeplearningspark_tpu.ops.flash_attention import as_kv_mask

        extras.append(as_kv_mask(mask, b, s))
    if has_segs:
        segs = jnp.asarray(segment_ids)
        if segs.shape != (b, s):
            raise ValueError(
                f"segment_ids must be [batch, seq] = {(b, s)}, "
                f"got {segs.shape}")
        extras.append(segs.astype(jnp.int32))

    # custom_vjp nondiff args must be passed positionally (not via partial
    # keywords) or jax rejects the call under differentiation
    def local(qq, kk, vv, *ex):
        mm, ss = _unpack_extras(ex, has_mask, has_segs)
        return _ring_attention_local(
            qq, kk, vv, mm, ss, AXIS_SEQ, causal, scale, impl)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec,
                  *([P(BATCH_AXES, AXIS_SEQ)] * len(extras))),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v, *extras)
