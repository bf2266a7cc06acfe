"""Pallas flash attention vs dense XLA attention (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jaxpr import eqns
from distributeddeeplearningspark_tpu.ops.attention import (
    _pick_impl,
    _xla_attention,
    padding_mask,
)
from distributeddeeplearningspark_tpu.ops.flash_attention import flash_attention


def _qkv(b=2, s=128, h=2, d=32, seed=0, dtype=np.float32, hkv=None):
    rng = np.random.default_rng(seed)
    mk = lambda hh: jnp.asarray(rng.normal(0, 1, (b, s, hh, d)).astype(dtype))
    hkv = hkv or h
    return mk(h), mk(hkv), mk(hkv)


def _pad_mask(b, s, valid, seed=0):
    """[B, S] 1/0 attention mask with `valid` real tokens per row."""
    am = np.zeros((b, s), np.int32)
    am[:, :valid] = 1
    return jnp.asarray(am)


def _dense(q, k, v, *, mask=None, causal=False):
    """XLA reference; expands GQA KV heads the reference way (repeat)."""
    h, hkv = q.shape[2], k.shape[2]
    if h != hkv:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    return _xla_attention(q, k, v, bias=None, mask=mask, causal=causal, scale=None)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    want = _dense(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(causal):
    q, k, v = _qkv(b=1, s=64, h=2, d=16, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=32, block_k=32) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)


# -- key-padding masks (the BERT case: VERDICT r1 item 2) --------------------

@pytest.mark.parametrize("mask_shape", ["bs", "b11s"])
def test_flash_padding_mask_matches_dense(mask_shape):
    b, s = 2, 128
    q, k, v = _qkv(b=b, s=s)
    am = _pad_mask(b, s, valid=80)
    mask = am if mask_shape == "bs" else padding_mask(am)
    want = _dense(q, k, v, mask=padding_mask(am))
    got = flash_attention(q, k, v, mask=mask, block_q=64, block_k=64)
    # padded *query* rows still attend (masked in the loss downstream); all
    # rows must agree since the mask is key-only
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_padding_mask_gradients_match_dense():
    b, s = 1, 64
    q, k, v = _qkv(b=b, s=s, h=2, d=16, seed=5)
    am = _pad_mask(b, s, valid=40)
    # weight like a real loss: only valid query rows contribute
    w = jnp.asarray(np.asarray(am), jnp.float32)[:, :, None, None]

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, mask=am, block_q=32, block_k=32)
        return jnp.sum((o * w) ** 2)

    def loss_dense(q, k, v):
        o = _dense(q, k, v, mask=padding_mask(am))
        return jnp.sum((o * w) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)


def test_flash_fully_masked_key_block_no_nan():
    # valid tokens confined to the first of two key blocks: the second block
    # is fully masked for every row and must contribute exactly nothing
    b, s = 1, 64
    q, k, v = _qkv(b=b, s=s, h=1, d=16, seed=9)
    am = _pad_mask(b, s, valid=32)
    got = flash_attention(q, k, v, mask=am, block_q=32, block_k=32)
    assert np.isfinite(np.asarray(got)).all()
    want = _dense(q, k, v, mask=padding_mask(am))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_rejects_query_varying_mask():
    q, k, v = _qkv(s=64)
    with pytest.raises(NotImplementedError, match="key-only"):
        flash_attention(q, k, v, mask=jnp.ones((2, 1, 64, 64), bool))


# -- GQA (grouped KV without jnp.repeat: VERDICT r1 item 2) ------------------

@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_dense(causal):
    q, k, v = _qkv(b=2, s=128, h=4, hkv=2, d=32, seed=11)
    want = _dense(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_gqa_gradients_match_dense():
    q, k, v = _qkv(b=1, s=64, h=4, hkv=2, d=16, seed=13)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=32, block_k=32) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)


def test_flash_gqa_masked_causal_combined():
    b, s = 2, 64
    q, k, v = _qkv(b=b, s=s, h=4, hkv=2, d=16, seed=17)
    am = _pad_mask(b, s, valid=48)
    want = _dense(q, k, v, mask=padding_mask(am), causal=True)
    got = flash_attention(q, k, v, mask=am, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_gqa_bad_head_ratio_rejected():
    q, k, v = _qkv(b=1, s=64, h=4, hkv=3, d=16)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, v)


# -- auto impl selection -----------------------------------------------------

def test_pick_impl_routes_bert_and_gqa_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((2, 512, 12, 64))        # BERT-base: S=512, d=64
    kv = jnp.zeros((2, 512, 12, 64))
    bert_mask = padding_mask(jnp.ones((2, 512), jnp.int32))
    # BERT with its padding mask rides the kernel (in-model measured faster)
    assert _pick_impl(q, kv, None, bert_mask) == "flash"
    # GQA llama: 8 q heads / 2 kv heads, long seq
    q2 = jnp.zeros((1, 8192, 8, 128))
    kv2 = jnp.zeros((1, 8192, 2, 128))
    assert _pick_impl(q2, kv2, None, None) == "flash"
    # q-varying mask → xla
    assert _pick_impl(q, kv, None, jnp.ones((2, 1, 512, 512), bool)) == "xla"
    # bias → xla
    assert _pick_impl(q, kv, jnp.zeros((2, 12, 512, 512)), None) == "xla"
    # threshold override forces the XLA path (A/B timing escape hatch)
    monkeypatch.setenv("DLS_FLASH_MIN_SEQ", "100000")
    assert _pick_impl(q, kv, None, bert_mask) == "xla"
    assert _pick_impl(q2, kv2, None, None) == "xla"


def test_flash_runs_per_shard_on_a_multi_device_mesh(monkeypatch):
    """GSPMD cannot partition a Mosaic kernel (first met on the four-chip
    host), so under a multi-device mesh the router wraps the kernel in a
    shard_map: batch over (data, fsdp), heads over tensor. Same numbers as
    the XLA path, fwd and bwd, with GQA + padding mask + segments + causal;
    shapes that do not split evenly route to XLA under 'auto' and are an
    error under an explicit impl='flash'."""
    from distributeddeeplearningspark_tpu.ops import attention, ring_attention
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec(data=2, fsdp=2, tensor=2).build()
    monkeypatch.setattr(ring_attention, "_default_mesh", mesh)
    q, k, v = _qkv(b=8, s=128, h=4, hkv=2, d=32)
    mask = padding_mask(_pad_mask(8, 128, 100))
    segs = _seg_ids(8, 128, [[0, 40]] * 8)

    def run(impl):
        def loss(q, k, v):
            o = attention.dot_product_attention(
                q, k, v, mask=mask, segment_ids=segs, causal=True, impl=impl)
            return jnp.sum(o ** 2), o

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, o), g = run("flash")(q, k, v)
    (_, o_ref), g_ref = run("xla")(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
    for got, want in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)
    assert o.sharding.spec == jax.sharding.PartitionSpec(
        ("data", "fsdp"), None, "tensor")
    with pytest.raises(ValueError, match="impl='xla'"):
        attention.dot_product_attention(q[:3], k[:3], v[:3], impl="flash")
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    q512 = jnp.zeros((8, 512, 4, 32))
    assert _pick_impl(q512, q512, None, None) == "flash"
    assert _pick_impl(q512[:3], q512[:3], None, None) == "xla"   # rows % 4
    assert _pick_impl(q512, q512[:, :, :1], None, None) == "xla"  # kv heads % 2


def test_flash_uneven_blocks_rejected():
    q, k, v = _qkv(s=96)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, v, block_q=64, block_k=64)


def test_flash_bf16_close_to_f32_reference():
    q, k, v = _qkv(s=64, d=32, seed=7)
    want = _dense(q, k, v, causal=True)
    got = flash_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)),
                          causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=5e-2, rtol=5e-2)


# -- packed-sequence segment ids (VERDICT r2 #4: BERT packing) ---------------

def _seg_ids(b, s, boundaries, seed=0):
    """[B, S] int32 segment ids: `boundaries[i]` = doc-start offsets of row i."""
    out = np.zeros((b, s), np.int32)
    for i, starts in enumerate(boundaries):
        for d, st in enumerate(starts):
            out[i, st:] = d
    return jnp.asarray(out)


def _seg_mask(segs):
    """Dense [B, 1, S, S] attend-mask equivalent of segment-id blocking."""
    return (segs[:, None, :, None] == segs[:, None, None, :])


class TestSegmentIds:
    def test_forward_matches_dense(self):
        q, k, v = _qkv(b=2, s=128, h=2, d=32, seed=7)
        segs = _seg_ids(2, 128, [[0, 40, 90], [0, 64]])
        want = _dense(q, k, v, mask=_seg_mask(segs))
        got = flash_attention(q, k, v, segment_ids=segs, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_dense(self, causal):
        q, k, v = _qkv(b=1, s=64, h=2, d=16, seed=8)
        segs = _seg_ids(1, 64, [[0, 17, 40]])

        def loss_flash(a, b_, c):
            return jnp.sum(flash_attention(
                a, b_, c, causal=causal, segment_ids=segs,
                block_q=32, block_k=32) ** 2)

        def loss_dense(a, b_, c):
            return jnp.sum(_dense(a, b_, c, mask=_seg_mask(segs),
                                  causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4, rtol=2e-4)

    def test_composes_with_padding_mask(self):
        """Packed tail window: padding mask AND segment ids together (pads
        additionally carry segment -1, the pipeline's convention)."""
        b, s = 2, 128
        q, k, v = _qkv(b=b, s=s, seed=9)
        am = _pad_mask(b, s, 100)
        segs = np.array(_seg_ids(b, s, [[0, 30], [0, 77]]))
        segs[:, 100:] = -1
        segs = jnp.asarray(segs)
        want = _dense(q, k, v, mask=_seg_mask(segs) & padding_mask(am))
        got = flash_attention(q, k, v, mask=padding_mask(am), segment_ids=segs,
                              block_q=64, block_k=64)
        w, g = np.asarray(want), np.asarray(got)
        # valid rows agree; pad q rows: flash emits zeros (fully-masked-row
        # convention) — assert finite
        np.testing.assert_allclose(g[:, :100], w[:, :100], atol=2e-5, rtol=2e-5)
        assert np.isfinite(g).all()

    def test_gqa_with_segments(self):
        q, k, v = _qkv(b=2, s=128, h=4, d=32, seed=10, hkv=2)
        segs = _seg_ids(2, 128, [[0, 50], [0]])
        want = _dense(q, k, v, mask=_seg_mask(segs))
        got = flash_attention(q, k, v, segment_ids=segs, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_bad_shape_rejected(self):
        q, k, v = _qkv()
        with pytest.raises(ValueError, match="segment_ids"):
            flash_attention(q, k, v, segment_ids=jnp.zeros((2, 64), jnp.int32))


# -- blocks no document spans are skipped (PR 31) -----------------------------

def _every_block_meets(monkeypatch):
    """Patch the shared bounds so that every block's id range is everything:
    the predicate then walks what the kernels walked before they could skip
    (all blocks, or those on or under the diagonal with ``causal``)."""
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    def whole_range(segs, block):
        shape = (segs.shape[0], segs.shape[1] // block)
        info = jnp.iinfo(jnp.int32)
        return (jnp.full(shape, info.min, jnp.int32),
                jnp.full(shape, info.max, jnp.int32))

    monkeypatch.setattr(fa, "_block_id_range", whole_range)


def _ids_of(lengths, s):
    """Running ids of documents of the given lengths, the last one filling
    the rest of the window."""
    ids = np.repeat(np.arange(len(lengths)), lengths)[:s]
    return np.concatenate([ids, np.full(s - len(ids), len(lengths))])


def _skip_case(name):
    """-> (q, k, v, mask [B, S] | None, q_segs, kv_segs, causal), block 64."""
    if name == "causal_gqa_documents":
        # row 0: a document that ends inside a block (at 100), one that ends
        # on a block edge (at 192), a one-token document (192), the rest;
        # row 1: one document fills the window
        s = 320
        segs = np.stack([_ids_of([100, 92, 1], s), np.zeros(s, np.int64)])
        q, k, v = _qkv(b=2, s=s, h=4, d=64, hkv=1, seed=31)
        return q, k, v, None, segs, segs, True
    if name == "masked_segments_pads":
        # BERT packing: no causal, key mask, pads carry -1
        s = 256
        segs = np.stack([_ids_of([70, 58, 64], s), _ids_of([130], s)])
        segs[0, 230:] = -1
        segs[1, 200:] = -1
        q, k, v = _qkv(b=2, s=s, h=2, d=32, seed=32)
        return q, k, v, (segs >= 0).astype(np.int32), segs, segs, False
    if name == "unordered_ids_two_sides":
        # a ring hop: the keys are another shard's, ids in no order. Blocks
        # of 64: the queries' ranges are [6,7] [0,1] [2,4] [9,9], the keys'
        # [5,5] [1,9] [0,0] [3,7]: 8 of 16 blocks meet, 4 hold an allowed
        # pair, and the third query block is allowed nothing at all
        s = 256
        q_segs = np.repeat(np.array([6, 7, 0, 1, 4, 2, 9, 9]), 32)[None, :]
        kv_segs = np.repeat(np.array([5, 5, 1, 9, 0, 0, 7, 3]), 32)[None, :]
        q, k, v = _qkv(b=1, s=s, h=2, d=32, seed=33)
        return q, k, v, None, q_segs, kv_segs, False
    raise KeyError(name)


SKIP_CASES = ("causal_gqa_documents", "masked_segments_pads",
              "unordered_ids_two_sides")


def _run_kernels(case, block_q=64, block_k=64):
    """Forward and the three gradients through the kernels themselves."""
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    q, k, v, mask, q_segs, kv_segs, causal = case
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, *x.shape[1::2])
    qf, kf, vf = flat(q), flat(k), flat(v)
    int32 = lambda x: None if x is None else jnp.asarray(x, jnp.int32)
    mask, q_segs, kv_segs = int32(mask), int32(q_segs), int32(kv_segs)
    opts = dict(scale=q.shape[-1] ** -0.5, causal=causal,
                group=q.shape[2] // k.shape[2], block_q=block_q,
                block_k=block_k, interpret=True)
    o, lse = fa._flash_fwd(qf, kf, vf, mask, q_segs=q_segs, kv_segs=kv_segs,
                           **opts)
    do = jnp.asarray(np.random.default_rng(5).normal(0, 1, o.shape), o.dtype)
    dq, dk, dv = fa._flash_bwd((qf, kf, vf, mask, o, lse, q_segs, kv_segs),
                               do, **opts)
    return {"o": o, "lse": lse, "dq": dq, "dk": dk, "dv": dv}


@pytest.mark.parametrize("name", SKIP_CASES)
def test_skipping_kernels_give_the_bits_of_the_walking_ones(name, monkeypatch):
    """A block in which nothing is allowed leaves ``m``, ``l``, ``acc`` and
    the three gradient accumulators as they were, so the kernels that skip it
    must give what the kernels that walk and mask it give: EQUAL, not close."""
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    case = _skip_case(name)
    q_segs, kv_segs, causal = (jnp.asarray(case[4], jnp.int32),
                               jnp.asarray(case[5], jnp.int32), case[6])
    plan = lambda: fa.segment_block_walk(q_segs, kv_segs, causal=causal,
                                         block_q=64, block_k=64)[0]
    skipping = _run_kernels(case)
    walked = int(jnp.sum(plan()))
    _every_block_meets(monkeypatch)
    walking = _run_kernels(case)
    n = q_segs.shape[1] // 64
    every = q_segs.shape[0] * (n * (n + 1) // 2 if causal else n * n)
    assert int(jnp.sum(plan())) == every   # the patch walks as the parent did
    assert walked < every                  # and the case has blocks to skip
    for key, want in walking.items():
        np.testing.assert_array_equal(np.asarray(skipping[key]),
                                      np.asarray(want), err_msg=key)
    assert np.isfinite(np.asarray(skipping["o"])).all()


def _blocks_with_an_allowed_pair(q_segs, kv_segs, causal, block):
    """Brute force, from the dense allowed mask."""
    q_segs, kv_segs = np.asarray(q_segs), np.asarray(kv_segs)
    allowed = q_segs[:, :, None] == kv_segs[:, None, :]
    if causal:
        s = q_segs.shape[1]
        allowed &= np.arange(s)[:, None] >= np.arange(s)[None, :]
    b, s, _ = allowed.shape
    return allowed.reshape(b, s // block, block, s // block, block).any((2, 4))


@pytest.mark.parametrize("name", SKIP_CASES)
def test_walked_blocks_against_the_dense_mask(name):
    """The predicate walks exactly the blocks that hold an allowed pair when
    the ids run (documents packed one after another), and a superset of them
    for ids in any order; the counter is its count over the triangle."""
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    _, _, _, _, q_segs, kv_segs, causal = _skip_case(name)
    walk, q_side, k_side = fa.segment_block_walk(
        jnp.asarray(q_segs, jnp.int32), jnp.asarray(kv_segs, jnp.int32),
        causal=causal, block_q=64, block_k=64)
    walk = np.asarray(walk)
    need = _blocks_with_an_allowed_pair(q_segs, kv_segs, causal, 64)
    assert not (need & ~walk).any()
    if name == "causal_gqa_documents":
        np.testing.assert_array_equal(walk, need)
        share = float(fa.attn_blocks_walked_share(
            jnp.asarray(q_segs, jnp.int32), block=64))
        n = q_segs.shape[1] // 64
        assert share == pytest.approx(
            need.sum() / q_segs.shape[0] / (n * (n + 1) / 2))
        assert share == pytest.approx(walk.sum() / 2 / 15)
        # row 0 by hand, documents [0, 100) [100, 192) {192} [193, 320): the
        # five query blocks meet 1, 2, 2, 1 and 2 key blocks
        assert walk[0].sum() == 1 + 2 + 2 + 1 + 2 and walk[1].sum() == 15
    # the hulls the index maps clamp to hold every walked block
    b, nq, nk = walk.shape
    k_first, k_last = np.asarray(q_side[2:]).reshape(2, b, nq)
    q_first, q_last = np.asarray(k_side[2:]).reshape(2, b, nk)
    for r, i, j in zip(*np.nonzero(walk)):
        assert k_first[r, i] <= j <= k_last[r, i]
        assert q_first[r, j] <= i <= q_last[r, j]


def test_a_window_of_one_document_walks_the_whole_triangle():
    from distributeddeeplearningspark_tpu.ops.flash_attention import (
        attn_blocks_walked_share,
    )

    one = jnp.zeros((2, 256), jnp.int32)
    assert float(attn_blocks_walked_share(one, block=64)) == 1.0
    # 64-token documents on the block's grid: the diagonal alone, 4 of 10
    many = jnp.asarray(np.arange(256)[None, :] // 64, jnp.int32)
    assert float(attn_blocks_walked_share(many, block=64)) == pytest.approx(0.4)
    # shorter than a block: one block, walked
    assert float(attn_blocks_walked_share(one[:, :48])) == 1.0


# -- a step does only what its block needs (PR 33) ----------------------------

def _class_case(name):
    """-> (case as :func:`_skip_case` gives it, block_q, block_k)."""
    if name in ("causal", "causal_bq64_bk32", "causal_bq32_bk64"):
        q, k, v = _qkv(b=1, s=256, h=2, d=32, seed=41)
        bq, bk = {"causal": (64, 64), "causal_bq64_bk32": (64, 32),
                  "causal_bq32_bk64": (32, 64)}[name]
        return (q, k, v, None, None, None, True), bq, bk
    if name == "causal_gqa":
        q, k, v = _qkv(b=2, s=256, h=4, d=32, hkv=1, seed=42)
        return (q, k, v, None, None, None, True), 64, 64
    if name == "causal_documents":
        return _skip_case("causal_gqa_documents"), 64, 64
    if name == "documents_bq64_bk32":
        # a document edge at 96 lies on a key block's edge and inside a
        # query block; the second row is one document
        s = 256
        segs = np.stack([_ids_of([96, 70], s), np.zeros(s, np.int64)])
        q, k, v = _qkv(b=2, s=s, h=2, d=32, seed=43)
        return (q, k, v, None, segs, segs, True), 64, 32
    if name == "two_sided_ids":
        # a ring hop, not causal, ids in no order. Blocks of 64: the queries'
        # ranges are [3,3] [0,1] [5,5] [2,2], the keys' [5,5] [3,3] [2,2]
        # [0,4]: three blocks are whole, (0, 1), (2, 0) and (3, 2)
        q_segs = np.repeat(np.array([3, 3, 0, 1, 5, 5, 2, 2]), 32)[None, :]
        kv_segs = np.repeat(np.array([5, 5, 3, 3, 2, 2, 0, 4]), 32)[None, :]
        q, k, v = _qkv(b=1, s=256, h=2, d=32, seed=44)
        return (q, k, v, None, q_segs, kv_segs, False), 64, 64
    if name == "causal_key_mask":
        q, k, v = _qkv(b=2, s=256, h=2, d=32, seed=45)
        mask = np.ones((2, 256), np.int32)
        mask[0, 200:] = 0
        return (q, k, v, mask, None, None, True), 64, 64
    if name == "causal_192_128":
        q, k, v = _qkv_two_sizes(1, 256, 2, 192, 128, seed=46)
        return (q, k, v, None, None, None, True), 64, 64
    raise KeyError(name)


CLASS_CASES = ("causal", "causal_gqa", "causal_documents", "two_sided_ids",
               "causal_key_mask", "causal_192_128", "causal_bq64_bk32",
               "causal_bq32_bk64", "documents_bq64_bk32")


def _allowed(q_segs, kv_segs, causal, b, s):
    """The dense allowed mask [B, S, S] of a regime without a key mask."""
    allowed = np.ones((b, s, s), bool)
    if q_segs is not None:
        allowed &= (np.asarray(q_segs)[:, :, None]
                    == np.asarray(kv_segs)[:, None, :])
    if causal:
        allowed &= np.arange(s)[:, None] >= np.arange(s)[None, :]
    return allowed


def _blocks(allowed, block_q, block_k, every):
    b, s, _ = allowed.shape
    tiles = allowed.reshape(b, s // block_q, block_q, s // block_k, block_k)
    return tiles.all((2, 4)) if every else tiles.any((2, 4))


def _classes(q_segs, kv_segs, causal, b, s, block_q, block_k):
    """(walked, whole of them) [B, S/block_q, S/block_k] by the kernels'
    predicates: on the tables under segment ids, without ids on the block
    coordinates alone (the plain causal regime hands the kernels no table)."""
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    if q_segs is not None:
        return [np.asarray(x) for x in fa.segment_block_classes(
            jnp.asarray(q_segs, jnp.int32), jnp.asarray(kv_segs, jnp.int32),
            causal=causal, block_q=block_q, block_k=block_k)]
    qb = np.arange(s // block_q)[None, :, None]
    kb = np.arange(s // block_k)[None, None, :]
    walk = np.broadcast_to(kb * block_k < (qb + 1) * block_q,
                           (b, s // block_q, s // block_k))
    whole = fa._block_whole(None, None, None, None, qb, kb, causal=causal,
                            block_q=block_q, block_k=block_k)
    return walk, walk & np.asarray(whole)


@pytest.mark.parametrize("name", CLASS_CASES)
def test_classed_kernels_give_the_bits_of_the_kernels_that_mask_every_block(
        name, monkeypatch):
    """In a *whole* block ``allowed`` is all true, so the select on the scores
    and the select on ``p`` are identities: the kernels that leave both out
    there must give what the kernels that mask every walked block give, in
    output, log-sum-exp and the three gradients: EQUAL, not close. The
    predicate names a block *whole* exactly when the dense mask allows every
    pair in it (a regime with a key mask has no such class)."""
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    case, bq, bk = _class_case(name)
    q, mask, q_segs, kv_segs, causal = case[0], *case[3:]
    classed = _run_kernels(case, bq, bk)
    if mask is None:
        b, s = q.shape[:2]
        walk, whole = _classes(q_segs, kv_segs, causal, b, s, bq, bk)
        allowed = _allowed(q_segs, kv_segs, causal, b, s)
        np.testing.assert_array_equal(whole,
                                      _blocks(allowed, bq, bk, every=True))
        assert not (_blocks(allowed, bq, bk, every=False) & ~walk).any()
        # the case has blocks of both bodies
        assert whole.any() and (walk & ~whole).any()
    monkeypatch.setattr(fa, "_block_whole", lambda *a, **kw: False)
    masking = _run_kernels(case, bq, bk)
    for key, want in masking.items():
        np.testing.assert_array_equal(np.asarray(classed[key]),
                                      np.asarray(want), err_msg=key)
    assert np.isfinite(np.asarray(classed["o"])).all()


def _named_blocks(eqn, operand):
    """Per grid step in the order the grid runs (last index fastest), the
    block indices the index map of ``operand`` names: [steps, ndim]."""
    mapping = eqn.params["grid_mapping"]
    index_map = mapping.block_mappings[operand].index_map_jaxpr
    at = np.stack(np.meshgrid(*[np.arange(n) for n in mapping.grid],
                              indexing="ij"), -1).reshape(-1, len(mapping.grid))
    named = jax.vmap(lambda *ix: jax.core.eval_jaxpr(
        index_map.jaxpr, index_map.consts, *ix))(*jnp.asarray(at, jnp.int32).T)
    return at, np.stack([np.broadcast_to(np.asarray(x), at.shape[:1])
                         for x in named], -1)


@pytest.mark.parametrize("bq, bk", [(64, 64), (64, 32), (32, 64)])
def test_plain_causal_index_maps_fetch_only_what_a_walked_step_reads(bq, bk):
    """Without segment ids, too, a step above the diagonal names the block
    already resident (Pallas copies a block only when its index changes). In
    the forward and dQ grids the streamed block changes only ON a walked
    step; in dK/dV (a group of 4: the sweep of every query head starts above
    the diagonal) a skipped step names the block of its head's FIRST walked
    step, which that step reads. In all three the blocks named along the grid,
    runs of one block taken once, are exactly those of the walked steps: no
    copy is made that no step reads, and none is left out."""
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    s, group = 256, 4
    q = jnp.zeros((group, s, 32))
    k, v, do = jnp.zeros((1, s, 32)), jnp.zeros((1, s, 16)), jnp.zeros(
        (group, s, 16))
    opts = dict(scale=1.0, causal=True, group=group, block_q=bq, block_k=bk,
                interpret=True)

    def both(q, k, v):
        o, lse = fa._flash_fwd(q, k, v, None, **opts)
        return fa._flash_bwd((q, k, v, None, o, lse, None, None), do, **opts)

    calls = {e.params["name"]: e for e in jax.make_jaxpr(both)(q, k, v).eqns
             if e.primitive.name == "pallas_call"}
    runs = lambda rows: [r for i, r in enumerate(rows)
                         if i == 0 or r != rows[i - 1]]
    # (kernel, the streamed operands, whether a change needs a walked step)
    for name, streamed, strict in (("flash_fwd", (1, 2), True),
                                   ("flash_bwd_dq", (1, 2), True),
                                   ("flash_bwd_dkv", (0, 3, 4, 5), False)):
        for operand in streamed:
            at, named = _named_blocks(calls[name], operand)
            if name == "flash_bwd_dkv":
                qb, kb = at[:, 2] % (s // bq), at[:, 1]
            else:
                qb, kb = at[:, 1], at[:, 2]
            walked = kb * bk < (qb + 1) * bq
            assert 0 < walked.sum() < len(walked)
            rows = [tuple(r) for r in named]
            changed = np.array([False] + [a != b for a, b in
                                          zip(rows[1:], rows[:-1])])
            if strict:
                assert not (changed & ~walked).any(), (name, operand)
            assert runs(rows) == runs([r for r, w in zip(rows, walked) if w])
            # a walked step names its own block: the step's, of its head
            own = kb if name != "flash_bwd_dkv" else qb
            assert (named[walked, 1] == own[walked]).all()


@pytest.mark.parametrize("name", ["one_document", "documents",
                                  "documents_on_block_edges", "two_rows"])
def test_masked_share_against_the_dense_mask(name):
    """``attn_blocks_masked_share``: a walked block is *whole* iff the dense
    causal in-document mask allows EVERY pair in it, *edge* otherwise; the
    counter is the edge blocks over the walked, over the batch."""
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    s, block = 512, 64
    segs = {
        "one_document": np.zeros((1, s), np.int64),
        "documents": _ids_of([100, 92, 1, 200], s)[None, :],
        "documents_on_block_edges": (np.arange(s) // 128)[None, :],
        "two_rows": np.stack([_ids_of([130, 250], s), np.zeros(s, np.int64)]),
    }[name]
    allowed = _allowed(segs, segs, True, segs.shape[0], s)
    walked = _blocks(allowed, block, block, every=False)
    whole = _blocks(allowed, block, block, every=True)
    want = (walked & ~whole).sum() / walked.sum()
    got = float(fa.attn_blocks_masked_share(jnp.asarray(segs, jnp.int32),
                                            block=block))
    assert got == pytest.approx(want)
    n = s // block
    if name == "one_document":
        assert got == pytest.approx(2 / (n + 1))      # the diagonal alone
    if name == "documents_on_block_edges":
        # four documents of two blocks: 2 of 3 walked blocks on the diagonal
        assert got == pytest.approx(2 / 3)
    # a length the kernels do not take goes to the XLA path: all masked
    assert float(fa.attn_blocks_masked_share(
        jnp.asarray(segs[:, :500], jnp.int32), block=block)) == 1.0


def test_the_three_kernels_carry_stable_names():
    """A trace tells a kernel by its name: the gradient of a flash-routed
    attention (``impl="flash"``, key-padding mask, as BERT calls it) holds
    three ``pallas_call`` equations called flash_fwd, flash_bwd_dq and
    flash_bwd_dkv (unnamed, all three take the enclosing function's name)."""
    from distributeddeeplearningspark_tpu.ops.attention import (
        dot_product_attention,
    )

    q, k, v = _qkv(b=1, s=64, h=2, d=16)
    mask = padding_mask(_pad_mask(1, 64, 48))

    def loss(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, mask=mask, impl="flash") ** 2)

    names = [eqn.params["name"] for eqn in eqns(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr, "pallas_call")]
    assert sorted(names) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


# -- two head sizes: q, k wider than v (latent attention's 192 / 128) ---------

def _qkv_two_sizes(b, s, h, d_qk, d_v, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda d: jnp.asarray(rng.normal(0, 1, (b, s, h, d)), jnp.float32)
    return mk(d_qk), mk(d_qk), mk(d_v)


@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "segments"])
@pytest.mark.parametrize("d_qk, d_v", [(192, 128), (24, 16)])
def test_flash_takes_a_value_head_narrower_than_the_query_and_key(
        d_qk, d_v, segmented):
    """``q, k [.., d_qk]`` and ``v, o, do [.., d_v]`` go to the three kernels
    as they are (interpreted): output, log-sum-exp and the three gradients
    against ``_xla_attention``, causal, with and without segment ids; nothing
    is padded to the wider size, and the default scale is ``d_qk ** -0.5``."""
    from distributeddeeplearningspark_tpu.ops import flash_attention as fa

    b, s, h = 2, 128, 2
    q, k, v = _qkv_two_sizes(b, s, h, d_qk, d_v, seed=d_qk)
    segs = _seg_ids(b, s, [[0, 50], [0, 30, 100]]) if segmented else None
    seg_mask = (None if segs is None else
                segs[:, None, :, None] == segs[:, None, None, :])

    def dense(q, k, v):
        return _xla_attention(q, k, v, bias=None, mask=seg_mask, causal=True,
                              scale=None)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, segment_ids=segs,
                               block_q=64, block_k=64)

    got, want = flash(q, k, v), dense(q, k, v)
    assert got.shape == (b, s, h, d_v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)
    # the scale the kernel took is the query's: 1 / sqrt(d_qk), not d_v's
    other = flash_attention(q, k, v, causal=True, segment_ids=segs,
                            scale=d_v ** -0.5, block_q=64, block_k=64)
    assert float(jnp.abs(other - want).max()) > 1e-3
    w = jnp.asarray(np.random.default_rng(1).normal(size=got.shape),
                    jnp.float32)
    gf = jax.jit(jax.grad(lambda *a: jnp.sum(flash(*a) * w),
                          argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(lambda *a: jnp.sum(dense(*a) * w),
                          argnums=(0, 1, 2)))(q, k, v)
    for a, b_, like in zip(gf, gd, (q, k, v)):
        assert a.shape == like.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=3e-4, rtol=3e-4)
    # the row statistic the backward kernels read: log-sum-exp of the scores
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])
    _, lse = fa._flash_fwd(
        flat(q), flat(k), flat(v), None, scale=d_qk ** -0.5, causal=True,
        group=1, block_q=64, block_k=64, interpret=True, q_segs=segs,
        kv_segs=segs)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d_qk ** -0.5
    ok = jnp.tril(jnp.ones((s, s), bool))[None, None]
    if seg_mask is not None:
        ok = ok & seg_mask
    want_lse = jax.nn.logsumexp(jnp.where(ok, logits, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(lse).reshape(b, h, s),
                               np.asarray(want_lse), atol=3e-5, rtol=3e-5)


def test_two_head_sizes_route_to_the_kernel_on_a_tpu(monkeypatch):
    """``_pick_impl`` reads each head size by itself: 192 / 128 qualifies (192
    is no multiple of the 128 lanes, but a multiple of 8), and a mesh splits
    the call as any other."""
    from distributeddeeplearningspark_tpu.ops import attention, ring_attention
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    q = jnp.zeros((1, 1024, 32, 192))
    v = jnp.zeros((1, 1024, 32, 128))
    assert _pick_impl(q, q, None, None, v) == "flash"
    assert _pick_impl(q, q, None, None, v[..., :100]) == "xla"   # 100 % 8
    assert _pick_impl(q[..., :100], q[..., :100], None, None, v) == "xla"
    monkeypatch.setattr(attention, "on_tpu", lambda: False)
    mesh = MeshSpec(data=2, tensor=2).build(jax.devices()[:4])
    monkeypatch.setattr(ring_attention, "_default_mesh", mesh)
    q, k, v = _qkv_two_sizes(4, 128, 4, 24, 16, seed=2)

    def run(impl):
        def loss(q, k, v):
            o = attention.dot_product_attention(q, k, v, causal=True,
                                                impl=impl)
            return jnp.sum(o ** 2), o
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, o), g = run("flash")(q, k, v)
    (_, o_ref), g_ref = run("xla")(q, k, v)
    assert o.shape == (4, 128, 4, 16)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
    for got, want in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)


def test_flash_rejects_keys_and_values_that_differ_but_for_the_head_size():
    q, k, v = _qkv_two_sizes(1, 64, 2, 24, 16)
    with pytest.raises(ValueError, match="k/v shapes"):
        flash_attention(q, k, v[:, :, :1])
    with pytest.raises(ValueError, match="q/k shape"):
        flash_attention(q, k[..., :16], v)
