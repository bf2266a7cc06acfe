"""``ops/ssd.py``: the chunked state-space scan against the recurrence
position by position, values and the gradient of every input; a packed row
against its documents run one by one; the padding of a length that fills no
whole chunk; bf16 inputs. All on the CPU in float32 unless said."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jaxpr import eqns
from distributeddeeplearningspark_tpu.ops import ssd

NAMES = ("x", "dt", "A", "B", "C", "D")


def _scan(*args, **kw):
    """``y`` of ``ssd_scan`` (its second result is the state's peak)."""
    return ssd.ssd_scan(*args, **kw)[0]


def _inputs(seed, b, s, h, p, g, n, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, bm, cm = (arr(b, s, h, p).astype(dtype), arr(b, s, g, n).astype(dtype),
                 arr(b, s, g, n).astype(dtype))
    # step sizes between 0.01 and 1, A between -16 and -1: a state that
    # lasts a few positions in some heads and hundreds in others
    dt = jnp.exp(jnp.asarray(rng.uniform(np.log(0.01), 0.0, (b, s, h)),
                             jnp.float32))
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    return x, dt, a, bm, cm, arr(h)


def _segments(rng, b, s, docs):
    return jnp.asarray(np.sort(rng.integers(0, docs, (b, s)), axis=1),
                       jnp.int32)


@pytest.mark.parametrize("chunk", [8, 32])
@pytest.mark.parametrize("packed", [False, True], ids=["one_doc", "packed"])
def test_chunked_scan_is_the_recurrence_values_and_every_gradient(chunk,
                                                                  packed):
    """Six heads in three groups: head ``h`` reads group ``h // 2``, which
    ``h % 3`` is not. 80 positions are ten chunks of 8 (two groups of chunks:
    the state crosses the outer scan's step) or three of 32 with padding."""
    args = _inputs(0, 2, 80, 6, 4, 3, 8)
    seg = _segments(np.random.default_rng(1), 2, 80, 5) if packed else None
    got = _scan(*args, seg, chunk=chunk)
    want = ssd.ssd_scan_sequential(*args, seg)
    assert got.shape == want.shape == (2, 80, 6, 4) and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the other mapping of heads to groups is another function
    other = ssd.ssd_scan_sequential(
        args[0], args[1], args[2], *(t[:, :, jnp.arange(6) % 3].reshape(
            2, 80, 3, 2, 8)[:, :, :, 0] for t in args[3:5]), args[5], seg)
    assert float(jnp.max(jnp.abs(other - want))) > 0.1

    weights = jnp.asarray(np.random.default_rng(2).normal(size=got.shape),
                          jnp.float32)
    scalar = lambda fn: lambda *a: jnp.sum(weights * fn(*a))
    grads = jax.grad(scalar(lambda *a: _scan(*a, seg, chunk=chunk)),
                     argnums=tuple(range(6)))(*args)
    wants = jax.grad(scalar(lambda *a: ssd.ssd_scan_sequential(*a, seg)),
                     argnums=tuple(range(6)))(*args)
    for name, a, b in zip(NAMES, grads, wants):
        assert a.shape == b.shape, name
        assert float(jnp.linalg.norm(b)) > 0, name
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 2e-5, name


@pytest.mark.parametrize("cuts", [
    (21,),            # a boundary inside a chunk
    (32,),            # on a chunk's edge
    (35, 41),         # two in one chunk
    (16, 17, 64, 95),  # edges, a one-position document, the last position
], ids=["inside", "edge", "two_in_one", "many"])
def test_a_packed_row_equals_its_documents_run_one_by_one(cuts):
    """Values and gradients: a document's first position takes nothing from
    before it, in the mask and in the passing of states."""
    s, chunk = 96, 16
    x, dt, a, bm, cm, d = _inputs(3, 1, s, 4, 8, 2, 16)
    seg = jnp.asarray(np.searchsorted(np.asarray(cuts), np.arange(s),
                                      side="right"), jnp.int32)[None]
    weights = jnp.asarray(np.random.default_rng(4).normal(
        size=(1, s, 4, 8)), jnp.float32)

    def packed(x, dt, bm, cm):
        return jnp.sum(weights * _scan(x, dt, a, bm, cm, d, seg,
                                              chunk=chunk))

    def one_by_one(x, dt, bm, cm):
        total, bounds = 0.0, (0, *cuts, s)
        for lo, hi in zip(bounds, bounds[1:]):
            y = _scan(x[:, lo:hi], dt[:, lo:hi], a, bm[:, lo:hi],
                             cm[:, lo:hi], d, None, chunk=chunk)
            total = total + jnp.sum(weights[:, lo:hi] * y)
        return total

    got, grads = jax.value_and_grad(packed, argnums=(0, 1, 2, 3))(
        x, dt, bm, cm)
    want, wants = jax.value_and_grad(one_by_one, argnums=(0, 1, 2, 3))(
        x, dt, bm, cm)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(grads, wants):
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 2e-5
    # and it is NOT what the row gives as one document
    whole = jnp.sum(weights * _scan(x, dt, a, bm, cm, d, None,
                                           chunk=chunk))
    assert abs(float(whole) - float(want)) > 1e-3


def test_a_length_that_fills_no_whole_chunk_is_padded_and_cut():
    args = _inputs(5, 2, 50, 4, 4, 2, 8)
    seg = _segments(np.random.default_rng(6), 2, 50, 3)
    want = ssd.ssd_scan_sequential(*args, seg)
    for chunk in (16, 64, 7):      # 4 chunks of 16, one of 64, 8 of 7
        got = _scan(*args, seg, chunk=chunk)
        assert got.shape == (2, 50, 4, 4)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    grad = jax.grad(lambda x: jnp.sum(_scan(
        x, *args[1:], seg, chunk=16) ** 2))(args[0])
    assert grad.shape == args[0].shape and bool(jnp.all(jnp.isfinite(grad)))
    with pytest.raises(ValueError, match="ssd_scan"):
        _scan(args[0], args[1][:, :, :3], *args[2:], seg)
    with pytest.raises(ValueError, match="G dividing H"):
        _scan(*args[:3], args[3][:, :, :1].repeat(3, 2),
                     args[4][:, :, :1].repeat(3, 2), args[5])


def test_masked_decays_neither_overflow_nor_poison_the_gradient():
    """``exp(cs_i - cs_j)`` ABOVE the diagonal would be ``exp`` of a large
    positive number: it is masked before the exponential, so neither the
    values nor the gradients see an infinity."""
    x, dt, a, bm, cm, d = _inputs(7, 1, 64, 2, 4, 1, 8)
    dt, a = dt * 0 + 4.0, a * 0 - 16.0            # 64 a position: e^{-64}
    seg = _segments(np.random.default_rng(8), 1, 64, 3)
    value, grads = jax.value_and_grad(
        lambda *t: jnp.sum(_scan(*t, seg, chunk=32) ** 2),
        argnums=tuple(range(6)))(x, dt, a, bm, cm, d)
    assert np.isfinite(float(value))
    for name, g in zip(NAMES, grads):
        assert bool(jnp.all(jnp.isfinite(g))), name
    np.testing.assert_allclose(
        _scan(x, dt, a, bm, cm, d, seg, chunk=32),
        ssd.ssd_scan_sequential(x, dt, a, bm, cm, d, seg), rtol=1e-5,
        atol=1e-5)


def test_bf16_inputs_stay_inside_a_stated_tolerance():
    """bf16 activations: products in bf16 with float32 accumulation, decays,
    running sums and states in float32; the output is bf16. Against the
    float32 recurrence on the SAME (bf16-rounded) inputs: 2% of the output's
    largest magnitude, which is a few bf16 roundings of a sum of a few
    dozen terms."""
    args = _inputs(9, 2, 128, 4, 16, 2, 32, dtype=jnp.bfloat16)
    seg = _segments(np.random.default_rng(10), 2, 128, 4)
    got = _scan(*args, seg, chunk=32)
    assert got.dtype == jnp.bfloat16
    want = ssd.ssd_scan_sequential(
        *(t.astype(jnp.float32) for t in args), seg)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err < 0.02 * float(jnp.max(jnp.abs(want))), err
    dx = jax.grad(lambda x: jnp.sum(_scan(
        x, *args[1:], seg, chunk=32).astype(jnp.float32) ** 2))(args[0])
    assert dx.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(
        dx.astype(jnp.float32))))


def test_the_two_counters():
    """``chunks_reset_share``: of the chunks, those that hold a document's
    first position other than the row's own. ``ssd_scan``'s second result: the
    largest magnitude of a state handed between chunks, which a reset
    zeroes."""
    seg = jnp.asarray([[0] * 10 + [1] * 20 + [2] * 2,       # chunks 1 and 3
                       [5] * 32], jnp.int32)                # none
    assert float(ssd.chunks_reset_share(seg, 8)) == pytest.approx(2 / 8)
    assert float(ssd.chunks_reset_share(seg, 16)) == pytest.approx(2 / 4)
    # (30 positions: the last chunk is padded, and position 30 is gone)
    assert float(ssd.chunks_reset_share(seg[:, :30], 8)) == pytest.approx(
        1 / 8)
    x, dt, a, bm, cm, d = _inputs(11, 1, 32, 2, 4, 1, 8)
    _, peak = ssd.ssd_scan(x, dt, a, bm, cm, d, None, chunk=8)

    def states(seg):
        """The states before positions 8, 16, 24 by the recurrence."""
        start = ssd.document_starts(seg, 1, 32)
        state, seen = jnp.zeros((2, 4, 8)), []
        for t in range(32):
            if t in (8, 16, 24):     # what chunk t // 8 - 1 closed with
                seen.append(state)
            keep = jnp.where(start[0, t], 0.0, jnp.exp(dt[0, t] * a))
            state = keep[:, None, None] * state + (
                dt[0, t][:, None] * x[0, t])[:, :, None] * bm[0, t, 0]
        return max(float(jnp.max(jnp.abs(s))) for s in seen)

    assert float(peak) == pytest.approx(states(None), rel=1e-5)
    # every chunk's first position a document's first: what is handed on is
    # still the state its chunk CLOSED with (the next chunk drops it)
    every = jnp.asarray(np.arange(32) // 8, jnp.int32)[None]
    _, cut = ssd.ssd_scan(x, dt, a, bm, cm, d, every, chunk=8)
    assert float(cut) == pytest.approx(states(every), rel=1e-5)
    # no gradient reaches the counter
    g = jax.grad(
        lambda x: ssd.ssd_scan(x, dt, a, bm, cm, d, None, chunk=8)[1])(x)
    assert float(jnp.max(jnp.abs(g))) == 0.0


@pytest.mark.parametrize("chunk", [8, 32, 128])
def test_the_running_sum_is_a_float32_triangular_product(chunk):
    """``cs`` is the product with a triangle of ones (``ops/ssd.py``'s
    docstring): the running sum within float32 rounding at the tests'
    harshest magnitudes (``dt`` 1 and ``A`` -16 in one row: ``cs`` to -16 x
    ``chunk``), and in ``_group`` that product is at ``Precision.HIGHEST``,
    which is what keeps it float32 on the TPU (one bf16 pass by default,
    which no CPU run shows)."""
    rng = np.random.default_rng(12)
    dt = np.exp(rng.uniform(np.log(0.01), 0.0, (3, 5, chunk)))
    a = -rng.uniform(1.0, 16.0, (3, 5, 1))
    dt[0], a[0] = 1.0, -16.0
    v = jnp.asarray(dt * a, jnp.float32)
    got, want = ssd.running_sum(v), jnp.cumsum(v, axis=-1)
    assert got.dtype == jnp.float32 and float(got[0, 0, -1]) == -16.0 * chunk
    # one sign throughout: |cs| is the sum of the terms' magnitudes
    err = float(jnp.max(jnp.abs(got - want) / jnp.abs(want)))
    assert err <= chunk * 2.0 ** -23, err

    b, c, g, r, p, n = 1, 2, 2, 2, 4, 8
    f32 = jnp.float32
    args = (jnp.zeros((b, g, r, p, n), f32), jnp.zeros((b, c, chunk, g, r, p)),
            jnp.ones((b, c, chunk, g, r), f32), -jnp.ones((g, r), f32),
            jnp.ones((g, r), f32), jnp.zeros((b, c, chunk, g, n)),
            jnp.zeros((b, c, chunk, g, n)), jnp.zeros((b, c, chunk), bool))
    jaxpr = jax.make_jaxpr(ssd._group)(*args).jaxpr
    sums = [e for e in eqns(jaxpr, "dot_general")
            if e.invars[1].aval.shape == (chunk, chunk)]
    assert len(sums) == 1
    assert sums[0].outvars[0].aval.shape == (b, c, g, r, chunk)
    assert sums[0].outvars[0].aval.dtype == f32
    assert sums[0].params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
