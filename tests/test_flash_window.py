"""The band regime of the flash kernels (``ops/flash_attention.py`` with a
``window``), interpreted on the CPU: output and the three gradients against
the XLA path and against a softmax under a dense mask built from positions
and segment ids, for windows smaller than, equal to, larger than and not a
multiple of the block, with and without segment ids, at groups 6 and 8; the
share of the triangle the kernels walk against the blocks the dense mask
allows; the grids that span the band and not the row, and the calls' names;
what the band refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jaxpr import eqns
from distributeddeeplearningspark_tpu.ops.attention import (
    dot_product_attention,
)
from distributeddeeplearningspark_tpu.ops.flash_attention import (
    band_blocks,
    flash_attention,
    window_blocks_walked_share,
)

S, D, BLOCK = 128, 16, 16


def _qkv(h, hkv, seed):
    rng = np.random.default_rng(seed)
    mk = lambda n: jnp.asarray(rng.normal(size=(1, S, n, D)), jnp.float32)
    return mk(h), mk(hkv), mk(hkv)


def _segments(seed):
    """Four documents with boundaries inside blocks and on an edge."""
    cuts = (0, 21, 48, 90, S)
    del seed
    return jnp.asarray(np.searchsorted(np.asarray(cuts[1:-1]), np.arange(S),
                                       side="right"), jnp.int32)[None]


def _allowed(window, seg):
    """[S, S] bool: key j allowed to query i."""
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    ok = (j <= i) & (i - j < window)
    if seg is not None:
        s = np.asarray(seg[0])
        ok &= s[:, None] == s[None, :]
    return ok


def _dense(q, k, v, window, seg):
    """Softmax under the dense mask, the grouped keys repeated."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    logits = jnp.where(jnp.asarray(_allowed(window, seg))[None, None],
                       logits, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)


def _flash(q, k, v, window, seg):
    return flash_attention(q, k, v, causal=True, segment_ids=seg,
                           window=window, block_q=BLOCK, block_k=BLOCK)


def _xla(q, k, v, window, seg):
    return dot_product_attention(q, k, v, causal=True, segment_ids=seg,
                                 window=window, impl="xla")


#: smaller than, equal to and larger than the block of 16, none of them a
#: multiple of it but the one, and one of the whole row
WINDOWS = [5, 16, 40, 23, 1, S]


@pytest.mark.parametrize("segmented", [False, True], ids=["row", "segments"])
@pytest.mark.parametrize("heads", [(12, 2), (16, 2)], ids=["g6", "g8"])
@pytest.mark.parametrize("window", WINDOWS)
def test_the_band_gives_the_dense_masks_output_and_gradients(
        window, heads, segmented):
    q, k, v = _qkv(*heads, seed=window)
    seg = _segments(0) if segmented else None
    out = _flash(q, k, v, window, seg)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense(q, k, v, window, seg)),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_xla(q, k, v, window, seg)),
                               atol=2e-5, rtol=2e-5)
    cot = jnp.asarray(np.random.default_rng(1).normal(size=out.shape),
                      jnp.float32)
    grads = lambda fn: jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v, window, seg) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for got, want, xla in zip(grads(_flash), grads(_dense), grads(_xla)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(xla),
                                   atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("segmented", [False, True], ids=["row", "segments"])
@pytest.mark.parametrize("window", [5, 16, 23, 40, 64, S])
def test_the_walked_share_is_the_dense_masks_count(window, segmented):
    """``window_blocks_walked_share``: of the ``n (n + 1) / 2`` blocks on
    or under the diagonal, those that hold an allowed pair, counted from the
    dense mask; a band of the whole row walks the causal triangle."""
    seg = _segments(0) if segmented else jnp.zeros((1, S), jnp.int32)
    allowed = _allowed(window, seg if segmented else None)
    n = S // BLOCK
    blocks = allowed.reshape(n, BLOCK, n, BLOCK).any(axis=(1, 3))
    want = blocks.sum() / (n * (n + 1) / 2)
    got = float(window_blocks_walked_share(seg, window, block=BLOCK))
    assert got == pytest.approx(want)
    if window >= S and not segmented:
        assert got == 1.0


def test_at_the_cells_size_the_band_walks_an_eighth_of_the_triangle():
    """32 blocks of 512, a window of 512: a query block's band is its own
    block and the one before, 63 of 528 blocks, 11.9%; documents take some
    away."""
    row = jnp.zeros((1, 16384), jnp.int32)
    assert float(window_blocks_walked_share(row, 512)) == pytest.approx(
        63 / 528)
    docs = jnp.asarray(np.searchsorted([5000, 11000], np.arange(16384),
                                       side="right"), jnp.int32)[None]
    assert float(window_blocks_walked_share(docs, 512)) < 63 / 528
    assert band_blocks(512, block_q=512, block_k=512, num_qb=32,
                       num_kb=32) == (2, 2)
    assert band_blocks(512, block_q=256, block_k=256, num_qb=64,
                       num_kb=64) == (3, 3)


@pytest.mark.parametrize("window, span", [(5, 2), (16, 2), (23, 3), (40, 4),
                                          (S, 8)])
def test_the_grids_span_the_band_and_carry_the_bands_names(window, span):
    """The key axis of the forward and dQ grids and the query axis of dK/dV
    walk ``ceil((window - 1) / block) + 1`` blocks (never more than the
    row's), not the row's; the calls are ``flash_window_*``, which neither
    ``flash_fwd`` nor ``flash_bwd`` is a part of."""
    q, k, v = _qkv(12, 2, seed=0)
    seg = _segments(0)
    calls = {}
    for eqn in eqns(jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(_flash(q, k, v, window, seg)),
            argnums=(0, 1, 2)))(q, k, v).jaxpr, "pallas_call"):
        calls[eqn.params["name"]] = eqn.params["grid_mapping"].grid
    n = S // BLOCK
    assert min(-(-(window - 1) // BLOCK) + 1, n) == span
    assert calls == {"flash_window_fwd": (12, n, span),
                     "flash_window_bwd_dq": (12, n, span),
                     "flash_window_bwd_dkv": (2, n, 6 * span)}
    for name in calls:
        assert "flash_fwd" not in name and "flash_bwd" not in name


def test_a_window_the_kernels_cannot_take_is_refused():
    q, k, v = _qkv(4, 2, seed=0)
    for window in (0, -3, 2.5, True):
        with pytest.raises(ValueError, match="window"):
            _flash(q, k, v, window, None)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=16, block_q=BLOCK, block_k=BLOCK)
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, window=16, impl="xla")
    for impl in ("ring", "ulysses"):
        with pytest.raises(NotImplementedError, match="window"):
            dot_product_attention(q, k, v, causal=True, window=16, impl=impl)
