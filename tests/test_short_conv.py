"""The gated short convolution (``ops/short_conv.py``): the two Pallas kernels
in interpret mode and the XLA path against a position-by-position loop, with
document boundaries inside the three taps and at a kernel block's edge,
forward and backward; the ``shard_map`` over the batch rows on the CPU mesh.
Mosaic's verdict on the kernels is in ``tests/test_compile_for_v5e.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearningspark_tpu.ops import short_conv as sc

B, S, C, K = 2, 64, 256, 3
BLOCK_T, BLOCK_C = 16, 128


def _inputs(dtype=jnp.float32):
    rng = np.random.default_rng(0)
    bcx = jnp.asarray(rng.normal(size=(B, S, 3 * C)), dtype)
    w = jnp.asarray(rng.normal(size=(C, K)), jnp.float32)
    seg = np.zeros((B, S), np.int32)
    # a document of ONE position at a block's edge (16), one that starts a
    # position before an edge (31), two boundaries within three taps (31,
    # 33), one at the window's last position
    for at in (16, 17, 31, 33, 63):
        seg[0, at:] += 1
    for at in (1, 15, 48):
        seg[1, at:] += 1
    return bcx, w, jnp.asarray(seg)


def _by_hand(bcx, w, seg):
    b, c, u = np.split(np.asarray(bcx, np.float64), 3, -1)
    v, w = b * u, np.asarray(w, np.float64)
    z = np.zeros_like(v)
    for row in range(B):
        for t in range(S):
            for j in range(K):
                s = t - (K - 1) + j
                if s >= 0 and (seg is None or seg[row, s] == seg[row, t]):
                    z[row, t] += w[:, j] * v[row, s]
    return c * z


def _pallas(bcx, w, seg):
    return sc.gated_short_conv_pallas(bcx, w, seg, block_t=BLOCK_T,
                                      block_c=BLOCK_C)


IMPLS = {"xla": sc.gated_short_conv_xla, "pallas_interpreted": _pallas}


@pytest.mark.parametrize("segmented", [True, False],
                         ids=["documents", "one_document"])
@pytest.mark.parametrize("impl", list(IMPLS))
def test_taps_stop_at_document_boundaries(impl, segmented):
    bcx, w, seg = _inputs()
    seg = seg if segmented else None
    want = _by_hand(bcx, w, None if seg is None else np.asarray(seg))
    got = IMPLS[impl](bcx, w, seg)
    assert got.shape == (B, S, C) and got.dtype == bcx.dtype
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    if segmented:
        # and the boundaries matter: without them the answer is another
        other = _by_hand(bcx, w, None)
        assert np.abs(other - want).max() > 0.1


@pytest.mark.parametrize("segmented", [True, False],
                         ids=["documents", "one_document"])
def test_backward_kernel_is_the_gradient_of_the_plain_path(segmented):
    bcx, w, seg = _inputs()
    seg = seg if segmented else None
    g = jnp.asarray(np.random.default_rng(1).normal(size=(B, S, C)),
                    jnp.float32)
    grads = {name: jax.grad(lambda a, b, fn=fn: jnp.sum(fn(a, b, seg) * g),
                            (0, 1))(bcx, w) for name, fn in IMPLS.items()}
    for got, want in zip(grads["pallas_interpreted"], grads["xla"]):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=1e-5)
    assert float(jnp.abs(grads["xla"][1]).max()) > 1.0   # the taps' gradient


def test_bf16_in_gives_bf16_out_from_float32_arithmetic():
    bcx, w, seg = _inputs(jnp.bfloat16)
    got = _pallas(bcx, w, seg)
    assert got.dtype == jnp.bfloat16
    want = _by_hand(bcx.astype(jnp.float32), w, np.asarray(seg))
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=0.05, rtol=0.02)


def test_shapes_the_kernels_do_not_take_are_refused():
    bcx, w, seg = _inputs()
    with pytest.raises(ValueError, match="divide"):
        sc.gated_short_conv_pallas(bcx[:, :60], w, seg[:, :60],
                                   block_t=BLOCK_T, block_c=BLOCK_C)
    with pytest.raises(ValueError, match="K <="):
        sc.gated_short_conv_pallas(bcx, jnp.zeros((C, 5)), seg,
                                   block_t=BLOCK_T, block_c=BLOCK_C)
    with pytest.raises(ValueError, match="unknown"):
        sc.gated_short_conv(bcx, w, seg, impl="cudnn")
    # off the TPU the router takes the plain path
    np.testing.assert_array_equal(
        np.asarray(sc.gated_short_conv(bcx, w, seg)),
        np.asarray(sc.gated_short_conv_xla(bcx, w, seg)))


def test_on_a_mesh_the_kernels_run_in_a_shard_map_over_the_batch_rows():
    """Two devices, a row each: the same numbers, and the taps' gradient
    summed over the rows' ranks."""
    from distributeddeeplearningspark_tpu.ops import ring_attention
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    bcx, w, seg = _inputs()
    bcx, w = bcx[:, :, :3 * 128], w[:128]     # 128 channels: one block wide
    mesh = MeshSpec(data=2).build(jax.devices()[:2])
    g = jnp.asarray(np.random.default_rng(2).normal(size=(B, S, 128)),
                    jnp.float32)

    def scalar(fn):
        return lambda a, b: jnp.sum(fn(a, b, seg) * g)

    ring_attention.set_default_mesh(mesh)
    try:
        got = jax.jit(jax.value_and_grad(scalar(
            lambda a, b, s: sc.gated_short_conv(a, b, s, impl="pallas")),
            (0, 1)))(bcx, w)
    finally:
        ring_attention.set_default_mesh(None)
    want = jax.value_and_grad(scalar(sc.gated_short_conv_xla), (0, 1))(bcx, w)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-5)
