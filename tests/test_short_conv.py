"""The short convolutions (``ops/short_conv.py``), gated and ungated: each
form's two Pallas kernels in interpret mode and its XLA path against a
position-by-position loop, with document boundaries inside the taps and at a
kernel block's edge, forward and backward; the routers; the ``shard_map``
over the batch rows on the CPU mesh. Mosaic's verdict on the kernels is in
``tests/test_compile_for_v5e.py``."""

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


# -- the ungated form of a state-space layer: four taps, a bias, a SiLU -------

#: the ungated kernels run with the positions in the lanes: blocks of 128
#: positions (four) and 128 channels (two)
S_U, BLOCK_T_U, BLOCK_C_U = 512, 128, 128


def _ungated_inputs(dtype=jnp.float32):
    """Four taps; documents of one position at a block's start (128), that
    end a position before a block's end (255) and start one after it (257),
    one at the window's last position, and boundaries three positions apart
    across a block's start (381, 384)."""
    rng = np.random.default_rng(3)
    v = jnp.asarray(rng.normal(size=(B, S_U, C)), dtype)
    w = jnp.asarray(rng.normal(size=(C, 4)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    seg = np.zeros((B, S_U), np.int32)
    for at in (128, 129, 255, 257, 511):
        seg[0, at:] += 1
    for at in (1, 127, 381, 384):
        seg[1, at:] += 1
    return v, w, bias, jnp.asarray(seg)


def _ungated_by_hand(v, w, bias, seg):
    """``silu(b + sum_j w[:, j] v[t - 3 + j])`` position by position."""
    v, w = np.asarray(v, np.float64), np.asarray(w, np.float64)
    z = np.zeros_like(v) + np.asarray(bias, np.float64)
    for row in range(v.shape[0]):
        for t in range(v.shape[1]):
            for j in range(w.shape[1]):
                s = t - (w.shape[1] - 1) + j
                if s >= 0 and (seg is None or seg[row, s] == seg[row, t]):
                    z[row, t] += w[:, j] * v[row, s]
    return z / (1 + np.exp(-z))


def _silu_pallas(v, w, bias, seg):
    """The kernels at four position blocks and two channel blocks."""
    return sc.silu_short_conv_pallas(v, w, bias, seg, block_t=BLOCK_T_U,
                                     block_c=BLOCK_C_U)


UNGATED = {"xla": sc.silu_short_conv_xla, "pallas_interpreted": _silu_pallas}


@pytest.mark.parametrize("segmented", [True, False],
                         ids=["documents", "one_document"])
@pytest.mark.parametrize("impl", list(UNGATED))
def test_the_ungated_form_is_its_shifted_sums_forward_and_backward(
        impl, segmented):
    """Four taps, a bias and a SiLU, the terms of a tap counted inside the
    position's document (boundaries within the four taps, at, after and
    before a block's edge, a document of one position, one at the window's
    end); the gradient of the input, the taps and the bias against the same
    written with ``jnp`` position shifts."""
    v, w, bias, seg = _ungated_inputs()
    seg = seg if segmented else None
    fn = UNGATED[impl]
    got = fn(v, w, bias, seg)
    assert got.shape == v.shape and got.dtype == v.dtype
    np.testing.assert_allclose(
        got, _ungated_by_hand(v, w, bias,
                              None if seg is None else np.asarray(seg)),
        rtol=1e-5, atol=1e-5)

    def shifted_sums(v, w, bias):
        z = jnp.broadcast_to(bias, v.shape)
        for j in range(4):
            d = 3 - j
            moved = jnp.roll(v, d, axis=1)
            ok = (jnp.arange(S_U) >= d)[None, :]
            if seg is not None:
                ok = ok & (jnp.roll(seg, d, axis=1) == seg)
            z = z + w[:, j] * jnp.where(ok[..., None], moved, 0.0)
        return z * jax.nn.sigmoid(z)

    weights = jnp.asarray(np.random.default_rng(4).normal(size=v.shape),
                          jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(weights * fn(*a, seg)),
                     argnums=(0, 1, 2))(v, w, bias)
    wants = jax.grad(lambda *a: jnp.sum(weights * shifted_sums(*a)),
                     argnums=(0, 1, 2))(v, w, bias)
    for name, a, b in zip(("v", "taps", "bias"), grads, wants):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("segmented", [True, False],
                         ids=["documents", "one_document"])
def test_the_ungated_kernels_give_what_the_plain_path_gives(segmented):
    """``mamba_conv_fwd`` and ``mamba_conv_bwd`` against
    ``silu_short_conv_xla``: the same float32 arithmetic, so the output and
    the three gradients within float32 rounding; the taps' and the bias's
    gradients summed over both rows and all four position blocks."""
    v, w, bias, seg = _ungated_inputs()
    seg = seg if segmented else None
    weights = jnp.asarray(np.random.default_rng(5).normal(size=v.shape),
                          jnp.float32)
    seen = {name: jax.value_and_grad(
        lambda *a, fn=fn: jnp.sum(weights * fn(*a, seg)), (0, 1, 2))(
            v, w, bias) for name, fn in UNGATED.items()}
    got, want = seen["pallas_interpreted"], seen["xla"]
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    for name, a, b in zip(("v", "taps", "bias"), got[1], want[1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5, err_msg=name)
    assert float(jnp.abs(want[1][2]).max()) > 1.0   # the bias's gradient


@pytest.mark.parametrize("impl", list(UNGATED))
def test_the_ungated_form_in_bf16_and_its_refusals(impl):
    v, w, bias, seg = _ungated_inputs(jnp.bfloat16)
    got = UNGATED[impl](v, w, bias, seg)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        _ungated_by_hand(v.astype(jnp.float32), w, bias, np.asarray(seg)),
        rtol=2e-2, atol=2e-2)       # one bf16 rounding of the output
    with pytest.raises(ValueError, match="taps"):
        sc.silu_short_conv(v, w[:8], bias, seg)
    with pytest.raises(ValueError, match="bias"):
        sc.silu_short_conv(v, w, bias[:8], seg)
    # the gated form's XLA path shares the tap loop and gives what it gave
    bcx, w3, seg = _inputs()
    np.testing.assert_allclose(
        sc.gated_short_conv_xla(bcx, w3, seg),
        _by_hand(bcx, w3, np.asarray(seg)), rtol=1e-5, atol=1e-5)


def test_the_ungated_router_takes_the_kernels_where_the_shape_fits(
        monkeypatch):
    """Answered "on a TPU": ``"auto"`` runs the kernels where the window
    divides by their blocks and falls back to XLA where it does not;
    ``"pallas"`` refuses such a shape, and more taps than the lanes hold."""
    from _jaxpr import eqns

    monkeypatch.setattr(sc, "on_tpu", lambda: True)
    v, w, bias, seg = _ungated_inputs()

    def kernels(v, seg):
        jaxpr = jax.make_jaxpr(jax.grad(lambda v: jnp.sum(
            sc.silu_short_conv(v, w, bias, seg))))(v)
        return sorted(e.params["name"] for e in eqns(jaxpr.jaxpr,
                                                     "pallas_call"))

    assert kernels(v, seg) == ["mamba_conv_bwd", "mamba_conv_fwd"]
    odd = v[:, :500], seg[:, :500]       # no multiple of 128 positions
    assert kernels(*odd) == []
    np.testing.assert_array_equal(
        sc.silu_short_conv(odd[0], w, bias, odd[1]),
        sc.silu_short_conv_xla(odd[0], w, bias, odd[1]))
    with pytest.raises(ValueError, match="divide"):
        sc.silu_short_conv(odd[0], w, bias, odd[1], impl="pallas")
    with pytest.raises(ValueError, match="K <="):
        sc.silu_short_conv(v, jnp.zeros((C, 5)), bias, seg, impl="pallas")
    with pytest.raises(ValueError, match="unknown"):
        sc.silu_short_conv(v, w, bias, seg, impl="cudnn")


def test_on_a_mesh_the_ungated_kernels_run_in_a_shard_map_over_the_rows():
    """Two devices, a row each: the same numbers, and the taps' and the
    bias's gradients summed over the rows' ranks."""
    from distributeddeeplearningspark_tpu.ops import ring_attention
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    v, w, bias, seg = _ungated_inputs()
    v, w, bias = v[..., :128], w[:128], bias[:128]  # one block wide
    mesh = MeshSpec(data=2).build(jax.devices()[:2])
    g = jnp.asarray(np.random.default_rng(6).normal(size=v.shape),
                    jnp.float32)

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a, seg) * g)

    ring_attention.set_default_mesh(mesh)
    try:
        got = jax.jit(jax.value_and_grad(scalar(
            lambda *a: sc.silu_short_conv(*a, impl="pallas")),
            (0, 1, 2)))(v, w, bias)
    finally:
        ring_attention.set_default_mesh(None)
    want = jax.value_and_grad(scalar(sc.silu_short_conv_xla), (0, 1, 2))(
        v, w, bias)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-5)
