"""The sparse-attention, routed-expert decoder on the CPU at small sizes,
seeded weights: the program (``models/sparse_decoder.py``, ``models/moe.py``
``RoutedExperts``, ``ops/indexed_attention.py``) against the benchmark's
plain float32 reference (``benchmark/reference/keye_vl2_30b_a3b.py``), which
shares no code with it."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearningspark_tpu.models.moe import RoutedExperts
from distributeddeeplearningspark_tpu.models.sparse_decoder import (
    SparseDecoderConfig,
    SparseDecoderLayer,
    SparseDecoderLM,
)
from distributeddeeplearningspark_tpu.ops import indexed_attention as ia
from distributeddeeplearningspark_tpu.ops.attention import indexed_attention
from distributeddeeplearningspark_tpu.train import losses

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind: str, name: str):
    path = os.path.join(ROOT, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"keye_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference():
    return _load("reference", "keye_vl2_30b_a3b")


def _ref_cfg(cfg: SparseDecoderConfig) -> dict:
    """The configuration file's keys the reference reads, for ``cfg``."""
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob,
        "experts_held": list(cfg.experts_held or (0, cfg.num_experts)),
        "sa_config": {"indexer_num_heads": cfg.index_heads,
                      "indexer_head_dim": cfg.index_head_dim,
                      "topk": cfg.index_topk},
        "assumed_values": {"router_aux_loss_coef": cfg.router_aux_weight,
                           "indexer_loss_weight": cfg.index_loss_weight},
    }


def _flat(tree):
    return np.concatenate([np.asarray(a, np.float64).ravel()
                           for a in jax.tree.leaves(tree)])


def _rel(got, want):
    return np.linalg.norm(_flat(got) - _flat(want)) / np.linalg.norm(
        _flat(want))


# -- the selection -------------------------------------------------------------

def _topk_sets(scores, topk):
    """``lax.top_k``'s own selection as a mask: the first min(t+1, topk) of
    its indices for each query."""
    s = scores.shape[-1]
    _, idx = jax.lax.top_k(scores, min(topk, s))
    want = np.zeros(scores.shape, np.int8)
    for b in range(scores.shape[0]):
        for t in range(s):
            want[b, t, np.asarray(idx[b, t, :min(t + 1, topk)])] = 1
    return want


@pytest.mark.parametrize("case", ["distinct", "ties_at_threshold",
                                  "all_equal", "topk_over_seq"])
def test_selection_is_lax_top_k_including_ties_and_short_rows(case):
    s, topk = 48, 8
    rng = np.random.default_rng(0)
    scores = rng.standard_normal((2, s, s)).astype(np.float32)
    if case == "ties_at_threshold":
        scores = np.round(scores * 2) / 2 + 0.0  # many equal values a row
    elif case == "all_equal":
        scores[:] = 0.0                        # relu's exact zeros, all tied
    elif case == "topk_over_seq":
        topk = 64
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    mask, lse = ia.select_topk(jnp.asarray(scores), topk)
    want = _topk_sets(jnp.asarray(scores), topk)
    np.testing.assert_array_equal(np.asarray(mask), want)
    assert (np.asarray(mask).sum(-1)
            == np.minimum(np.arange(s) + 1, topk)).all()
    ref_lse = jax.nn.logsumexp(jnp.where(want > 0, scores, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), rtol=1e-6)


# -- the kernels against the dense path, and both against the reference --------

def _attention_inputs(seed=0, b=2, s=256, h=4, hkv=2, d=32, hi=4, di=16):
    ks = jax.random.split(jax.random.key(seed), 7)
    n = jax.random.normal
    return (n(ks[0], (b, s, h, d)), n(ks[1], (b, s, hkv, d)),
            n(ks[2], (b, s, hkv, d)), n(ks[3], (b, s, hi, di)),
            n(ks[4], (b, s, di)), 0.1 * n(ks[5], (b, s, hi)),
            n(ks[6], (b, s, h, d)))


def test_kernels_match_the_dense_path_forward_and_backward(monkeypatch):
    # four tiles at this size: the diagonal, below it, and skipped above it
    monkeypatch.setattr(ia, "DEFAULT_BLOCK", 128)
    *args, cot = _attention_inputs()

    def scalar(fn):
        def f(*a):
            o, kl, sel = fn(*a)
            return jnp.sum(o * cot) + 3.0 * jnp.mean(kl), (o, kl, sel)
        return jax.jit(jax.value_and_grad(f, argnums=tuple(range(6)),
                                          has_aux=True))

    kern = scalar(lambda *a: ia.indexed_attention(*a, topk=64))
    dense = scalar(lambda *a: ia.indexed_attention_xla(*a, topk=64))
    (lk, (ok, klk, sk)), gk = kern(*args)
    (ld, (od, kld, sd)), gd = dense(*args)
    np.testing.assert_allclose(ok, od, atol=2e-5)
    np.testing.assert_allclose(klk, kld, atol=2e-5)
    np.testing.assert_array_equal(sk, sd)
    for a, b in zip(gk, gd):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max())
                                   + 1e-7)


def test_attention_matches_the_reference_block():
    ref = _reference()
    q, k, v, qi, ki, wi, _ = _attention_inputs(seed=3, b=1, s=128)
    o, kl, sel = ia.indexed_attention_xla(q, k, v, qi, ki, wi, topk=32)
    with jax.default_matmul_precision("highest"):
        ro, rkl = ref._attend_block(q[0], k[0], v[0], qi[0], ki[0], wi[0], 0,
                                    topk=32, group=2)
        _, hit = ref.selection(qi[0], ki[0], wi[0], 0, 32)
    np.testing.assert_allclose(o[0], ro, atol=1e-5)
    np.testing.assert_allclose(kl[0], rkl, atol=1e-5)
    assert float(sel[0]) == float(hit.sum())


# -- the experts ---------------------------------------------------------------

def _experts(held=None, e=16, k=4, h=32, i=16):
    return RoutedExperts(h, i, e, k, held=held, dtype=jnp.float32)


def _share_params(params, first, count):
    return {"router": params["router"],
            **{n: params[n][first:first + count]
               for n in ("w_gate", "w_up", "w_down")}}


def test_the_shares_add_up_to_the_uncut_reference_layer():
    ref = _reference()
    x = jax.random.normal(jax.random.key(1), (2, 24, 32))
    full = _experts()
    params = full.init(jax.random.key(0), x)["params"]
    cfg = {"num_experts_per_tok": 4, "norm_topk_prob": True,
           "rms_norm_eps": 1e-6, "experts_held": [0, 16]}
    # the reference's expert layer normalises its input itself: give it a
    # unit scale and hand the program the normalised rows
    flat = x.reshape(-1, 32)
    normed = ref._rms(flat, jnp.ones(32), 1e-6)
    with jax.default_matmul_precision("highest"):
        want, want_aux = ref.experts(
            flat, {"mlp_norm": {"scale": jnp.ones(32)}, "moe": params}, cfg)
    total, shares = 0.0, []
    for r in range(8):   # 8 ranks of 2 experts
        y, st = _experts(held=(2 * r, 2)).apply(
            {"params": _share_params(params, 2 * r, 2)},
            normed.reshape(x.shape))
        total = total + y.reshape(-1, 32)
        shares.append(float(st["rows_held_share"]))
        np.testing.assert_allclose(st["aux"], want_aux, rtol=1e-5)
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert abs(sum(shares) - 1.0) < 1e-6


def test_no_assignment_is_dropped_when_every_token_picks_the_same_experts():
    x = jax.random.normal(jax.random.key(2), (1, 64, 32))
    layer = _experts(held=(0, 4), e=16, k=4)
    params = layer.init(jax.random.key(0), x)["params"]
    # a router that sends every token to experts 0..3: their columns are a
    # small positive constant, the rest 0, and the rows are positive
    params = {**params, "router": jnp.ones((32, 1)) * 1e-3
              * (jnp.arange(16) < 4)[None, :]}
    x = jnp.abs(x) + 1.0
    y, st = layer.apply({"params": params}, x)
    assert float(st["rows_held_share"]) == 1.0      # all 64 * 4 rows are here
    assert float(st["load_max_over_mean"]) == 1.0   # 64 rows each
    ref = _reference()
    cfg = {"num_experts_per_tok": 4, "norm_topk_prob": True,
           "rms_norm_eps": 1e-6, "experts_held": [0, 4]}
    flat = x.reshape(-1, 32)
    # the reference on the same (already "normed") rows: undo its norm by
    # feeding rows whose RMS is 1
    rows = ref._rms(flat, jnp.ones(32), 0.0)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(rows, {"mlp_norm": {"scale": jnp.ones(32)},
                                     "moe": params}, cfg)
    got, _ = layer.apply({"params": params}, rows.reshape(x.shape))
    np.testing.assert_allclose(got.reshape(-1, 32), want, atol=1e-5)


# -- the layer and the model against the reference -----------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = SparseDecoderConfig.tiny(experts_held=(2, 4))
    model = SparseDecoderLM(cfg)
    batch = {"input_ids": jax.random.randint(jax.random.key(5), (2, 96), 0,
                                             cfg.vocab_size)}
    params = model.init(jax.random.key(6), batch)["params"]
    return cfg, model, params, batch


def test_layer_matches_the_reference(tiny):
    cfg, _, params, _ = tiny
    ref = _reference()
    x = jax.random.normal(jax.random.key(7), (2, 96, cfg.hidden_size))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    got, stats = jax.jit(SparseDecoderLayer(cfg).apply)({"params": lp}, x)
    with jax.default_matmul_precision("highest"):
        want, kl, aux = jax.jit(
            lambda x, lp: ref.layer(x, lp, _ref_cfg(cfg)))(x, lp)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(stats["index_kl"], kl, rtol=1e-4)
    np.testing.assert_allclose(stats["moe_aux"], aux, rtol=1e-5)
    assert 0.0 < float(stats["moe_rows_held_share"]) < 1.0


def test_loss_and_gradients_of_a_two_layer_model_match_the_reference(tiny):
    cfg, model, params, batch = tiny
    ref = _reference()

    def program(p):
        return losses.sparse_moe_lm(model.apply({"params": p}, batch), batch)[0]

    got_loss, got_grad = jax.jit(jax.value_and_grad(program))(params)
    with jax.default_matmul_precision("highest"):
        want_loss, want_grad = jax.jit(jax.value_and_grad(
            lambda p: ref.training_loss(p, batch, _ref_cfg(cfg))))(params)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    assert _rel(got_grad, want_grad) < 1e-4


def test_the_compared_scalar_adds_the_expert_probe_and_weighs_leaves(tiny):
    """What the benchmark's harness compares (``reference.loss`` against the
    configuration's ``program_loss``): the training loss plus the logarithm
    of the experts' output energy in float32, equal on both sides, and the
    named leaves' gradients times their weights, on both sides."""
    cfg, model, params, batch = tiny
    ref = _reference()
    config = _load("configs", "keye_vl2_30b_a3b")
    rcfg = {**_ref_cfg(cfg), "check": {"grad_leaf_weights": {
        "layers/wq/kernel": 32, "layers/wk/kernel": 32}}}
    with jax.default_matmul_precision("highest"):
        plain, plain_grad = jax.jit(jax.value_and_grad(
            lambda p: ref.training_loss(p, batch, rcfg)))(params)
        want, want_grad = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, {}, batch, rcfg)))(params)
        probe = ref.expert_probe(params, batch["input_ids"], rcfg)
    got_probe = config.expert_probe(model, params, batch)
    assert float(got_probe) == pytest.approx(float(probe), rel=1e-5)
    assert float(want) == pytest.approx(float(plain) + np.log(float(probe)),
                                        abs=1e-5)
    for name, leaf in want_grad["layers"].items():
        scale = 32.0 if name in ("wq", "wk") else 1.0
        for a, b in zip(jax.tree.leaves(leaf),
                        jax.tree.leaves(plain_grad["layers"][name])):
            np.testing.assert_allclose(a, scale * np.asarray(b), rtol=1e-5,
                                       atol=1e-9)
    weights = rcfg["check"]["grad_leaf_weights"]
    weighed = jax.jit(jax.grad(lambda p: losses.sparse_moe_lm(model.apply(
        {"params": config._weigh(p, weights)}, batch), batch)[0]))(params)
    assert _rel(weighed, want_grad) < 1e-4


def test_indexer_trains_on_the_kl_term_alone(tiny):
    cfg, model, params, batch = tiny

    def term(name):
        def f(p):
            out = model.apply({"params": p}, batch)
            if name == "index_kl":
                return out["index_kl"]
            return losses.sparse_moe_lm(out, batch)[1]["lm_loss"] \
                + out["moe_aux"]
        return jax.jit(jax.grad(f))(params)["layers"]

    by_kl, by_lm = term("index_kl"), term("lm")
    indexer = ("index_wq", "index_wk", "index_w", "index_k_norm")
    for name in indexer:
        assert float(jnp.abs(_flat(by_kl[name])).max()) > 0, name
        assert float(jnp.abs(_flat(by_lm[name])).max()) == 0.0, name
    for name in set(by_kl) - set(indexer):
        assert float(np.abs(_flat(by_kl[name])).max()) == 0.0, name
        assert float(np.abs(_flat(by_lm[name])).max()) > 0, name


def test_kernel_path_of_the_model_matches_the_dense_path(monkeypatch):
    from distributeddeeplearningspark_tpu.models import sparse_decoder

    cfg = SparseDecoderConfig.tiny(index_topk=64)
    batch = {"input_ids": jax.random.randint(jax.random.key(8), (1, 256), 0,
                                             cfg.vocab_size)}
    dense = SparseDecoderLM(cfg)
    params = dense.init(jax.random.key(9), batch)["params"]

    def run():
        return jax.jit(jax.value_and_grad(lambda p: losses.sparse_moe_lm(
            dense.apply({"params": p}, batch), batch)[0]))(params)

    la, ga = run()  # off the TPU "auto" is the dense path
    monkeypatch.setattr(sparse_decoder, "indexed_attention", functools.partial(
        indexed_attention, impl="pallas"))
    lb, gb = run()
    assert abs(float(la) - float(lb)) < 1e-5
    assert _rel(gb, ga) < 1e-4


def test_router_dispatch_under_a_data_mesh_is_batch_local():
    """The kernels' ``shard_map`` on more than one device: batch rows over
    ``data``, every head on every device."""
    from distributeddeeplearningspark_tpu.ops import ring_attention

    if len(jax.devices()) < 2:
        pytest.skip("needs two host devices")
    *args, _ = _attention_inputs(seed=11)
    want = ia.indexed_attention_xla(*args, topk=64)
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
    mesh = MeshSpec(data=2).build(jax.devices()[:2])
    ring_attention.set_default_mesh(mesh)
    try:
        with mesh:
            got = jax.jit(lambda *a: indexed_attention(
                *a, topk=64, impl="pallas"))(*args)
    finally:
        ring_attention.set_default_mesh(None)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)
