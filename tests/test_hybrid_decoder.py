"""The hybrid decoder (``models/hybrid_decoder.py``) at a toy size on the CPU:
the program against the benchmark's plain reference
(``benchmark/reference/lfm2_24b_a2b.py``) for the loss and every gradient
leaf, both operators across document boundaries (XLA paths, and the Pallas
kernels interpreted), both feed-forwards, the sigmoid router's selection on
score + bias, the bias through ``make_train_step`` under scan, remat and
gradient accumulation, the eight ranks' shares against the uncut layer, and
``packed_token_windows(segment_ids=True)``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distributeddeeplearningspark_tpu.models.hybrid_decoder import (  # noqa: E402
    ATTENTION, CONV, COUNTERS, HybridDecoderConfig, HybridDecoderLM,
    document_positions)
from distributeddeeplearningspark_tpu.models.moe import (  # noqa: E402
    BIAS_COLLECTION, RoutedExperts)
from distributeddeeplearningspark_tpu.train import losses  # noqa: E402
from distributeddeeplearningspark_tpu.train import step as step_lib  # noqa: E402

GAMMA = 0.001


def _load(rel):
    from benchmark.harness import runner
    return runner.load_module(os.path.join(ROOT, "benchmark", rel))


@pytest.fixture(scope="module")
def reference():
    ref = _load("reference/lfm2_24b_a2b.py")
    # blocks small enough that a toy window has several of each kind
    ref.ROWS, ref.QUERIES, ref.CHANNELS = 48, 32, 64
    return ref


def _reference_cfg(cfg: HybridDecoderConfig) -> dict:
    """What the reference reads of a configuration file, for ``cfg``."""
    first, count = cfg.experts_held or (0, cfg.num_experts)
    return {"layer_types": list(cfg.layer_types),
            "num_dense_layers": cfg.num_dense_layers,
            "norm_eps": cfg.rms_eps, "conv_L_cache": cfg.conv_taps,
            "rope_parameters": {"rope_theta": cfg.rope_theta},
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "experts_held": [first, count],
            "num_experts_per_tok": cfg.experts_per_token,
            "norm_topk_prob": cfg.norm_topk_prob,
            "train_router": cfg.train_router,
            "router_width": cfg.num_experts, "check": {}}


def _batch(seq=128, rows=2, documents=5, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": jnp.asarray(rng.integers(1, 256, (rows, seq)),
                                     jnp.int32),
            "segment_ids": jnp.asarray(
                np.sort(rng.integers(0, documents, (rows, seq)), axis=1),
                jnp.int32)}


def _init(cfg, batch, bias_scale=0.05, seed=0):
    model = HybridDecoderLM(cfg)
    variables = model.init(jax.random.PRNGKey(seed), batch)
    rng = np.random.default_rng(seed + 1)
    bias = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape) * bias_scale,
                              jnp.float32), variables[BIAS_COLLECTION])
    return model, variables["params"], {BIAS_COLLECTION: bias}


def _program_loss(model, params, mutable, batch):
    out = model.apply({"params": params, **mutable}, batch)
    return losses.hybrid_moe_lm(out, batch)[0]


def _leaf_errors(got, want):
    out = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        scale = float(jnp.sqrt(jnp.sum(b ** 2))) + 1e-12
        out[jax.tree_util.keystr(path)] = float(
            jnp.sqrt(jnp.sum((a - b) ** 2))) / scale
    return out


def test_the_depth_is_laid_out_as_leading_layers_periods_and_a_tail():
    lead, period, whole, trail = HybridDecoderConfig().layout()
    assert len(HybridDecoderConfig().layer_types) == 40
    assert lead == (CONV, CONV) and whole == 9
    assert period == (ATTENTION, CONV, CONV, CONV)
    assert trail == (ATTENTION, CONV)
    cut = HybridDecoderConfig(
        layer_types=(CONV, ATTENTION, CONV, CONV, CONV), num_dense_layers=1)
    assert cut.layout() == ((CONV,), (ATTENTION, CONV, CONV, CONV), 1, ())
    with pytest.raises(ValueError):
        HybridDecoderConfig(layer_types=("conv", "mamba"))
    # the published depth builds and counts (shapes only: 24B parameters)
    full = HybridDecoderLM(HybridDecoderConfig())
    shapes = jax.eval_shape(
        full.init, jax.random.PRNGKey(0),
        {"input_ids": jnp.zeros((1, 64), jnp.int32)})
    n = sum(x.size for x in jax.tree.leaves(shapes["params"]))
    assert 23.5e9 < n < 24.5e9, n
    assert shapes["params"]["periods"]["layer_0"]["moe"]["w_up"].shape == (
        9, 64, 2048, 1536)
    assert shapes[BIAS_COLLECTION]["trail_1"]["moe"]["bias"].shape == (64,)


@pytest.mark.parametrize("layout, train_router", [
    ("periods", True), ("with_a_tail", True), ("periods", False)])
def test_program_equals_reference_for_loss_and_every_gradient_leaf(
        reference, layout, train_router):
    """Both operators with document boundaries inside their reach, the dense
    and the expert feed-forward, a bias that is not zero, experts 2-5 of 8
    held; scanned periods with and without trailing layers; the router
    trained, and as a share without its exchange keeps it (no gradient
    through a token's weights: the router's kernel gets none at all)."""
    kinds = {"periods": (CONV, ATTENTION, CONV, ATTENTION, CONV),
             "with_a_tail": (CONV, CONV, ATTENTION, CONV, CONV, ATTENTION,
                             CONV, CONV, ATTENTION)}[layout]
    dense = {"periods": 1, "with_a_tail": 2}[layout]
    cfg = HybridDecoderConfig.tiny(layer_types=kinds, num_dense_layers=dense,
                                   experts_held=(2, 4),
                                   train_router=train_router)
    assert bool(cfg.layout()[3]) == (layout == "with_a_tail")
    batch = _batch()
    model, params, mutable = _init(cfg, batch)
    got, got_grad = jax.value_and_grad(
        lambda p: _program_loss(model, p, mutable, batch))(params)
    want, want_grad = jax.value_and_grad(
        lambda p: reference.training_loss(p, mutable, batch,
                                          _reference_cfg(cfg)))(params)
    assert abs(float(got) - float(want)) < 2e-5
    errors = _leaf_errors(got_grad, want_grad)
    assert len(errors) > 30
    assert max(errors.values()) < 2e-3, max(errors.items(), key=lambda kv: kv[1])
    # the gradient reaches the taps, the router and the tied embedding
    leaves = {k: float(jnp.abs(v).max()) for k, v in zip(
        errors, jax.tree.leaves(want_grad))}
    for name in ("taps", "embedding", "q_norm", "w_down", "w2"):
        assert any(name in k and v > 0 for k, v in leaves.items()), name
    routers = [v for k, v in leaves.items() if "router" in k]
    assert routers and all((v > 0) == train_router for v in routers)
    assert all((float(jnp.abs(g).max()) > 0) == train_router
               for k, g in zip(errors, jax.tree.leaves(got_grad))
               if "router" in k)


def test_positions_that_do_not_restart_give_the_same_attention(monkeypatch):
    """A rotary product depends on the difference of two positions, and no
    query reads a key of another document: restarting the positions with the
    document changes no number. The program restarts them (a window's late
    documents then see the angles its first sees)."""
    from distributeddeeplearningspark_tpu.models import hybrid_decoder

    cfg = HybridDecoderConfig.tiny(experts_held=(2, 4))
    batch = _batch()
    model, params, mutable = _init(cfg, batch)
    sound = _program_loss(model, params, mutable, batch)
    seg = jnp.asarray([[0, 0, 0, 1, 1, 2, 2, 2]])
    assert document_positions(seg).tolist() == [[0, 1, 2, 0, 1, 0, 1, 2]]
    monkeypatch.setattr(
        hybrid_decoder, "document_positions",
        lambda seg: jnp.broadcast_to(jnp.arange(seg.shape[1]), seg.shape))
    other = _program_loss(model, params, mutable, batch)
    assert abs(float(other) - float(sound)) < 1e-5
    # ... while a boundary that is not there is another function
    monkeypatch.undo()
    one = {**batch, "segment_ids": jnp.zeros_like(batch["segment_ids"])}
    assert abs(float(_program_loss(model, params, mutable, one))
               - float(sound)) > 1e-4


def test_the_kernels_interpreted_give_the_models_numbers(monkeypatch):
    """The model's two routers sent to their Pallas kernels (interpreted
    here): the flash kernel causal + grouped + segment ids, and the two
    short-convolution kernels, forward and backward, a boundary at a block's
    edge (512) among them."""
    from distributeddeeplearningspark_tpu.ops import attention, short_conv

    cfg = HybridDecoderConfig.tiny(
        layer_types=(CONV, ATTENTION, CONV), experts_held=(0, 4))
    rng = np.random.default_rng(3)
    seg = np.zeros((1, 1024), np.int32)
    for at in (100, 511, 512, 513, 900):
        seg[0, at:] += 1
    batch = {"input_ids": jnp.asarray(rng.integers(1, 256, (1, 1024)),
                                      jnp.int32),
             "segment_ids": jnp.asarray(seg)}
    model, params, mutable = _init(cfg, batch)
    fn = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, mutable, batch)))
    want, want_grad = fn(params)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(short_conv, "on_tpu", lambda: True)
    got, got_grad = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, mutable, batch)))(params)
    assert abs(float(got) - float(want)) < 1e-4
    errors = _leaf_errors(got_grad, want_grad)
    assert max(errors.values()) < 5e-3, max(errors.items(),
                                            key=lambda kv: kv[1])


def _routed(score, **kw):
    return RoutedExperts(16, 32, num_experts=8, top_k=2, dtype=jnp.float32,
                         score=score, **kw)


def test_the_bias_chooses_the_experts_and_the_scores_weigh_them():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 64, 16)),
                    jnp.float32)
    layer = _routed("sigmoid", select_bias=True)
    variables = layer.init(jax.random.PRNGKey(0), x)
    assert set(variables) == {"params", BIAS_COLLECTION}
    assert variables[BIAS_COLLECTION]["bias"].tolist() == [0.0] * 8
    params = variables["params"]
    score = jax.nn.sigmoid(x[0] @ params["router"])

    def expected(bias):
        """The equations with a loop over tokens and experts."""
        y = np.zeros((64, 16))
        for t in range(64):
            chosen = np.argsort(-(np.asarray(score[t]) + bias),
                                kind="stable")[:2]
            total = float(sum(score[t, e] for e in chosen)) + 1e-6
            for e in chosen:
                hid = jax.nn.silu(x[0, t] @ params["w_gate"][e]) * (
                    x[0, t] @ params["w_up"][e])
                y[t] += float(score[t, e]) / total * np.asarray(
                    hid @ params["w_down"][e])
        return y

    bias = np.zeros(8, np.float32)
    bias[3] = 0.5          # expert 3 now enters many tokens' top two
    for b in (np.zeros(8, np.float32), bias):
        y, stats = layer.apply({"params": params,
                                BIAS_COLLECTION: {"bias": jnp.asarray(b)}}, x)
        np.testing.assert_allclose(np.asarray(y[0]), expected(b), atol=2e-5)
        assert float(stats["bias_abs_max"]) == float(np.abs(b).max())
    plain = layer.apply({"params": params,
                         BIAS_COLLECTION: {"bias": jnp.zeros(8)}}, x)[0]
    moved = layer.apply({"params": params,
                         BIAS_COLLECTION: {"bias": jnp.asarray(bias)}}, x)[0]
    assert float(jnp.abs(plain - moved).max()) > 1e-3
    # no gradient reaches the bias, and the softmax router takes none
    grad = jax.grad(lambda b: jnp.sum(layer.apply(
        {"params": params, BIAS_COLLECTION: {"bias": b}}, x)[0] ** 2))(
        jnp.asarray(bias))
    assert float(jnp.abs(grad).max()) == 0.0
    with pytest.raises(ValueError, match="sigmoid only"):
        _routed("softmax", select_bias=True).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        _routed("tanh").init(jax.random.PRNGKey(0), x)


def test_the_bias_moves_only_when_its_collection_is_mutable():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 64, 16)),
                    jnp.float32)
    layer = _routed("sigmoid", select_bias=True, bias_update_rate=GAMMA)
    variables = layer.init(jax.random.PRNGKey(0), x)
    (_, stats), updated = layer.apply(variables, x,
                                      mutable=[BIAS_COLLECTION])
    moved = np.asarray(updated[BIAS_COLLECTION]["bias"])
    assert set(np.round(moved / GAMMA).tolist()) <= {-1.0, 0.0, 1.0}
    assert np.abs(moved).max() == pytest.approx(GAMMA)
    # an expert above the mean load moves down, one below it up
    score = jax.nn.sigmoid(x[0] @ variables["params"]["router"])
    counts = np.bincount(np.asarray(jax.lax.top_k(score, 2)[1]).reshape(-1),
                         minlength=8)
    np.testing.assert_array_equal(np.sign(moved),
                                  np.sign(counts.mean() - counts))
    del stats


def _train_state(cfg, batch, tx):
    model = HybridDecoderLM(cfg)
    variables = dict(model.init(jax.random.PRNGKey(0), batch))
    params = variables.pop("params")
    return model, step_lib.TrainState.create(
        params=params, opt_state=tx.init(params), mutable=variables)


@pytest.mark.parametrize("accum_steps", [1, 2])
def test_the_step_moves_the_bias_outside_the_gradient(accum_steps):
    """Through ``make_train_step``, under the period scan and the layers'
    remat: every bias moves by a multiple of gamma (once a micro-batch),
    the optimizer holds no state for it, and a skipped step keeps it."""
    cfg = HybridDecoderConfig.tiny(bias_update_rate=GAMMA)
    batch = _batch(seq=64, rows=2)
    tx = optax.adam(1e-3)
    model, state = _train_state(cfg, batch, tx)
    assert set(state.mutable) == {BIAS_COLLECTION}
    n_bias = len(jax.tree.leaves(state.mutable))
    assert len(jax.tree.leaves(state.opt_state)) == 1 + 2 * len(
        jax.tree.leaves(state.params))           # count, mu, nu: no bias
    step = jax.jit(step_lib.make_train_step(
        model.apply, tx, losses.hybrid_moe_lm,
        mutable_keys=(BIAS_COLLECTION,), accum_steps=accum_steps,
        guard_nonfinite=True))
    new, metrics = step(state, batch)
    assert float(metrics["skipped"]) == 0.0
    for name in COUNTERS:
        assert np.isfinite(float(metrics[name])), name
    assert float(metrics["router_bias_abs_max"]) <= GAMMA * (accum_steps - 1)
    moved = [np.asarray(b) for b in jax.tree.leaves(new.mutable)]
    assert len(moved) == n_bias == 2 and moved[0].shape == (2, 8)
    for b in moved:
        steps = b / GAMMA
        np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
        assert 0 < np.abs(steps).max() <= accum_steps + 1e-3
    # a step whose gradients are not finite keeps params, moments AND bias
    poisoned = new.replace(params=jax.tree.map(
        lambda p: p.at[(0,) * p.ndim].set(jnp.nan), new.params))
    kept, metrics = step(poisoned, batch)
    assert float(metrics["skipped"]) == 1.0
    for a, b in zip(jax.tree.leaves(kept.mutable),
                    jax.tree.leaves(new.mutable)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(kept.step) == 2


@pytest.mark.parametrize("ends, want, masked", [
    ((), 1.0, 0.4),                   # one document fills the window
    ((600, 1024, 1500), 0.6, 1.0),    # four: see the count below
], ids=["one_document", "four_documents"])
def test_the_walked_share_of_the_triangle_reaches_the_steps_metrics(
        ends, want, masked):
    """``attn_blocks_walked_share`` through ``losses.hybrid_moe_lm``: what the
    flash kernels' own predicate walks of the 10 blocks of 512 x 512 on or
    under the diagonal of a 2,048-window. Documents [0, 600) [600, 1024)
    [1024, 1500) [1500, 2048): the four query blocks hold documents {0},
    {0, 1}, {2, 3}, {3} and meet 1, 2, 1 and 2 key blocks, 6 of 10. And
    ``attn_blocks_masked_share``: of the walked, the blocks that need their
    mask: the diagonal's 4 of one document's 10; all 6 of the four documents'
    (4 on the diagonal, and (1, 0) and (3, 2) hold a document's edge)."""
    cfg = HybridDecoderConfig.tiny(layer_types=(CONV, ATTENTION),
                                   num_dense_layers=1)
    seg = np.searchsorted(np.asarray(ends), np.arange(2048), side="right")
    batch = {"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(1, 256, (1, 2048)), jnp.int32),
        "segment_ids": jnp.asarray(seg[None, :], jnp.int32)}
    model, params, mutable = _init(cfg, batch)
    out = jax.jit(model.apply)({"params": params, **mutable}, batch)
    metrics = losses.hybrid_moe_lm(out, batch)[1]
    assert set(COUNTERS) <= set(metrics)
    share = float(metrics["attn_blocks_walked_share"])
    assert 0.0 < share <= 1.0 and share == pytest.approx(want)
    # never under what attention requires of the window
    assert share >= float(metrics["attn_pairs_share"])
    assert float(metrics["attn_blocks_masked_share"]) == pytest.approx(masked)


def test_the_bias_is_saved_and_restored_with_the_state(tmp_path):
    from distributeddeeplearningspark_tpu.checkpoint import Checkpointer

    cfg = HybridDecoderConfig.tiny()
    batch = _batch(seq=64, rows=2)
    tx = optax.adam(1e-3)
    model, state = _train_state(cfg, batch, tx)
    step = jax.jit(step_lib.make_train_step(
        model.apply, tx, losses.hybrid_moe_lm,
        mutable_keys=(BIAS_COLLECTION,)))
    state, _ = step(state, batch)
    _, fresh = _train_state(cfg, batch, tx)
    with Checkpointer(tmp_path / "ckpt", async_save=False) as ckpt:
        ckpt.save(1, state)
        ckpt.wait()
        restored, _ = ckpt.restore(fresh)
    saved = jax.tree.leaves(state.mutable)
    assert max(float(jnp.abs(b).max()) for b in saved) > 0
    for a, b in zip(jax.tree.leaves(restored.mutable), saved):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_eight_shares_add_up_to_the_uncut_layer(reference):
    """Each rank holds 8 of 64 experts, routes over all 64 with the same
    bias and computes its own experts' part; the parts of the eight ranks
    (what every rank computes alike, the router, counted once) are the whole
    layer, in the program and in the reference."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 96, 32)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=64) * 0.1, jnp.float32)
    make = lambda held: RoutedExperts(
        32, 48, num_experts=64, top_k=4, held=held, dtype=jnp.float32,
        score="sigmoid", select_bias=True)
    whole = make(None).init(jax.random.PRNGKey(0), x)["params"]
    variables = lambda p: {"params": p, BIAS_COLLECTION: {"bias": bias}}
    want, stats = make(None).apply(variables(whole), x)
    assert float(stats["rows_held_share"]) == 1.0
    cfg = {"experts_held": [0, 64], "num_experts_per_tok": 4,
           "norm_topk_prob": True}
    np.testing.assert_allclose(
        np.asarray(reference.routed(x[0], whole, bias, cfg)),
        np.asarray(want[0]), atol=2e-5)
    total = jnp.zeros_like(want)
    total_ref = jnp.zeros_like(want[0])
    shares = 0.0
    for rank in range(8):
        held = (rank * 8, 8)
        mine = {"router": whole["router"], **{
            k: whole[k][held[0]:held[0] + 8]
            for k in ("w_gate", "w_up", "w_down")}}
        part, stats = make(held).apply(variables(mine), x)
        shares += float(stats["rows_held_share"])
        total = total + part
        total_ref = total_ref + reference.routed(x[0], mine, bias, cfg, held)
    assert shares == pytest.approx(1.0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total_ref), np.asarray(want[0]),
                               atol=2e-5)


def test_softmax_callers_get_the_layer_they_had():
    """``score="softmax"`` is the default, holds no collection but the
    parameters, and its stats are the three it had."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 32, 16)),
                    jnp.float32)
    layer = RoutedExperts(16, 32, num_experts=4, top_k=2, dtype=jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)
    assert set(variables) == {"params"}
    y, stats = layer.apply(variables, x)
    assert set(stats) == {"aux", "load_max_over_mean", "rows_held_share"}
    probs = jax.nn.softmax(x[0] @ variables["params"]["router"])
    gate, idx = jax.lax.top_k(probs, 2)
    gate = gate / gate.sum(-1, keepdims=True)
    p = variables["params"]
    want = sum(gate[:, j, None] * jnp.einsum(
        "ti,tih->th", jax.nn.silu(jnp.einsum("th,thi->ti", x[0],
                                              p["w_gate"][idx[:, j]]))
        * jnp.einsum("th,thi->ti", x[0], p["w_up"][idx[:, j]]),
        p["w_down"][idx[:, j]]) for j in range(2))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("partitions", [1, 3])
def test_token_windows_carry_the_document_of_every_position(tmp_path,
                                                            partitions):
    from distributeddeeplearningspark_tpu.data import records, text

    rng = np.random.default_rng(0)
    docs = [{"tokens": rng.integers(1, 100, rng.integers(3, 90))
             .astype(np.int32)} for _ in range(40)]
    records.write_array_records(iter(docs), str(tmp_path), num_shards=4)
    src = records.array_records(str(tmp_path))
    plain = text.packed_token_windows(src, seq_len=64, eos_id=0).collect()
    got = text.packed_token_windows(src, seq_len=64, eos_id=0,
                                    num_partitions=partitions,
                                    segment_ids=True).collect()
    assert len(got) == len(plain) > 10
    for ex, old in zip(got, plain):
        assert set(ex) == {"input_ids", "segment_ids"}
        assert ex["input_ids"].tobytes() == old["input_ids"].tobytes()
        seg = ex["segment_ids"]
        assert seg.dtype == np.int32 and seg.shape == (64,) and seg[0] == 0
        # a new document begins exactly after every EOS
        starts = np.flatnonzero(np.diff(seg)) + 1
        assert (np.diff(seg) >= 0).all() and (np.diff(seg) <= 1).all()
        np.testing.assert_array_equal(
            starts, np.flatnonzero(ex["input_ids"][:-1] == 0) + 1)
    one = text.packed_token_windows(src, seq_len=64, eos_id=0,
                                    segment_ids=True).collect()
    assert all(a["segment_ids"].tobytes() == b["segment_ids"].tobytes()
               for a, b in zip(got, one))


# -- blocks of ONE sublayer: Mamba-2, experts, attention without positions ----

@pytest.fixture(scope="module")
def ssm_reference():
    ref = _load("reference/nemotron3_nano_30b_a3b.py")
    # blocks small enough that a toy window has several of each kind
    ref.ROWS, ref.QUERIES, ref.STATES = 48, 32, 12
    return ref


def _ssm_reference_cfg(cfg: HybridDecoderConfig) -> dict:
    """What the reference reads of a configuration file, for ``cfg``."""
    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd

    letters = {hd.MAMBA: "M", hd.EXPERTS: "E", hd.BARE_ATTENTION: "*"}
    first, count = cfg.experts_held or (0, cfg.num_experts)
    return {"hybrid_override_pattern": "".join(
                letters[k] for k in cfg.layer_types),
            "layer_norm_epsilon": cfg.rms_eps,
            "mamba_num_heads": cfg.ssm_heads,
            "mamba_head_dim": cfg.ssm_head_dim, "n_groups": cfg.ssm_groups,
            "ssm_state_size": cfg.ssm_state_size,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "experts_held": [first, count],
            "num_experts_per_tok": cfg.experts_per_token,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "train_router": cfg.train_router,
            "router_width": cfg.num_experts, "check": {}}


@pytest.mark.parametrize("layout, train_router", [
    ("one_period", True), ("periods_and_a_tail", False)])
def test_the_ssm_preset_equals_its_reference_for_loss_and_every_leaf(
        ssm_reference, layout, train_router):
    """``tiny_ssm`` (the pattern ``M E M E M * E``: the chunked scan with its
    resets, the ungated convolution, the gated group norm, attention without
    positions, relu² experts beside a wider shared one, experts 2-5 of 8
    held) against the sequential recurrence of
    ``benchmark/reference/nemotron3_nano_30b_a3b.py``; and two scanned
    periods with a trailing layer. 100 positions fill no whole chunk of
    16."""
    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd

    kinds = {} if layout == "one_period" else {"layer_types": (
        hd.MAMBA, hd.EXPERTS, hd.BARE_ATTENTION) * 2 + (hd.MAMBA,)}
    cfg = HybridDecoderConfig.tiny_ssm(experts_held=(2, 4),
                                       train_router=train_router, **kinds)
    lead, period, whole, trail = cfg.layout()
    assert lead == () and (len(period), whole, len(trail)) == (
        (7, 1, 0) if layout == "one_period" else (3, 2, 1))
    batch = _batch(seq=100)
    model, params, mutable = _init(cfg, batch)
    rng = np.random.default_rng(5)     # off a zero bias and a unit D
    params = jax.tree.map(lambda a: a + jnp.asarray(
        0.05 * rng.normal(size=a.shape), a.dtype), params)
    got, got_grad = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, mutable, batch)))(params)
    want, want_grad = jax.jit(jax.value_and_grad(
        lambda p: ssm_reference.training_loss(
            p, mutable, batch, _ssm_reference_cfg(cfg))))(params)
    assert abs(float(got) - float(want)) < 2e-5
    errors = _leaf_errors(got_grad, want_grad)
    assert len(errors) > 30
    assert max(errors.values()) < 2e-3, max(errors.items(), key=lambda kv: kv[1])
    leaves = {k: float(jnp.abs(v).max()) for k, v in zip(
        errors, jax.tree.leaves(want_grad))}
    for name in ("A_log", "'D'", "dt_bias", "conv_taps", "conv_bias",
                 "in_proj", "out_proj", "mixer']['norm", "wq", "w_up",
                 "shared_down", "lm_head", "embedding"):
        assert any(name in k and v > 0 for k, v in leaves.items()), name
    routers = [v for k, v in leaves.items() if "router" in k]
    assert routers and all((v > 0) == train_router for v in routers)


def test_a_block_of_one_sublayer_has_one_norm_and_the_siblings_keep_theirs():
    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd
    import hashlib

    cfg = HybridDecoderConfig.tiny_ssm()
    batch = _batch(seq=64)
    shapes = jax.eval_shape(HybridDecoderLM(cfg).init, jax.random.PRNGKey(0),
                            batch)
    period = shapes["params"]["periods"]
    inside = {hd.MAMBA: "mixer", hd.EXPERTS: "moe",
              hd.BARE_ATTENTION: "self_attn"}
    for j, kind in enumerate(cfg.layer_types):
        assert set(period[f"layer_{j}"]) == {"norm", inside[kind]}, j
    assert set(period["layer_0"]["mixer"]) == {
        "in_proj", "conv_taps", "conv_bias", "A_log", "D", "dt_bias", "norm",
        "out_proj"}
    # inner 32 = 4 heads of 8; xBC 32 + 2 groups x 16 x 2 = 96; dt 4
    assert period["layer_0"]["mixer"]["in_proj"]["kernel"].shape == (
        1, 128, 32 + 96 + 4)
    assert period["layer_0"]["mixer"]["conv_taps"].shape == (1, 96, 4)
    assert set(period["layer_5"]["self_attn"]) == {"wq", "wk", "wv", "wo"}
    assert "w_gate" not in period["layer_1"]["moe"]
    # only expert blocks keep a bias
    assert set(shapes[BIAS_COLLECTION]["periods"]) == {
        "layer_1", "layer_3", "layer_6"}
    with pytest.raises(ValueError, match="one sublayer"):
        HybridDecoderConfig.tiny_ssm(num_dense_layers=1)

    # the two sibling presets and the published depth: every leaf's path,
    # shape and dtype as in the tree before this model came (digests taken
    # on the parent commit)
    def digest(cfg):
        shapes = jax.eval_shape(
            HybridDecoderLM(cfg).init, jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 64), jnp.int32)})
        lines = sorted(
            f"{jax.tree_util.keystr(p)} {a.shape} {a.dtype}"
            for p, a in jax.tree_util.tree_leaves_with_path(shapes))
        return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]

    assert digest(HybridDecoderConfig.tiny()) == "dd76e23b050a24f8"
    assert digest(HybridDecoderConfig.tiny_latent()) == "8e2a2fa29f88ec8c"
    assert digest(HybridDecoderConfig()) == "43047667391444dc"


def test_a_packed_row_equals_its_documents_through_scan_conv_and_attention():
    """The whole ``tiny_ssm`` model: the hidden states of a packed row are
    those of its documents run alone (the experts route a token by itself,
    so they mix nothing either); and the two state-space counters reach the
    step's metrics through the loss."""
    cfg = HybridDecoderConfig.tiny_ssm()
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(1, 256, (1, 64)), jnp.int32)
    cuts = (0, 21, 32, 64)               # inside a chunk of 16, on an edge
    seg = jnp.asarray(np.searchsorted(np.asarray(cuts[1:-1]), np.arange(64),
                                      side="right"), jnp.int32)[None]
    batch = {"input_ids": ids, "segment_ids": seg}
    model = HybridDecoderLM(cfg)
    variables = model.init(jax.random.PRNGKey(1), batch)
    out = model.apply(variables, batch)
    for lo, hi in zip(cuts, cuts[1:]):
        alone = model.apply(variables, {"input_ids": ids[:, lo:hi]})
        np.testing.assert_allclose(out["hidden"][:, lo:hi], alone["hidden"],
                                   rtol=2e-4, atol=2e-4)
    # 2 boundaries in 4 chunks of 16: positions 21 and 32
    assert float(out["ssm_chunks_reset_share"]) == pytest.approx(2 / 4)
    assert float(out["ssm_state_abs_max"]) > 0
    _, metrics = losses.hybrid_moe_lm(out, batch)
    assert {"ssm_chunks_reset_share", "ssm_state_abs_max"} <= set(metrics)
    # a model without state-space layers has neither
    plain = HybridDecoderLM(HybridDecoderConfig.tiny())
    plain_out = plain.apply(plain.init(jax.random.PRNGKey(0), batch), batch)
    assert "ssm_state_abs_max" not in plain_out
    assert set(COUNTERS) <= set(plain_out)
