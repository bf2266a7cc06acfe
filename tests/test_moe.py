"""Routed experts (models/moe.py) and expert parallelism.

The layer must be a faithful router: every token's output is the convex
combination of its chosen experts' FFN outputs, whatever the imbalance (no
capacity, nothing dropped); E=1 reduces to a plain SwiGLU; the same layer
split over a mesh's ``expert`` (and ``tensor``, and token) axes gives the
one-device result, forward and backward; and Llama trains with it under a
data × expert mesh with the stacked expert kernels genuinely sharded."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributeddeeplearningspark_tpu.models import LlamaConfig, LlamaForCausalLM
from distributeddeeplearningspark_tpu.models.moe import RoutedExperts
from distributeddeeplearningspark_tpu.ops import ring_attention
from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
from distributeddeeplearningspark_tpu.train import losses, step as step_lib


def _x(b=2, s=8, h=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0, 1, (b, s, h)).astype(np.float32))


def _dense_experts(x, p, top_k):
    """Every expert on every token, weighted by the renormalised top-k
    gates: the layer's definition, in numpy."""
    x = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = x @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, axis=-1, kind="stable")[:, :top_k]
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        gates = probs[t, top[t]] / probs[t, top[t]].sum()
        for g, e in zip(gates, top[t]):
            a = x[t] @ np.asarray(p["w_gate"][e], np.float64)
            u = x[t] @ np.asarray(p["w_up"][e], np.float64)
            y[t] += g * ((a / (1 + np.exp(-a)) * u)
                         @ np.asarray(p["w_down"][e], np.float64))
    return y


class TestRoutedExperts:
    def test_shapes_and_finite(self):
        x = _x()
        m = RoutedExperts(16, 32, num_experts=4, top_k=2, dtype=jnp.float32)
        v = m.init(jax.random.PRNGKey(0), x)
        y, stats = m.apply(v, x)
        assert y.shape == x.shape and y.dtype == x.dtype
        assert np.isfinite(np.asarray(y)).all()
        assert np.isfinite(float(stats["aux"])) and float(stats["aux"]) > 0
        assert float(stats["rows_held_share"]) == 1.0   # it holds them all
        assert float(stats["load_max_over_mean"]) >= 1.0

    def test_single_expert_matches_dense_swiglu(self):
        """E=1, top_k=1: routing is the identity, so the output must equal
        the plain SwiGLU with the same kernels."""
        x = _x(seed=1)
        m = RoutedExperts(16, 32, num_experts=1, top_k=1, dtype=jnp.float32)
        v = m.init(jax.random.PRNGKey(1), x)
        y, stats = m.apply(v, x)
        p = v["params"]
        g = np.asarray(x) @ np.asarray(p["w_gate"][0])
        u = np.asarray(x) @ np.asarray(p["w_up"][0])
        silu = g * (1 / (1 + np.exp(-g)))
        want = (silu * u) @ np.asarray(p["w_down"][0])
        np.testing.assert_allclose(np.asarray(y), want, atol=1e-4, rtol=1e-4)
        # single expert: perfectly "balanced" → aux = E · 1 · 1 = 1
        assert abs(float(stats["aux"]) - 1.0) < 1e-5

    @pytest.mark.parametrize("top_k", [1, 2, 4])
    def test_output_is_the_convex_combination_of_the_chosen_experts(
            self, top_k):
        x = _x(b=2, s=16, seed=2)
        m = RoutedExperts(16, 32, num_experts=4, top_k=top_k,
                          dtype=jnp.float32)
        v = m.init(jax.random.PRNGKey(2), x)
        y, _ = m.apply(v, x)
        np.testing.assert_allclose(
            np.asarray(y).reshape(-1, 16),
            _dense_experts(x, v["params"], top_k), atol=1e-5)

    def test_nothing_is_dropped_at_any_imbalance(self):
        """A router that sends EVERY token to expert 0: a capacity layer
        would drop all but a few; here every token gets expert 0's FFN."""
        x = jnp.abs(_x(b=1, s=32, seed=3))   # positive: column 0 then wins
        m = RoutedExperts(16, 32, num_experts=4, top_k=1, dtype=jnp.float32)
        p = dict(m.init(jax.random.PRNGKey(3), x)["params"])
        p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(100.0)
        y, stats = m.apply({"params": p}, x)
        assert float(stats["load_max_over_mean"]) == pytest.approx(4.0)
        np.testing.assert_allclose(
            np.asarray(y).reshape(-1, 16), _dense_experts(x, p, 1), atol=1e-5)
        assert (np.abs(np.asarray(y)).max(axis=-1) > 0).all()

    def test_top_k_bounds_checked(self):
        with pytest.raises(ValueError, match="top_k"):
            RoutedExperts(16, 32, num_experts=2, top_k=3).init(
                jax.random.PRNGKey(0), _x())

    def test_each_expert_is_initialised_by_its_own_fan_in(self):
        x = _x(h=64)
        m = RoutedExperts(64, 256, num_experts=16, top_k=2)
        p = m.init(jax.random.PRNGKey(0), x)["params"]
        assert float(p["w_gate"].std()) == pytest.approx(64 ** -0.5, rel=0.05)
        assert float(p["w_down"].std()) == pytest.approx(256 ** -0.5, rel=0.05)

    @pytest.mark.parametrize("axes", [
        dict(expert=4), dict(data=2, expert=2), dict(expert=2, tensor=2),
        dict(data=2, seq=2, expert=2)])
    def test_split_over_a_mesh_is_the_one_device_layer(self, eight_devices,
                                                       axes):
        """Output, statistics and every gradient, with the experts' kernels
        over ``expert``, their width over ``tensor`` and the tokens over
        data and seq: a ``psum`` of the ranks' parts is the whole layer."""
        x = _x(b=4, s=8, seed=4)
        m = RoutedExperts(16, 32, num_experts=4, top_k=2, dtype=jnp.float32)
        v = m.init(jax.random.PRNGKey(4), x)
        cot = _x(b=4, s=8, seed=5)

        def f(params, x):
            y, stats = m.apply({"params": params}, x)
            return jnp.sum(y * cot) + 3.0 * stats["aux"], (y, stats)

        want = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(
            v["params"], x)
        size = int(np.prod(list(axes.values())))
        mesh = MeshSpec(**axes).build(eight_devices[:size])
        ring_attention.set_default_mesh(mesh)
        try:
            with mesh:
                got = jax.jit(jax.value_and_grad(
                    f, argnums=(0, 1), has_aux=True))(v["params"], x)
        finally:
            ring_attention.set_default_mesh(None)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("scale", [1.0, 2.5])
    def test_a_shared_expert_beside_the_routed_ones_and_their_factor(
            self, scale):
        """``shared_size``: a SwiGLU every token passes, added to the routed
        part; ``routed_scale``: the routed part's weights times it. Against
        the layer's definition in numpy; the routed part alone is what the
        layer sows where "intermediates" is asked for."""
        x = _x(b=2, s=16, seed=6)
        m = RoutedExperts(16, 32, num_experts=4, top_k=2, dtype=jnp.float32,
                          routed_scale=scale, shared_size=24)
        v = m.init(jax.random.PRNGKey(6), x)
        p = v["params"]
        assert set(p) == {"router", "w_gate", "w_up", "w_down", "shared_gate",
                          "shared_up", "shared_down"}
        assert p["shared_gate"]["kernel"].shape == (16, 24)
        (y, _), seen = m.apply(v, x, mutable=["intermediates"])
        xf = np.asarray(x, np.float64).reshape(-1, 16)
        a = xf @ np.asarray(p["shared_gate"]["kernel"], np.float64)
        shared = (a / (1 + np.exp(-a)) * (
            xf @ np.asarray(p["shared_up"]["kernel"], np.float64))
        ) @ np.asarray(p["shared_down"]["kernel"], np.float64)
        routed = scale * _dense_experts(x, p, 2)
        np.testing.assert_allclose(
            np.asarray(seen["intermediates"]["routed"][0]).reshape(-1, 16),
            routed, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y).reshape(-1, 16),
                                   routed + shared, atol=1e-5)
        assert set(m.apply(v, x)[1]) == {"aux", "load_max_over_mean",
                                         "rows_held_share"}

    def test_the_shared_expert_is_whole_on_every_rank_of_a_mesh(
            self, eight_devices):
        """Over ``data=2 x expert=2 x tensor=2`` the routed part is the
        ``psum`` of the ranks' parts and the shared expert is added ONCE,
        outside the ``shard_map``: the one-device layer, forward and
        backward."""
        x = _x(b=4, s=8, seed=7)
        m = RoutedExperts(16, 32, num_experts=4, top_k=2, dtype=jnp.float32,
                          score="sigmoid", select_bias=True, routed_scale=2.5,
                          shared_size=16)
        v = m.init(jax.random.PRNGKey(7), x)
        cot = _x(b=4, s=8, seed=8)

        def f(params, x):
            y, stats = m.apply({**v, "params": params}, x)
            return jnp.sum(y * cot), (y, stats)

        want = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(
            v["params"], x)
        mesh = MeshSpec(data=2, expert=2, tensor=2).build(eight_devices)
        ring_attention.set_default_mesh(mesh)
        try:
            with mesh:
                got = jax.jit(jax.value_and_grad(
                    f, argnums=(0, 1), has_aux=True))(v["params"], x)
        finally:
            ring_attention.set_default_mesh(None)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    def test_a_mesh_the_experts_do_not_divide_by_is_refused(
            self, eight_devices):
        x = _x(b=4, s=8)
        m = RoutedExperts(16, 32, num_experts=3, top_k=1, dtype=jnp.float32)
        v = m.init(jax.random.PRNGKey(0), x)
        mesh = MeshSpec(expert=2).build(eight_devices[:2])
        ring_attention.set_default_mesh(mesh)
        try:
            with pytest.raises(ValueError, match="experts held by expert"):
                m.apply(v, x)
        finally:
            ring_attention.set_default_mesh(None)


class TestMoELlama:
    def _cfg(self, **kw):
        return LlamaConfig.tiny(moe_experts=4, moe_top_k=2,
                                intermediate_size=64, **kw)

    def test_forward_reports_aux(self):
        cfg = self._cfg()
        model = LlamaForCausalLM(cfg)
        batch = {"input_ids": np.ones((2, 16), np.int32)}
        v = model.init(jax.random.PRNGKey(0), batch, train=False)
        out = model.apply(v, batch, train=True)
        assert isinstance(out, dict) and "moe_aux" in out
        assert "moe_dropped_frac" not in out   # nothing is dropped
        assert out["logits"].shape == (2, 16, cfg.vocab_size)
        loss, metrics = losses.causal_lm(
            out, {"input_ids": batch["input_ids"],
                  "loss_mask": np.ones((2, 16), np.float32)})
        assert "moe_aux" in metrics and np.isfinite(float(loss))

    def test_trains_on_data_expert_mesh(self, eight_devices):
        """Full train step over data=2 × expert=4: expert kernels sharded,
        loss (incl. aux) finite, params move."""
        from distributeddeeplearningspark_tpu.data.feed import (
            put_global, stack_examples)
        from distributeddeeplearningspark_tpu.models import llama_rules

        mesh = MeshSpec(data=2, expert=4).build(eight_devices)
        cfg = self._cfg()
        model = LlamaForCausalLM(cfg)
        rules = llama_rules(cfg, fsdp_min_size=1)
        batch = stack_examples([
            {"input_ids": np.full((16,), i % cfg.vocab_size, np.int32),
             "loss_mask": np.ones((16,), np.float32)}
            for i in range(4)])
        tx = optax.adamw(1e-3)
        state, shardings = step_lib.init_state(model, tx, batch, mesh, rules)
        wg = shardings.params["layers"]["moe"]["w_gate"]
        assert "expert" in str(wg.spec), wg
        step = step_lib.jit_train_step(
            step_lib.make_train_step(model.apply, tx, losses.causal_lm),
            mesh, shardings)
        before = jax.device_get(
            jax.tree_util.tree_leaves(state.params)[0])
        state, metrics = step(state, put_global(batch, mesh))
        assert np.isfinite(float(jax.device_get(metrics["loss"])))
        assert np.isfinite(float(jax.device_get(metrics["moe_aux"])))
        after = jax.device_get(jax.tree_util.tree_leaves(state.params)[0])
        assert not np.allclose(before, after)

    def test_moe_composes_with_fused_head(self):
        cfg = self._cfg(fused_head_loss=True)
        model = LlamaForCausalLM(cfg)
        batch = {"input_ids": np.ones((2, 16), np.int32),
                 "loss_mask": np.ones((2, 16), np.float32)}
        v = model.init(jax.random.PRNGKey(0), batch, train=False)
        out = model.apply(v, batch, train=True)
        assert {"hidden", "lm_head", "moe_aux"} <= set(out)
        loss, metrics = losses.causal_lm_fused(out, batch)
        assert "moe_aux" in metrics and np.isfinite(float(loss))

    def test_moe_loss_decreases(self, eight_devices):
        """Training signal end-to-end: repeated-token corpus, loss drops."""
        mesh = MeshSpec(data=2, expert=4).build(eight_devices)
        from distributeddeeplearningspark_tpu.data.feed import (
            put_global, stack_examples)
        from distributeddeeplearningspark_tpu.models import llama_rules

        cfg = self._cfg()
        model = LlamaForCausalLM(cfg)
        batch = stack_examples([
            {"input_ids": (np.arange(16, dtype=np.int32) * (i + 1))
             % cfg.vocab_size,
             "loss_mask": np.ones((16,), np.float32)}
            for i in range(4)])
        tx = optax.adamw(3e-3)
        state, shardings = step_lib.init_state(
            model, tx, batch, mesh, llama_rules(cfg, fsdp_min_size=1))
        step = step_lib.jit_train_step(
            step_lib.make_train_step(model.apply, tx, losses.causal_lm),
            mesh, shardings)
        gbatch = put_global(batch, mesh)
        first = last = None
        for _ in range(30):
            state, metrics = step(state, gbatch)
            loss = float(jax.device_get(metrics["loss"]))
            first = loss if first is None else first
            last = loss
        assert last < first * 0.7, (first, last)


def test_predict_and_eval_get_plain_logits():
    """train=False must return a bare logits array — Trainer.predict row
    indexing and argmax output_fns cannot take the aux dict."""
    cfg = LlamaConfig.tiny(moe_experts=2, intermediate_size=64)
    model = LlamaForCausalLM(cfg)
    batch = {"input_ids": np.ones((2, 16), np.int32)}
    v = model.init(jax.random.PRNGKey(0), batch, train=False)
    out = model.apply(v, batch, train=False)
    assert not isinstance(out, dict)
    assert out.shape == (2, 16, cfg.vocab_size)


def test_moe_with_pipeline_rejected(eight_devices):
    """PP's stage forward discards the aux loss — must refuse, not silently
    train a collapsing router."""
    from distributeddeeplearningspark_tpu.models.llama_pp import make_pp_apply

    mesh = MeshSpec(data=4, pipe=2).build(eight_devices)
    cfg = LlamaConfig.tiny(moe_experts=2, intermediate_size=64)
    with pytest.raises(NotImplementedError, match="MoE"):
        make_pp_apply(cfg, mesh, 2)


# -- two-matrix relu² experts (expert_form="relu2") ---------------------------

def _relu2_layer(held=None, shared=24, **kw):
    return RoutedExperts(16, 12, num_experts=16, top_k=3, held=held,
                         dtype=jnp.float32, score="sigmoid", select_bias=True,
                         routed_scale=2.5, shared_size=shared,
                         expert_form="relu2", **kw)


def _relu2_loop(x, p, bias, held, k=3, scale=2.5):
    """A loop over the experts held: sigmoid scores, the top-k of score +
    bias, weights ``scale * s_e / sum of the chosen s``, every expert
    ``down(relu(up x)^2)``; the shared expert beside them. In numpy."""
    x = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    s = 1 / (1 + np.exp(-(x @ np.asarray(p["router"], np.float64))))
    top = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1,
                     kind="stable")[:, :k]
    first, count = held
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e in top[t]:
            if first <= e < first + count:
                up = x[t] @ np.asarray(p["w_up"][e - first], np.float64)
                y[t] += (scale * s[t, e] / (s[t, top[t]].sum() + 1e-6)
                         * (np.maximum(up, 0) ** 2)
                         @ np.asarray(p["w_down"][e - first], np.float64))
    shared = np.maximum(
        x @ np.asarray(p["shared_up"]["kernel"], np.float64), 0) ** 2 \
        @ np.asarray(p["shared_down"]["kernel"], np.float64)
    return y, shared


def test_relu2_experts_are_a_loop_over_experts_of_two_matrices():
    from distributeddeeplearningspark_tpu.models.moe import BIAS_COLLECTION

    x = _x(2, 24, 16, seed=3)
    layer = _relu2_layer()
    variables = layer.init(jax.random.PRNGKey(0), x)
    params = variables["params"]
    assert set(params) == {"router", "w_up", "w_down", "shared_up",
                           "shared_down"}                # no gate anywhere
    assert params["w_up"].shape == (16, 16, 12)
    assert params["shared_up"]["kernel"].shape == (16, 24)
    bias = jnp.asarray(np.random.default_rng(1).normal(size=16) * 0.2,
                       jnp.float32)
    variables = {"params": params, BIAS_COLLECTION: {"bias": bias}}
    y, _ = layer.apply(variables, x)
    routed, shared = _relu2_loop(x, params, bias, (0, 16))
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 16),
                               routed + shared, rtol=2e-4, atol=2e-5)
    # every leaf gets a gradient, and it is finite
    grads = jax.grad(lambda p: jnp.sum(layer.apply(
        {**variables, "params": p}, x)[0] ** 2))(params)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.linalg.norm(g)) > 0, jax.tree_util.keystr(path)
        assert bool(jnp.all(jnp.isfinite(g))), jax.tree_util.keystr(path)
    with pytest.raises(ValueError, match="expert_form"):
        RoutedExperts(16, 12, num_experts=4, top_k=2,
                      expert_form="gelu").init(jax.random.PRNGKey(0), x)


def test_the_sixteen_shares_add_up_to_the_uncut_relu2_layer():
    """Every rank holds 1 of 16 experts, routes over all 16 with the same
    bias and computes its expert's part and, alike on every rank, the shared
    expert: the routed parts of the 16 ranks and the shared expert counted
    ONCE are the uncut layer."""
    from distributeddeeplearningspark_tpu.models.moe import BIAS_COLLECTION

    x = _x(1, 40, 16, seed=5)
    whole = _relu2_layer().init(jax.random.PRNGKey(2), x)["params"]
    bias = jnp.asarray(np.random.default_rng(6).normal(size=16) * 0.2,
                       jnp.float32)
    variables = lambda p: {"params": p, BIAS_COLLECTION: {"bias": bias}}
    want, stats = _relu2_layer().apply(variables(whole), x)
    assert float(stats["rows_held_share"]) == 1.0
    total, shares = jnp.zeros_like(want), 0.0
    for rank in range(16):
        mine = {**whole, "w_up": whole["w_up"][rank:rank + 1],
                "w_down": whole["w_down"][rank:rank + 1]}
        (part, stats), seen = _relu2_layer(held=(rank, 1)).apply(
            variables(mine), x, mutable=["intermediates"])
        shares += float(stats["rows_held_share"])
        (routed,) = seen["intermediates"]["routed"]
        total = total + routed
        if rank == 0:
            shared = part - routed          # what every rank computes alike
            np.testing.assert_allclose(
                np.asarray(routed).reshape(-1, 16),
                _relu2_loop(x, mine, bias, (0, 1))[0], rtol=2e-4, atol=2e-5)
    assert shares == pytest.approx(1.0)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-4, atol=2e-5)
    # counted on every rank it would be there sixteen times
    assert float(jnp.max(jnp.abs(shared))) > 1e-3


def test_relu2_experts_split_over_an_expert_mesh_give_the_one_device_layer(
        eight_devices):
    """The ``shard_map`` path without a gate kernel: data=2 x expert=4."""
    x = _x(4, 8, 16, seed=7)
    layer = RoutedExperts(16, 12, num_experts=8, top_k=2, dtype=jnp.float32,
                          expert_form="relu2")
    variables = layer.init(jax.random.PRNGKey(0), x)
    want, _ = layer.apply(variables, x)
    mesh = MeshSpec(data=2, expert=4).build(eight_devices)
    ring_attention.set_default_mesh(mesh)
    try:
        got, _ = jax.jit(lambda v, x: layer.apply(v, x))(variables, x)
        grads = jax.jit(jax.grad(lambda v, x: jnp.sum(
            layer.apply(v, x)[0] ** 2)))(variables, x)
    finally:
        ring_attention.set_default_mesh(None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    wants = jax.grad(lambda v, x: jnp.sum(layer.apply(v, x)[0] ** 2))(
        variables, x)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(wants)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)
