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
