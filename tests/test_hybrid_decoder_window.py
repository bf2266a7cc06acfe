"""The hybrid decoder's sliding-window kind
(``HybridDecoderConfig.tiny_window``, Laguna-XS.2's shape) at a toy size on
the CPU: the program against ``benchmark/reference/laguna_xs2.py`` for the
loss and every gradient leaf, and a packed row against its documents run
alone through the band and the full layers. The helpers are
``tests/test_hybrid_decoder.py``'s; a layer kind a file, so that the kinds
run side by side."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd
from distributeddeeplearningspark_tpu.models.hybrid_decoder import (
    HybridDecoderConfig, HybridDecoderLM)
from distributeddeeplearningspark_tpu.train import losses
from test_hybrid_decoder import _batch, _leaf_errors, _load, _program_loss


# -- sliding-window layers among full ones (Laguna-XS.2's shape) -------------

@pytest.fixture(scope="module")
def window_reference():
    ref = _load("reference/laguna_xs2.py")
    ref.ROWS, ref.QUERIES = 40, 24     # blocks that do not divide the row
    return ref


def _window_reference_cfg(cfg: HybridDecoderConfig) -> dict:
    """What ``benchmark/reference/laguna_xs2.py`` reads of a configuration
    file, for ``cfg``."""
    first, count = cfg.experts_held or (0, cfg.num_experts)
    kinds = {hd.ATTENTION: "full_attention", hd.SLIDING: "sliding_attention"}
    return {
        "layer_types": [kinds[k] for k in cfg.layer_types],
        "mlp_layer_types": ["dense" if i < cfg.num_dense_layers else "sparse"
                            for i in range(len(cfg.layer_types))],
        "num_attention_heads_per_layer": [
            cfg.sliding_num_heads if k == hd.SLIDING else cfg.num_heads
            for k in cfg.layer_types],
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.rms_eps, "sliding_window": cfg.sliding_window,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": cfg.yarn_factor,
                "original_max_position_embeddings":
                    cfg.yarn_original_max_position,
                "beta_fast": cfg.yarn_beta_fast,
                "beta_slow": cfg.yarn_beta_slow,
                "attention_factor": cfg.rope_attention_factor,
                "partial_rotary_factor": cfg.partial_rotary_factor},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": cfg.sliding_rope_theta,
                "partial_rotary_factor": 1.0}},
        "experts_held": [first, count], "router_width": cfg.num_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "moe_routed_scaling_factor": cfg.routed_scaling_factor,
        "train_router": cfg.train_router,
        "assumed_values": {"norm_topk_prob": cfg.norm_topk_prob,
                           "router_aux_loss_coef": cfg.router_aux_loss_coef},
        "check": {}}


@pytest.mark.parametrize("experts_held, train_router", [
    ((2, 4), True), (None, False)])
def test_the_window_preset_equals_its_reference_for_loss_and_every_leaf(
        window_reference, experts_held, train_router):
    """``tiny_window`` (a dense full-attention layer, then sliding, sliding,
    sliding, full with experts: the band of 16 at 8 query heads on 2 with
    theta 1e4, the full layers at 6 on 2 with YaRN on half the head and its
    factor, per-head gates, no q/k norm, a softmax router with Switch's
    term beside an ungated shared expert, x2.5) against
    ``benchmark/reference/laguna_xs2.py``: the loss and every leaf's
    gradient, documents inside the band's reach."""
    cfg = HybridDecoderConfig.tiny_window(experts_held=experts_held,
                                          train_router=train_router)
    assert cfg.layout()[1:3] == (cfg.layer_types[1:], 1)
    batch = _batch(seq=100)
    model = HybridDecoderLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), batch)
    assert set(variables) == {"params"}              # no bias collection
    rng = np.random.default_rng(5)
    params = jax.tree.map(lambda a: a + jnp.asarray(
        0.05 * rng.normal(size=a.shape), a.dtype), variables["params"])
    got, got_grad = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, {}, batch)))(params)
    want, want_grad = jax.jit(jax.value_and_grad(
        lambda p: window_reference.training_loss(
            p, batch, _window_reference_cfg(cfg))))(params)
    assert abs(float(got) - float(want)) < 2e-5
    errors = _leaf_errors(got_grad, want_grad)
    assert len(errors) > 50
    assert max(errors.values()) < 2e-3, max(errors.items(), key=lambda kv: kv[1])
    leaves = {k: float(jnp.abs(v).max()) for k, v in zip(
        errors, jax.tree.leaves(want_grad))}
    for name in ("gate", "wq", "wk", "wo", "w_gate", "shared_gate", "mlp",
                 "lm_head", "embedding"):
        assert any(name in k and v > 0 for k, v in leaves.items()), name
    # Switch's term reaches the router whether its weights carry the
    # routing's gradient (train_router) or not
    routers = [v for k, v in leaves.items() if "router" in k]
    assert len(routers) == 4 and all(v > 0 for v in routers)


def test_a_packed_row_equals_its_documents_through_the_band_and_full_layers():
    """The whole ``tiny_window`` model: the hidden states of a packed row
    are those of its documents run alone; the band's two counters reach the
    step's metrics through the loss with the Switch term; a model without
    sliding layers has neither."""
    cfg = HybridDecoderConfig.tiny_window()
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(1, 256, (1, 64)), jnp.int32)
    cuts = (0, 21, 32, 64)               # inside the band of 16, on an edge
    seg = jnp.asarray(np.searchsorted(np.asarray(cuts[1:-1]), np.arange(64),
                                      side="right"), jnp.int32)[None]
    batch = {"input_ids": ids, "segment_ids": seg}
    model = HybridDecoderLM(cfg)
    variables = jax.jit(model.init)(jax.random.PRNGKey(1), batch)
    apply = jax.jit(model.apply)
    out = apply(variables, batch)
    for lo, hi in zip(cuts, cuts[1:]):
        alone = apply(variables, {"input_ids": ids[:, lo:hi]})
        np.testing.assert_allclose(out["hidden"][:, lo:hi], alone["hidden"],
                                   rtol=2e-4, atol=2e-4)
    # in-document keys inside the band, over S x 16: documents of 21, 11, 32
    want = sum(sum(min(t + 1, 16) for t in range(n)) for n in (21, 11, 32))
    assert float(out["window_pairs_share"]) == pytest.approx(want / (64 * 16))
    assert float(out["window_blocks_walked_share"]) == 1.0  # one block of 64
    loss, metrics = losses.hybrid_moe_lm(out, batch)
    assert set(hd.WINDOW_COUNTERS) <= set(metrics)
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics["lm_loss"]) + float(metrics["moe_aux"]))
    # 4 expert layers' Switch terms, each near 1, at 0.001
    assert 0.003 < float(metrics["moe_aux"]) < 0.008
    plain = HybridDecoderLM(HybridDecoderConfig.tiny())
    plain_out = jax.jit(plain.apply)(
        jax.jit(plain.init)(jax.random.PRNGKey(0), batch), batch)
    assert not set(hd.WINDOW_COUNTERS) & set(plain_out)
    assert "moe_aux" not in plain_out
    with pytest.raises(ValueError, match="sliding_window"):
        HybridDecoderConfig.tiny_window(sliding_window=None)
    with pytest.raises(ValueError, match="bias"):
        HybridDecoderConfig.tiny_window(use_expert_bias=True)
