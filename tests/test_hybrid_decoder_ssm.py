"""The hybrid decoder's Mamba-2 kind (``HybridDecoderConfig.tiny_ssm``) at a
toy size on the CPU: the program against the sequential recurrence of
``benchmark/reference/nemotron3_nano_30b_a3b.py`` for the loss and every
gradient leaf, the blocks of one sublayer, and a packed row against its
documents run alone. The helpers are ``tests/test_hybrid_decoder.py``'s; a
layer kind a file, so that the kinds run side by side."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd
from distributeddeeplearningspark_tpu.models.hybrid_decoder import (
    COUNTERS, HybridDecoderConfig, HybridDecoderLM)
from distributeddeeplearningspark_tpu.models.moe import BIAS_COLLECTION
from distributeddeeplearningspark_tpu.train import losses
from test_hybrid_decoder import (
    _batch, _init, _leaf_errors, _load, _program_loss)


# -- blocks of ONE sublayer: Mamba-2, experts, attention without positions ----

@pytest.fixture(scope="module")
def ssm_reference():
    ref = _load("reference/nemotron3_nano_30b_a3b.py")
    # blocks small enough that a toy window has several of each kind
    ref.ROWS, ref.QUERIES, ref.STATES = 48, 32, 12
    return ref


def _ssm_reference_cfg(cfg: HybridDecoderConfig) -> dict:
    """What the reference reads of a configuration file, for ``cfg``."""
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
    # (taken on the parent of the sliding-window layers)
    assert digest(HybridDecoderConfig.tiny_ssm()) == "0845a5a91455910b"


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
    variables = jax.jit(model.init)(jax.random.PRNGKey(1), batch)
    apply = jax.jit(model.apply)
    out = apply(variables, batch)
    for lo, hi in zip(cuts, cuts[1:]):
        alone = apply(variables, {"input_ids": ids[:, lo:hi]})
        np.testing.assert_allclose(out["hidden"][:, lo:hi], alone["hidden"],
                                   rtol=2e-4, atol=2e-4)
    # 2 boundaries in 4 chunks of 16: positions 21 and 32
    assert float(out["ssm_chunks_reset_share"]) == pytest.approx(2 / 4)
    assert float(out["ssm_state_abs_max"]) > 0
    _, metrics = losses.hybrid_moe_lm(out, batch)
    assert {"ssm_chunks_reset_share", "ssm_state_abs_max"} <= set(metrics)
    # a model without state-space layers has neither
    plain = HybridDecoderLM(HybridDecoderConfig.tiny())
    plain_out = jax.jit(plain.apply)(
        jax.jit(plain.init)(jax.random.PRNGKey(0), batch), batch)
    assert "ssm_state_abs_max" not in plain_out
    assert set(COUNTERS) <= set(plain_out)
