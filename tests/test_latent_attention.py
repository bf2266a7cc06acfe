"""The latent-attention decoder (``models/hybrid_decoder.py`` with
``"latent_attention"`` layers, a shared expert, a scaled router, an untied
head and the multi-token-prediction module) at a toy size on the CPU: the
layer against the benchmark's plain reference
(``benchmark/reference/joyai_llm_flash.py``), on its XLA path and with the
flash kernels interpreted at two head sizes, alone and inside the mesh's
``shard_map``s; the adjacent-pair rotary embedding; the 32 ranks' shares
against the uncut layer with the shared expert counted once; the module run
over S rows against the exact S - 1 form; the step's counters.
``tests/test_latent_moe_benchmark.py`` holds the whole model's loss and every
gradient leaf against the reference, through the configuration's file."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distributeddeeplearningspark_tpu.models import hybrid_decoder  # noqa: E402
from distributeddeeplearningspark_tpu.models.hybrid_decoder import (  # noqa: E402
    ATTENTION, LATENT, HybridDecoderConfig, HybridDecoderLM, LatentAttention,
    MTPModule)
from distributeddeeplearningspark_tpu.models.llama import (  # noqa: E402
    rotary_embedding)
from distributeddeeplearningspark_tpu.models.moe import (  # noqa: E402
    BIAS_COLLECTION, RoutedExperts)
from distributeddeeplearningspark_tpu.train import losses  # noqa: E402


@pytest.fixture(scope="module")
def reference():
    from benchmark.harness import runner

    ref = runner.load_module(os.path.join(
        ROOT, "benchmark", "reference", "joyai_llm_flash.py"))
    ref.ROWS, ref.QUERIES = 48, 32   # several blocks of each in a toy window
    return ref


def _reference_cfg(cfg: HybridDecoderConfig) -> dict:
    """What the reference reads of a configuration file, for ``cfg``."""
    first, count = cfg.experts_held or (0, cfg.num_experts)
    return {"rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "num_attention_heads": cfg.num_heads,
            "experts_held": [first, count],
            "num_experts_per_tok": cfg.experts_per_token,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "train_router": cfg.train_router,
            "router_width": cfg.num_experts}


def _ids(rows=2, seq=128, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, 256, (rows, seq)), jnp.int32)


def test_adjacent_pairs_rotate_as_complex_numbers_and_half_split_is_untouched():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 16, 3, 8)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 1000, (2, 16)), jnp.int32)
    got = rotary_embedding(x, pos, 32e6, interleaved=True)
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    angle = np.asarray(pos)[..., None, None] * (
        32e6 ** (-np.arange(0, 8, 2) / 8))
    want = z * np.exp(1j * angle)
    np.testing.assert_allclose(np.asarray(got[..., 0::2]), want.real,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[..., 1::2]), want.imag,
                               atol=1e-4)
    # the same rotation as the half-split form, in another layout: scores
    # between two vectors rotated alike agree
    half = lambda t: jnp.concatenate([t[..., 0::2], t[..., 1::2]], -1)
    np.testing.assert_allclose(
        np.asarray(half(got)),
        np.asarray(rotary_embedding(half(x), pos, 32e6)), atol=1e-5)
    # the other callers' form is what it was: no keyword, rotate-half
    x1, x2 = np.split(np.asarray(x), 2, axis=-1)
    inv = 1e4 ** (-np.arange(0, 8, 2) / 8)
    ang = np.asarray(pos)[..., None, None] * inv
    np.testing.assert_allclose(
        np.asarray(rotary_embedding(x, pos, 1e4)),
        np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                        x2 * np.cos(ang) + x1 * np.sin(ang)], -1), atol=1e-4)


@pytest.mark.parametrize("packed", [False, True], ids=["window", "documents"])
def test_the_layer_is_the_references_on_both_paths(reference, monkeypatch,
                                                   packed):
    """``LatentAttention`` against the reference's ``latent_attention`` (its
    input already normed: a unit norm scale), output and every leaf's
    gradient: on the XLA path, and with the three flash kernels interpreted
    at the two head sizes 24 / 16 over two blocks of 512."""
    from distributeddeeplearningspark_tpu.ops import attention

    cfg = HybridDecoderConfig.tiny_latent(dtype=jnp.float32)
    rng = np.random.default_rng(1)
    s = 1024
    x = jnp.asarray(rng.normal(size=(1, s, cfg.hidden_size)), jnp.float32)
    seg = np.zeros((1, s), np.int32)
    if packed:
        for at in (100, 512, 700):
            seg[0, at:] += 1
    seg = jnp.asarray(seg)
    pos = hybrid_decoder.document_positions(seg)
    layer = LatentAttention(cfg, packed)
    params = layer.init(jax.random.PRNGKey(0), x, seg, pos)["params"]
    w = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

    def program(p, x):
        return jnp.sum(layer.apply({"params": p}, x, seg, pos) * w)

    def plain(p, x):
        # the reference norms its input itself: undo nothing, scale by the
        # rows' own RMS so that its norm is the identity
        rms = jnp.sqrt(jnp.mean(x[0] ** 2, -1, keepdims=True) + cfg.rms_eps)
        return jnp.sum(reference.latent_attention(
            x[0], p, rms, seg[0], pos[0], _reference_cfg(cfg))[0] * w[0])

    want, want_grad = jax.value_and_grad(plain, argnums=(0, 1))(params, x)
    for on_tpu in (False, True):
        monkeypatch.setattr(attention, "on_tpu", lambda: on_tpu)
        got, got_grad = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1)))(params, x)
        assert float(got) == pytest.approx(float(want), rel=1e-4, abs=1e-3)
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(got_grad),
                jax.tree.leaves(want_grad)):
            err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            assert err < 2e-4, (on_tpu, jax.tree_util.keystr(path), err)


def test_scores_are_scaled_by_the_whole_query_width_and_the_key_is_shared():
    """What the layer hands the attention call: q and k ``nope + rot`` wide,
    v narrower, no scale of its own (so ``d_qk ** -0.5``), the rotary part of
    k the same in every head, the position-free part not rotated."""
    cfg = HybridDecoderConfig.tiny_latent(dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(1, 64, cfg.hidden_size)), jnp.float32)
    seg = jnp.zeros((1, 64), jnp.int32)
    pos = hybrid_decoder.document_positions(seg)
    layer = LatentAttention(cfg, False)
    params = layer.init(jax.random.PRNGKey(0), x, seg, pos)["params"]
    seen = {}

    def spy(q, k, v, **kw):
        seen.update(q=q, k=k, v=v, kw=kw)
        return jnp.zeros((*q.shape[:3], v.shape[-1]), q.dtype)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(hybrid_decoder, "dot_product_attention", spy)
        layer.apply({"params": params}, x, seg, pos)
        shifted = dict(seen)
        layer.apply({"params": params}, x, seg, pos + 7)
    assert seen["q"].shape == seen["k"].shape == (1, 64, 4, 24)
    assert seen["v"].shape == (1, 64, 4, 16)
    assert seen["kw"] == {"causal": True, "segment_ids": None}
    k = np.asarray(seen["k"])
    for head in range(1, 4):
        np.testing.assert_array_equal(k[:, :, head, 16:], k[:, :, 0, 16:])
        assert np.abs(k[:, :, head, :16] - k[:, :, 0, :16]).max() > 0.1
    # other positions move the rotary parts and leave the rest alone
    for name in ("q", "k"):
        a, b = np.asarray(shifted[name]), np.asarray(seen[name])
        np.testing.assert_array_equal(a[..., :16], b[..., :16])
        assert np.abs(a[..., 16:] - b[..., 16:]).max() > 0.1


def test_the_32_shares_and_one_shared_expert_add_up_to_the_uncut_layer(
        reference):
    """Each rank holds 8 of 256 experts, routes over all 256 with the same
    bias, computes its own experts' part times 2.5 and the shared expert
    whole; the routed parts of the 32 ranks and the shared expert COUNTED
    ONCE are the whole layer, in the program and in the reference."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 96, 32)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=256) * 0.1, jnp.float32)
    make = lambda held: RoutedExperts(
        32, 24, num_experts=256, top_k=8, held=held, dtype=jnp.float32,
        score="sigmoid", select_bias=True, routed_scale=2.5, shared_size=24)
    whole = make(None).init(jax.random.PRNGKey(0), x)["params"]
    variables = lambda p: {"params": p, BIAS_COLLECTION: {"bias": bias}}
    (want, stats), seen = make(None).apply(variables(whole), x,
                                           mutable=["intermediates"])
    assert float(stats["rows_held_share"]) == 1.0
    (routed,) = seen["intermediates"]["routed"]
    cfg = {"experts_held": [0, 256], "num_experts_per_tok": 8,
           "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    shared = reference.shared(x[0], whole)
    np.testing.assert_allclose(
        np.asarray(reference.routed(x[0], whole, bias, cfg)),
        np.asarray(routed[0]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(routed[0] + shared),
                               np.asarray(want[0]), atol=2e-5)
    # without the factor the routed part is 2.5 times smaller, no more
    plain = reference.routed(x[0], whole, bias,
                             {**cfg, "routed_scaling_factor": 1.0})
    np.testing.assert_allclose(np.asarray(plain) * 2.5, np.asarray(routed[0]),
                               atol=2e-5)
    total = jnp.zeros_like(want)
    total_ref = jnp.zeros_like(want[0])
    shares = 0.0
    for rank in range(32):
        held = (rank * 8, 8)
        mine = {**whole, **{k: whole[k][held[0]:held[0] + 8]
                            for k in ("w_gate", "w_up", "w_down")}}
        (part, stats), seen = make(held).apply(variables(mine), x,
                                               mutable=["intermediates"])
        shares += float(stats["rows_held_share"])
        # what a rank computes alike, the shared expert, is in every part
        np.testing.assert_allclose(
            np.asarray(part - seen["intermediates"]["routed"][0]),
            np.asarray(shared)[None], atol=2e-5)
        total = total + seen["intermediates"]["routed"][0]
        total_ref = total_ref + reference.routed(x[0], mine, bias, cfg, held)
    assert shares == pytest.approx(1.0)
    np.testing.assert_allclose(np.asarray(total + shared[None]),
                               np.asarray(want), atol=3e-5)
    np.testing.assert_allclose(np.asarray(total_ref + shared),
                               np.asarray(want[0]), atol=3e-5)


def test_callers_without_a_shared_expert_or_a_factor_get_the_layer_they_had():
    """The two new fields at their defaults add no parameter and no
    operation: the sigmoid layer of ``lfm2_24b_a2b`` is what it was."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 32, 16)),
                    jnp.float32)
    old = RoutedExperts(16, 32, num_experts=8, top_k=2, dtype=jnp.float32,
                        score="sigmoid", select_bias=True)
    new = RoutedExperts(16, 32, num_experts=8, top_k=2, dtype=jnp.float32,
                        score="sigmoid", select_bias=True, routed_scale=1.0,
                        shared_size=0)
    variables = old.init(jax.random.PRNGKey(0), x)
    assert set(variables["params"]) == {"router", "w_gate", "w_up", "w_down"}
    text = lambda m: jax.jit(lambda v, x: m.apply(v, x)).lower(
        variables, x).as_text()
    assert text(old) == text(new)
    scaled = RoutedExperts(16, 32, num_experts=8, top_k=2, dtype=jnp.float32,
                           score="sigmoid", select_bias=True, routed_scale=2.5)
    np.testing.assert_allclose(np.asarray(scaled.apply(variables, x)[0]),
                               2.5 * np.asarray(old.apply(variables, x)[0]),
                               rtol=1e-5, atol=1e-6)


def test_the_module_over_s_rows_is_the_exact_form_over_s_minus_1():
    """The model runs the MTP module over all S rows with a pad id as the
    last row's next token and the loss leaves the last two rows' targets out:
    that is the module over S - 1 rows (no pad row at all) against the tokens
    two on, to rounding; the pad id changes nothing the loss reads."""
    cfg = HybridDecoderConfig.tiny_latent(dtype=jnp.float32)
    ids = _ids(seq=96)
    batch = {"input_ids": ids}
    model = HybridDecoderLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), batch)
    params = variables["params"]
    out, seen = model.apply(
        variables, batch, mutable=["intermediates"],
        capture_intermediates=lambda m, _: isinstance(m, MTPModule))
    loss, metrics = losses.latent_moe_lm(out, batch)
    assert float(loss) == pytest.approx(
        float(metrics["lm_loss"]) + 0.1 * float(metrics["mtp_nll"]), rel=1e-6)
    # the exact form: the module given rows 0 .. S-2 and their next tokens
    lead, period, whole, trail = cfg.layout()
    assert (len(lead), period, whole, trail) == (1, (LATENT,), 2, ())
    h = _hidden_before_the_final_norm(model, variables, batch)
    embed = params["token_embed"]["embedding"]
    seg = jnp.zeros((2, 95), jnp.int32)
    pos = hybrid_decoder.document_positions(seg)
    exact, _ = MTPModule(cfg, False).apply(
        {"params": params["mtp"],
         BIAS_COLLECTION: variables[BIAS_COLLECTION]["mtp"]},
        h[:, :-1], embed[ids[:, 1:]], seg, pos)
    np.testing.assert_allclose(np.asarray(out["mtp_hidden"][:, :-1]),
                               np.asarray(exact), atol=2e-5)
    logp = jax.nn.log_softmax(exact[:, :-1] @ params["lm_head"], -1)
    want = -jnp.mean(jnp.take_along_axis(logp, ids[:, 2:, None], -1))
    assert float(metrics["mtp_nll"]) == pytest.approx(float(want), rel=1e-5)
    # head and embedding are the main model's own: both terms reach them
    main_only = jax.grad(lambda p: losses.hybrid_moe_lm(
        model.apply({**variables, "params": p}, batch), batch)[0])(params)
    both = jax.grad(lambda p: losses.latent_moe_lm(
        model.apply({**variables, "params": p}, batch), batch)[0])(params)
    for leaf in ("lm_head", "token_embed"):
        a, b = jax.tree.leaves(both[leaf])[0], jax.tree.leaves(
            main_only[leaf])[0]
        assert float(jnp.abs(a - b).max()) > 1e-6, leaf
    assert all(float(jnp.abs(g).max()) == 0
               for g in jax.tree.leaves(main_only["mtp"]))
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree.leaves(both["mtp"]))


def _hidden_before_the_final_norm(model, variables, batch):
    """The last main block's output: what the scan of periods carried out of
    its last iteration (captured stacked, an entry an iteration)."""
    _, seen = model.apply(
        variables, batch, mutable=["intermediates"],
        capture_intermediates=lambda m, _: m.name == "periods")
    (x, _), = seen["intermediates"]["periods"]["__call__"]
    return x[-1]


def test_the_counters_cover_the_modules_block_and_reach_the_metrics():
    """``moe_*`` and ``router_bias_abs_max`` are over the expert blocks the
    model runs, the module's among them; ``lm_loss`` and ``mtp_nll`` ride
    beside them; a model without the module has no second term."""
    cfg = HybridDecoderConfig.tiny_latent(dtype=jnp.float32,
                                          experts_held=(0, 4))
    batch = {"input_ids": _ids()}
    model = HybridDecoderLM(cfg)
    variables = dict(model.init(jax.random.PRNGKey(0), batch))
    assert set(variables) == {"params", BIAS_COLLECTION}
    bias = variables[BIAS_COLLECTION]
    bias["mtp"]["block"]["moe"]["bias"] = jnp.full((8,), -0.25)
    out = model.apply(variables, batch)
    # the largest |b| over the blocks is the module's
    assert float(out["router_bias_abs_max"]) == 0.25
    assert 0.2 < float(out["moe_rows_held_share"]) < 0.8
    _, metrics = losses.latent_moe_lm(out, batch)
    assert {"lm_loss", "mtp_nll", "moe_load_max_over_mean",
            "moe_rows_held_share", "router_bias_abs_max", "loss",
            "perplexity"} <= set(metrics)
    plain = HybridDecoderLM(HybridDecoderConfig.tiny_latent(
        dtype=jnp.float32, mtp_layers=0))
    out = plain.apply(plain.init(jax.random.PRNGKey(0), batch), batch)
    assert "mtp_hidden" not in out
    with pytest.raises(ValueError, match="mtp_layers"):
        HybridDecoderConfig.tiny_latent(mtp_layers=2)
    # tied, the head is the embedding and no leaf of its own
    tied = HybridDecoderLM(HybridDecoderConfig.tiny_latent(
        dtype=jnp.float32, tie_embeddings=True))
    assert "lm_head" not in tied.init(jax.random.PRNGKey(0), batch)["params"]


def test_latent_layers_mix_with_the_other_kinds():
    cfg = HybridDecoderConfig.tiny_latent(
        layer_types=(LATENT, ATTENTION, LATENT, ATTENTION, LATENT),
        dtype=jnp.float32)
    assert cfg.layout() == ((LATENT,), (ATTENTION, LATENT), 2, ())
    batch = {"input_ids": _ids(seq=64)}
    model = HybridDecoderLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), batch)
    loss, _ = losses.latent_moe_lm(model.apply(variables, batch), batch)
    assert np.isfinite(float(loss))


def test_the_decoder_on_a_mesh_runs_inside_its_shard_maps(monkeypatch):
    """On ``data=2 x expert=2 x tensor=2`` of the host's devices the flash
    kernels at two head sizes (interpreted) run inside ``_flash_on_mesh``'s
    ``shard_map`` and the experts inside theirs: loss and gradients of the
    one-device program."""
    from distributeddeeplearningspark_tpu.ops import attention, ring_attention
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    if len(jax.devices()) < 8:
        pytest.skip("needs eight host devices")
    cfg = HybridDecoderConfig.tiny_latent(dtype=jnp.float32)
    batch = {"input_ids": _ids(rows=2, seq=512, seed=4)}
    model = HybridDecoderLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), batch)

    def loss(p):
        out = model.apply({**variables, "params": p}, batch)
        return losses.latent_moe_lm(out, batch)[0]

    want, want_grad = jax.jit(jax.value_and_grad(loss))(variables["params"])
    mesh = MeshSpec(data=2, expert=2, tensor=2).build(jax.devices()[:8])
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    ring_attention.set_default_mesh(mesh)
    try:
        with mesh:
            lowered = jax.jit(jax.value_and_grad(loss)).lower(
                variables["params"])
            got, got_grad = lowered.compile()(variables["params"])
    finally:
        ring_attention.set_default_mesh(None)
    text = lowered.as_text()
    assert text.count("shard_map") >= 2 or "manual" in text
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_grad),
                            jax.tree.leaves(want_grad)):
        err = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
        assert err < 2e-4, (jax.tree_util.keystr(path), err)
