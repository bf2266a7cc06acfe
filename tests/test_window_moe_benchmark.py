"""The analytic counts, the configuration file, the per-layer readers and a
CPU rehearsal of the cell ``laguna_xs2.fit_swa16k`` at a toy size (as
``tests/test_ssm_moe_benchmark.py`` does for the cell before it): the
program's model against ``benchmark/reference/laguna_xs2.py`` (loss, every
gradient leaf, every term of the compared scalar), YaRN's table, the
sixteen shares of an expert layer against the uncut layer, and the refusal
a checkout without the band gives. A CPU run checks control flow, counts
and agreement with the reference; it yields no time, rate or utilisation."""

import json
import math
import os
import time

import pytest

import _cells as cells
from _cells import ROOT, ev, json_of, load, reader, tree  # noqa: F401

CELL = "laguna_xs2.fit_swa16k"
NAME = "laguna_xs2"
TRAFFIC = "fit_swa16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_attention_heads_per_layer", "num_experts", "vocab_size"]
TRACE_METRICS = ("window_attn_ms_per_step", "window_attn_roofline")
COUNTER_METRICS = ("window_blocks_walked_share",)
#: readers the benchmark had, whose layers this cell runs: its name is the
#: last of their ``workloads`` (an accepted list may be appended to)
ACCEPTED_COUNTERS = ("experts_load_max_over_mean", "attn_pairs_share",
                     "attn_blocks_walked_share", "attn_blocks_masked_share")
ACCEPTED_TRACE = ("experts_ms_per_step", "flash_causal_ms_per_step",
                  "flash_causal_roofline")
ACCEPTED_START = ("start_import_s", "start_session_s", "start_state_init_s",
                  "start_first_batch_s", "start_first_steps_s",
                  "start_unaccounted_s")


def _mod():
    return load(os.path.join(ROOT, "benchmark", "configs", NAME + ".py"))


def test_operations_per_token_count_required_work():
    from benchmark.harness import flops_hybrid, flops_window

    cfg = json_of("benchmark", "configs", NAME + ".json")
    traffic = json_of("benchmark", "traffic", TRAFFIC + ".json")
    per_token = _mod().flops_per_item(cfg, traffic)
    h, d, kv = 2048, 128, 8 * 128
    pairs = flops_hybrid.in_document_pairs_share(traffic)
    band = flops_window.window_pairs_share(traffic, 512)
    # about 2.7 documents a window: 0.61 of the triangle lies inside one,
    # and all but the first 512 positions of a document fill their band
    assert 0.55 < pairs < 0.7 and 0.9 < band < 1.0

    def attention(heads, per_query):
        qo = heads * d
        return 2 * (h * qo + 2 * h * kv + qo * h + h * heads) + (
            per_query * 2 * 2 * qo)

    full = attention(48, pairs * 16385 / 2)
    sliding = attention(64, band * 512)
    dense = 3 * 2 * h * 8192
    experts = 8 * 16 / 256 * 3 * 2 * h * 512 + 3 * 2 * h * 512
    router = 2 * h * 256
    head = 2 * h * 12544
    forward = 2 * full + 3 * sliding + dense + 4 * experts + head
    assert per_token == pytest.approx(3 * forward + 3 * 4 * router)
    assert 1.9e9 < per_token < 2.6e9
    # the band's share of the work: what walking the causal triangle at 64
    # heads would have added (PERF.md section 4)
    triangle = 3 * 3 * (pairs * 16385 / 2 - band * 512) * 2 * 2 * 64 * d
    assert 0.3 < triangle / per_token < 0.8
    # the kernels' required work: 2, 3 and 4 products over the band's pairs
    work = flops_window.flash_window_kernels(
        **_mod().flash_window_shapes(cfg, traffic), pairs_share=band)
    product = 2.0 * 64 * d * band * 16384 * 512
    assert [work[k]["ops"] for k in ("flash_window_fwd", "flash_window_bwd_dq",
                                     "flash_window_bwd_dkv")] == [
        pytest.approx(2 * product), pytest.approx(3 * product),
        pytest.approx(4 * product)]
    assert work["flash_window_fwd"]["bytes"] == 2 * (
        16384 * 64 * d * 2) + 2 * (16384 * kv * 2)
    assert _mod().flash_causal_shapes(cfg, traffic)["heads"] == 48


def test_configuration_file_states_the_catalog_and_its_cuts():
    bench = json_of("BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == NAME)
    cfg = json_of(conf["file"])
    assert conf["reduced"] == REDUCED
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert len(cfg["published"][key]) == 40
        assert cfg["published"][key][:5] == cfg[key], key
    assert cfg["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["published"]["num_experts"] == cfg["router_width"] == 256
    assert cfg["experts_held"] == [0, cfg["num_experts"]] == [0, 16]
    assert cfg["published"]["vocab_size"] == 100352 == cfg["vocab_size"] * 8
    for key in ("deployment", "assumed", "check"):
        assert cfg[key], key
    for key in ("router", "router_aux_loss", "router_trained", "gate",
                "shared_expert", "qk_norm", "rotary_full", "rotary_sliding",
                "sliding_window", "positions", "packed_documents",
                "optimizer", "weights"):
        assert key in cfg["assumed"], key
    assert "490,297,344" in cfg["deployment"] and "16" in cfg["deployment"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    traffic = json_of("benchmark", "traffic", TRAFFIC + ".json")
    assert {k: traffic[k] for k in (
        "feed", "num_docs", "doc_len_median", "doc_len_sigma", "doc_len_min",
        "doc_len_max", "zipf_exponent", "vocab_size", "eos_id", "seq_len",
        "per_chip_batch", "warmup_steps", "trace_steps", "loss_band")} == {
        "feed": "tokens_lm_docs", "num_docs": 1024, "doc_len_median": 6000,
        "doc_len_sigma": 1.0, "doc_len_min": 64, "doc_len_max": 65536,
        "zipf_exponent": 1.0, "vocab_size": 12544, "eos_id": 0,
        "seq_len": 16384, "per_chip_batch": 1, "warmup_steps": 5,
        "trace_steps": 5, "loss_band": 0.5}
    for name in TRACE_METRICS + COUNTER_METRICS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "throughput"
        assert (metric["layer"], metric["source"]) == (
            ("ops", "device_trace") if name in TRACE_METRICS
            else ("step, model and ops", "program_counter"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    for name in ACCEPTED_COUNTERS + ACCEPTED_TRACE + ACCEPTED_START:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"][-1] == CELL, name
        assert metric["workloads"].count(CELL) == 1, name
    if not os.path.exists(CATALOG):
        return
    row = next(json.loads(line) for line in open(CATALOG)
               if '"Laguna-XS.2"' in line)
    assert conf["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert cfg[key] == value, key
        else:
            assert cfg["published"][key] == value, key


def test_the_built_model_has_the_parameters_the_file_counts():
    """490,297,344, reckoned from the model the configuration builds, leaf
    by leaf."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = json_of("benchmark", "configs", NAME + ".json")
    traffic = json_of("benchmark", "traffic", TRAFFIC + ".json")
    model = _mod().build(cfg, traffic)["model"]
    lead, period, whole, trail = model.cfg.layout()
    assert (len(lead), len(period), whole, trail) == (1, 4, 1, ())
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), {"input_ids": jnp.zeros((1, 512), jnp.int32)}))
    assert set(shapes) == {"params"}           # no bias: a softmax router
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    params = shapes["params"]
    assert count(params) == 490_297_344
    full = {"wq": 2048 * 48 * 128, "wk": 2048 * 8 * 128,
            "wv": 2048 * 8 * 128, "wo": 48 * 128 * 2048, "gate": 2048 * 48}
    sliding = {**full, "wq": 2048 * 64 * 128, "wo": 64 * 128 * 2048,
               "gate": 2048 * 64}
    lead0 = params["lead_0"]
    assert {k: count(v) for k, v in lead0["self_attn"].items()} == full
    assert count(lead0["self_attn"]) == 29_458_432
    assert count(lead0["mlp"]) == 3 * 2048 * 8192
    assert count(lead0) == 79_794_176
    layers = params["periods"]
    for j in range(3):
        assert {k: count(v) for k, v in
                layers[f"layer_{j}"]["self_attn"].items()} == sliding
        assert count(layers[f"layer_{j}"]) == 91_885_568
    assert count(layers["layer_3"]["self_attn"]) == 29_458_432
    assert count(layers["layer_3"]) == 83_464_192
    moe = layers["layer_0"]["moe"]
    assert {k: count(v) for k, v in moe.items()} == {
        "router": 2048 * 256, "w_gate": 16 * 2048 * 512,
        "w_up": 16 * 2048 * 512, "w_down": 16 * 512 * 2048,
        "shared_gate": 2048 * 512, "shared_up": 2048 * 512,
        "shared_down": 512 * 2048}
    assert count(params["token_embed"]) == count(params["lm_head"]) \
        == 2048 * 12544
    assert count(params["final_norm"]) == 2048
    # no per-head norm on q and k
    assert "q_norm" not in lead0["self_attn"]


def test_yarn_frequencies_against_a_table_at_the_published_values():
    """``yarn_inv_freq`` (the program's) and the reference's own formula at
    d = 64, base 5e5, factor 64, original 4096, beta_fast 64, beta_slow 1:
    lo = 5, hi = 16; the first five pairs keep base^(-2i/64), the pairs from
    16 on are divided by 64, and the ramp joins them linearly between."""
    import numpy as np

    from distributeddeeplearningspark_tpu.models.llama import yarn_inv_freq

    ref = load(os.path.join(ROOT, "benchmark", "reference", NAME + ".py"))
    d, base = 64, 5e5
    lo = math.floor(d * math.log(4096 / (64 * 2 * math.pi))
                    / (2 * math.log(base)))
    hi = math.ceil(d * math.log(4096 / (2 * math.pi)) / (2 * math.log(base)))
    assert (lo, hi) == (5, 16)
    table = []
    for i in range(32):
        f = base ** (-2 * i / d)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        table.append(f / 64 * ramp + f * (1 - ramp))
    table = np.asarray(table)
    got = yarn_inv_freq(64, base, factor=64.0, original_max_position=4096,
                        beta_fast=64.0, beta_slow=1.0)
    want = ref.yarn_inv_freq(64, base, 64.0, 4096, 64.0, 1.0)
    np.testing.assert_allclose(got, table, rtol=1e-6)
    np.testing.assert_allclose(want, table, rtol=1e-6)
    assert got[0] == 1.0 and got[4] == pytest.approx(base ** (-8 / 64))
    assert got[16] == pytest.approx(base ** (-32 / 64) / 64, rel=1e-6)
    assert got[10] == pytest.approx(
        base ** (-20 / 64) * (1 - 5 / 11 + 5 / 11 / 64), rel=1e-6)


def test_partial_rotary_turns_the_leading_half_and_passes_the_rest():
    """``rotary_embedding`` with ``rotary_dim`` 64 of 128, YaRN's frequencies
    and the attention factor: the leading 64 dimensions are the rotate-half
    rotation of themselves (cos and sin times the factor), the other 64 come
    out as they went in; without the new arguments it gives today's bits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearningspark_tpu.models.llama import (
        rotary_embedding, yarn_inv_freq)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 40, 3, 128)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 16384, (1, 40)), jnp.int32)
    inv = yarn_inv_freq(64, 5e5, factor=64.0, original_max_position=4096,
                        beta_fast=64.0, beta_slow=1.0)
    got = rotary_embedding(x, pos, 5e5, rotary_dim=64, inv_freq=inv,
                           attention_factor=1.4158883)
    np.testing.assert_array_equal(np.asarray(got[..., 64:]),
                                  np.asarray(x[..., 64:]))
    # (the angles as the program takes them: a float32 product)
    angles = (np.asarray(pos, np.float32)[..., None, None] * inv).astype(
        np.float64)
    c, s = np.cos(angles) * 1.4158883, np.sin(angles) * 1.4158883
    x1, x2 = np.asarray(x[..., :32]), np.asarray(x[..., 32:64])
    np.testing.assert_allclose(np.asarray(got[..., :32]), x1 * c - x2 * s,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(got[..., 32:64]), x2 * c + x1 * s,
                               atol=2e-4)
    # the whole head at theta, as every sibling calls it: the same program
    plain = lambda t: rotary_embedding(t, pos, 1e4)
    explicit = lambda t: rotary_embedding(t, pos, 1e4, rotary_dim=128)
    assert str(jax.make_jaxpr(plain)(x)) == str(jax.make_jaxpr(explicit)(x))
    with pytest.raises(NotImplementedError):
        rotary_embedding(x, pos, 1e4, interleaved=True, rotary_dim=64)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Each of sixteen ranks holds 16 of 256 experts, routes over all 256 by
    the softmax, renormalised over the top 8 and scaled by 2.5, and computes
    its own experts' part beside the shared expert; the parts of the sixteen
    ranks, the shared expert counted ONCE, are the whole layer, in the
    program and in the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearningspark_tpu.models.moe import RoutedExperts

    ref = load(os.path.join(ROOT, "benchmark", "reference", NAME + ".py"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 64, 32)), jnp.float32)
    make = lambda held, shared: RoutedExperts(
        32, 16, num_experts=256, top_k=8, held=held, dtype=jnp.float32,
        score="softmax", routed_scale=2.5, shared_size=shared)
    whole = make(None, 24).init(jax.random.PRNGKey(0), x)["params"]
    want, stats = make(None, 24).apply({"params": whole}, x)
    assert float(stats["rows_held_share"]) == 1.0
    cfg = {"experts_held": [0, 256], "num_experts_per_tok": 8,
           "moe_routed_scaling_factor": 2.5,
           "assumed_values": {"norm_topk_prob": True}}
    y, _, _ = ref.routed(x[0], whole, cfg)
    shared = ref.swiglu(x[0], whole["shared_gate"]["kernel"],
                        whole["shared_up"]["kernel"],
                        whole["shared_down"]["kernel"])
    np.testing.assert_allclose(np.asarray(y + shared), np.asarray(want[0]),
                               atol=2e-5)
    total, total_ref, shares = 0.0, 0.0, 0.0
    for rank in range(16):
        held = (rank * 16, 16)
        mine = {"router": whole["router"], **{
            k: whole[k][held[0]:held[0] + 16]
            for k in ("w_gate", "w_up", "w_down")}}
        if rank == 0:     # the rank that adds the shared expert
            mine.update({k: whole[k] for k in (
                "shared_gate", "shared_up", "shared_down")})
        part, stats = make(held, 24 if rank == 0 else 0).apply(
            {"params": mine}, x)
        shares += float(stats["rows_held_share"])
        total = total + part
        total_ref = total_ref + ref.routed(x[0], mine, cfg, held)[0]
    assert shares == pytest.approx(1.0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total_ref + shared),
                               np.asarray(want[0]), atol=2e-5)


#: the configuration at a toy size: the same kinds, gate, rotary and router,
#: a window of 16
TOY = {
    f"configs/{NAME}.json": dict(
        vocab_size=256, hidden_size=128, head_dim=16, num_key_value_heads=2,
        num_attention_heads_per_layer=[6, 8, 8, 8, 6], sliding_window=16,
        router_width=8, num_experts=4, experts_held=[2, 4],
        num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=64, intermediate_size=192,
        compute_dtype="float32",
        check={"examples": 1, "loss_abs_tol": 1e-4, "grad_rel_tol": 1e-3,
               "term_weights": {"window_attention_energy": 1.0,
                                "full_attention_energy": 1.0,
                                "experts_energy": 1.0, "expert_probe": 1.0,
                                "window_edge_probe": 1.0,
                                "rotary_probe": 1.0},
               "held_share_floor": 0.0625, "why": "float32 on both sides"}),
    f"traffic/{TRAFFIC}.json": dict(
        num_docs=64, doc_len_median=40, doc_len_min=8, doc_len_max=200,
        vocab_size=256, seq_len=256, per_chip_batch=1, log_every=2,
        warmup_steps=2, trace_steps=2),
}


def _toy_cfg(root):
    return json.loads((root / "benchmark" / "configs"
                       / f"{NAME}.json").read_text())


def test_the_cell_rehearses_at_a_toy_size(tree):
    from benchmark.harness import runner

    with cells.run_in(tree, step_s=cells.REHEARSAL_STEP_S):
        r = runner.measure(str(tree), CELL, seed=2 ** 31 + 11, seconds=0.5,
                           trace=True, t_process=time.perf_counter(),
                           master="local[1]")
        again = runner.measure(str(tree), CELL, seed=2 ** 31 + 11,
                               seconds=0.5, trace=False,
                               t_process=time.perf_counter(),
                               master="local[1]")
    assert r["correct"], r["facts"]
    assert r["failed"] == 0 and r["attempted"] > 0
    ref = r["facts"]["reference"]
    assert abs(ref["loss_program"] - ref["loss_reference"]) < 1e-4
    assert ref["grad_rel_err"] < 1e-3
    assert r["facts"]["executable"]["batch_arrays"] == {
        "input_ids": [1, 256], "segment_ids": [1, 256]}
    assert r["facts"]["train_step"]["compiles"] == 1
    assert r["facts"]["train_step"]["aot"]
    # the step's counters, the Switch term among them, reach step_metrics
    # through the loss
    from distributeddeeplearningspark_tpu import telemetry
    laps = [e["metrics"] for e in telemetry.read_events(
        os.path.join(r["facts"]["out_dir"], "telemetry"))
        if e.get("kind") == "step_metrics"]
    for name in ("moe_load_max_over_mean", "moe_rows_held_share",
                 "attn_pairs_share", "window_blocks_walked_share",
                 "window_pairs_share", "moe_aux", "lm_loss"):
        assert all(name in lap for lap in laps), name
    for lap in laps:
        assert lap["loss"] == pytest.approx(lap["lm_loss"] + lap["moe_aux"])
        # 4 layers' Switch terms near 1 each, times 0.001
        assert 0.003 < lap["moe_aux"] < 0.02
        assert 0 < lap["window_pairs_share"] <= 1
    # (one block of 256 at this length: the band walks it)
    assert r["metrics"]["window_blocks_walked_share"]["value"] == 100.0
    for name in ACCEPTED_COUNTERS:
        assert name in r["metrics"], name
    # a CPU run has no device plane: the device-trace readers return nothing
    for name in TRACE_METRICS + ACCEPTED_TRACE + ("device_step_ms", "mfu"):
        assert name not in r["metrics"], name
    assert again["facts"]["feed"]["seed_cache_hit"] is True
    assert set(again["metrics"]) == {"throughput", "setup_s"}


def _toy_model(tree, seq=96, seed=0):
    """``(config module, reference, cfg, built, params, batch)`` at the toy
    size, the parameters off their initial values."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    mod = load(str(tree / "benchmark" / "configs" / f"{NAME}.py"))
    ref = load(str(tree / "benchmark" / "reference" / f"{NAME}.py"))
    ref.QUERIES, ref.ROWS = 32, 40       # blocks that do not divide the row
    cfg = _toy_cfg(tree)
    built = mod.build(cfg, {"vocab_size": 256, "seq_len": seq})
    rng = np.random.default_rng(seed)
    batch = {"input_ids": jnp.asarray(rng.integers(1, 256, (1, seq)),
                                      jnp.int32),
             "segment_ids": jnp.asarray(
                 np.sort(rng.integers(0, 4, (1, seq)), axis=1), jnp.int32)}
    init = jax.jit(built["model"].init)
    params = init(jax.random.PRNGKey(seed), batch)["params"]
    params = jax.tree.map(
        lambda a: a + jnp.asarray(0.05 * rng.normal(size=a.shape), a.dtype),
        params)
    return mod, ref, cfg, built, params, batch


def test_the_timed_forward_gives_the_terms_the_reference_gives(tree):
    """The energies read off the operators' outputs inside ``model.apply``
    (``step_parts``: the pass that gives the loss) and the three probes are
    the reference's; a run whose held share is under the floor compares as
    infinite."""
    import jax
    import numpy as np

    mod, ref, cfg, built, params, batch = _toy_model(tree)
    got = jax.jit(lambda p, b: mod.parts(built["model"], built["loss"], p,
                                         {}, b))(params, batch)
    want = jax.jit(lambda p, b: ref.parts(p, {}, b, cfg))(params, batch)
    assert set(got) - set(want) == {"rows_held_share"}
    assert set(cfg["check"]["term_weights"]) == set(want) - {"loss"}
    for name, value in want.items():
        assert float(value) > 0
        assert float(got[name]) == pytest.approx(float(value), rel=2e-5), name
    assert float(mod.compared(got)) == pytest.approx(
        float(ref.compared(want, cfg)), abs=1e-4)
    assert 0.3 < float(got["rows_held_share"]) < 0.7    # 4 of 8 held
    assert np.isinf(float(mod.compared({**got, "rows_held_share": 0.01})))
    with pytest.raises(ValueError, match="per-head gate"):
        mod.build({**cfg, "gating": False}, {"vocab_size": 256})
    with pytest.raises(ValueError, match="YaRN"):
        mod.build({**cfg, "rope_parameters": {
            **cfg["rope_parameters"], "sliding_attention": {
                **cfg["rope_parameters"]["full_attention"]}}},
            {"vocab_size": 256})


def test_every_leafs_gradient_is_the_references_in_float32(tree):
    """The program against ``benchmark/reference/laguna_xs2.py`` on seeded
    weights: the loss (the Switch term included) and EVERY leaf's gradient,
    leaf by leaf (the harness compares the flattened gradient's norm)."""
    import jax
    import jax.numpy as jnp

    _, ref, cfg, built, params, batch = _toy_model(tree, seq=80, seed=1)

    def program(p):
        out = built["model"].apply({"params": p}, batch)
        return built["loss"](out, batch)[0]

    got_loss, got = jax.jit(jax.value_and_grad(program))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.training_loss(p, batch, cfg)))(params)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    leaves = jax.tree_util.tree_leaves_with_path(want)
    assert len(leaves) == len(jax.tree.leaves(got)) == 69
    for (path, b), a in zip(leaves, jax.tree.leaves(got)):
        norm = float(jnp.linalg.norm(b))
        assert norm > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) / norm < 3e-5, \
            jax.tree_util.keystr(path)


def test_a_program_without_the_band_is_refused_with_a_message(
        tree, monkeypatch):
    """What the parent commit does with the new cell: it fails at once,
    before a device or the feed is touched."""
    import importlib.util

    from benchmark.harness import runner

    sound = importlib.util.find_spec

    def without_the_band(name, *a, **kw):
        # (the parent's flash module: the same file without the band)
        if name.endswith("ops.flash_attention"):
            return None
        return sound(name, *a, **kw)

    monkeypatch.setattr(importlib.util, "find_spec", without_the_band)
    with cells.run_in(tree), pytest.raises(runner.Refused) as e:
        runner.measure(str(tree), CELL, seed=3, seconds=0.5, trace=False,
                       t_process=time.perf_counter(), master="local[1]")
    assert "band" in e.value.args[0][0]
    assert not (tree / "benchmark" / ".cache").exists()   # no feed was built


def _band_step(t0, scale):
    """One step's band kernels of three sliding layers, each kernel
    ``scale`` times its least time; and the causal kernels of two full
    layers, which are not the band's."""
    from benchmark.harness import flops, flops_window

    ctx = cells.ctx(CELL, [])
    work = flops_window.flash_window_kernels(
        **_mod().flash_window_shapes(ctx["cfg"], ctx["traffic"]),
        pairs_share=0.95)
    events, t = [], t0
    for _ in range(3):
        for name in ("flash_window_fwd", "flash_window_bwd_dq",
                     "flash_window_bwd_dkv"):
            ms = scale * 1e3 * flops.least_seconds(
                work[name]["ops"], work[name]["bytes"], ctx["peaks"])[0]
            events.append(ev(f"{name}.1", t, ms, op="custom-call"))
            t += ms
    for _ in range(2):
        events.append(ev("flash_fwd.1", t, 9.0, op="custom-call"))
        events.append(ev("flash_bwd_dkv.1", t + 9, 9.0,
                         op="custom-call"))
        t += 18
    return events


def test_the_band_readers_read_the_band_kernels_and_stay_under_100():
    laps = [{"metrics": {"window_pairs_share": 0.95,
                         "window_blocks_walked_share": 0.11,
                         "attn_pairs_share": 0.4}},
            {"metrics": {"window_pairs_share": 0.95,
                         "window_blocks_walked_share": 0.12,
                         "attn_pairs_share": 0.4}}]
    events = _band_step(0.0, 4.0)
    ctx = cells.ctx(CELL, events, steps=1, laps=laps)
    band = sum(e[2] for e in events if "flash_window_" in e[0]) / 1e6
    assert reader("window_attn_ms_per_step")(ctx) == pytest.approx(band)
    # every kernel at four times its least time: 25%
    assert reader("window_attn_roofline")(ctx) == pytest.approx(25.0)
    assert ctx["facts"]["window_attn_roofline"][
        "pairs_share_of_the_laps"] == pytest.approx(0.95)
    at_roofline = cells.ctx(CELL, _band_step(0.0, 1.0), steps=1, laps=laps)
    assert reader("window_attn_roofline")(at_roofline) == pytest.approx(100.0)
    assert reader("window_blocks_walked_share")(ctx) == pytest.approx(11.5)
    # the causal readers take the full layers' kernels and not the band's
    causal = reader("flash_causal_ms_per_step")(ctx)
    assert causal == pytest.approx(36.0)
    # a program without the band (no kernels, no counters): nothing, no raise
    bare = cells.ctx(CELL, [ev("flash_fwd.1", 0, 5, op="custom-call")],
                     laps=[{"metrics": {"loss": 1}}])
    for name in TRACE_METRICS + COUNTER_METRICS:
        assert reader(name)(bare) is None, name
        assert reader(name)({**bare, "trace": None}) is None, name
    # ... and in a cell of another configuration
    other = {**ctx, "cell": {"config_mod": object()}}
    for name in TRACE_METRICS:
        assert reader(name)(other) is None, name
