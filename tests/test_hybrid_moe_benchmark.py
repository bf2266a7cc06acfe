"""The analytic counts, the configuration file, the per-layer readers and a
CPU rehearsal of the cell ``lfm2_24b_a2b.fit_seg32k`` at a toy size, its
planted faults among them (as ``tests/test_sparse_moe_benchmark.py`` does for
the cell before it). A CPU run checks control flow, counts and agreement with
the reference; it yields no time, rate or utilisation."""

import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "lfm2_24b_a2b.fit_seg32k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("shortconv_ms_per_step", "shortconv_roofline",
               "flash_causal_ms_per_step", "flash_causal_roofline",
               "experts_ms_per_step", "experts_load_max_over_mean",
               "attn_pairs_share", "attn_blocks_walked_share")


def _load(path):
    from benchmark.harness import runner
    return runner.load_module(path)


def _json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def test_operations_per_token_count_required_work():
    from benchmark.harness import flops_hybrid

    cfg = _json("benchmark", "configs", "lfm2_24b_a2b.json")
    traffic = _json("benchmark", "traffic", "fit_seg32k.json")
    mod = _load(os.path.join(ROOT, "benchmark", "configs", "lfm2_24b_a2b.py"))
    share = flops_hybrid.in_document_pairs_share(traffic)
    assert 0.42 < share < 0.45           # the issue's own count: 0.43
    assert share == flops_hybrid.in_document_pairs_share(dict(traffic))
    per_token = mod.flops_per_item(cfg, traffic)
    # the issue's hand count: 372M of projections, feed-forwards, experts and
    # head (373M with the router), 58M of attention, times 3
    attention = share * (32768 + 1) / 2 * 4 * 2048
    assert abs(attention - 58e6) < 1.5e6
    with_router = mod.flops_per_item({**cfg, "train_router": True}, traffic)
    assert abs(with_router / 3 - attention - 372.6e6) < 0.5e6
    # the cell's router is not trained: its four products have no backward
    assert with_router - per_token == 2 * (4 * 2 * 2048 * 64)
    # attention over the whole causal prefix would count 2.3 times the pairs
    whole = flops_hybrid.hybrid_moe_lm_flops_per_token(
        hidden_size=2048, layer_types=cfg["layer_types"], num_dense_layers=1,
        num_attention_heads=32, num_key_value_heads=8, head_dim=64,
        intermediate_size=11776, router_width=64, experts_per_token=4,
        experts_held=8, expert_size=1536, vocab_size=8192, seq_len=32768,
        pairs_share=1.0, train_router=False)
    assert whole - per_token == pytest.approx(
        3 * (1 - share) * (32768 + 1) / 2 * 4 * 2048)
    conv = flops_hybrid.shortconv_kernels(**mod.shortconv_shapes(cfg, traffic))
    assert conv["shortconv_fwd"]["bytes"] == pytest.approx(
        32768 * 2048 * 4 * 2, rel=0.01)          # 0.27 GB a layer forward
    assert conv["shortconv_bwd"]["bytes"] == pytest.approx(
        32768 * 2048 * 8 * 2, rel=0.01)
    flash = flops_hybrid.flash_causal_kernels(
        **mod.flash_causal_shapes(cfg, traffic), pairs_share=0.5)
    pairs = 0.5 * 32768 * 32769 / 2
    assert flash["flash_fwd"]["ops"] == 2 * 2 * 32 * 64 * pairs
    assert flash["flash_bwd_dkv"]["ops"] == 2 * flash["flash_fwd"]["ops"]


def test_configuration_file_states_the_catalog_and_its_cuts():
    bench = _json("BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == "lfm2_24b_a2b")
    cfg = _json(conf["file"])
    assert sorted(conf["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts", "num_hidden_layers",
        "vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["published"]["num_dense_layers"] == 2
    assert cfg["published"]["num_experts"] == cfg["router_width"] == 64
    assert cfg["published"]["vocab_size"] == 65536 == cfg["vocab_size"] * 8
    assert cfg["experts_held"] == [0, cfg["num_experts"]] == [0, 8]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    for key in ("deployment", "assumed", "check"):
        assert cfg[key], key
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "fit_seg32k"
    for name in NEW_METRICS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        # (the gated convolution is this model's alone; the six others
        # serve the state-space decoder's cell too, appended by PR 37)
        assert metric["workloads"] == [CELL] + (
            [] if name.startswith("shortconv_")
            else ["nemotron3_nano_30b_a3b.fit_seg16k"])
        assert metric["moves"] == "throughput"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    if not os.path.exists(CATALOG):
        return
    row = next(json.loads(line) for line in open(CATALOG)
               if '"LFM2-24B-A2B"' in line)
    assert conf["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert cfg[key] == value, key
    # the cut is the published layers 1-5, counted from 0
    assert cfg["layer_types"] == row["config"]["layer_types"][1:6]


def _copy_of_the_benchmark(root):
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "out",
                                                  "__pycache__", "tests"))
    return root


TOY = {
    "configs/lfm2_24b_a2b.json": dict(
        vocab_size=256, hidden_size=128, intermediate_size=192,
        layer_types=["conv", "full_attention", "conv"], num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        router_width=8, num_experts=4, experts_held=[2, 4],
        num_experts_per_tok=2, moe_intermediate_size=64,
        compute_dtype="float32",
        # ten times the cell's rate. The harness warms up for five seconds
        # and measures one, so the number of steps that move the bias is the
        # machine's speed: 170 here alone (|b| 0.17, ``selection_without_bias``
        # moves ``experts_energy`` by 0.19), about 8 on the driver's machine
        # under six test workers (0.0089 against the 0.01 asked of a fault).
        # The term grows by 1.1 a unit of |b|: at 0.01 a step, 8 steps give
        # 0.09, and a fast machine stops near |b| 0.3 (the load is even)
        assumed_values={"router_bias_update_rate": 0.01},
        check={"examples": 1, "loss_abs_tol": 1e-4, "grad_rel_tol": 1e-3,
               "term_weights": {"experts_energy": 1.0, "boundary_energy": 1.0,
                                "expert_probe": 1.0, "boundary_probe": 1.0},
               "held_share_floor": 0.0625, "why": "float32 on both sides"}),
    "traffic/fit_seg32k.json": dict(
        num_docs=64, doc_len_median=60, doc_len_min=8, doc_len_max=300,
        vocab_size=256, seq_len=256, per_chip_batch=1, log_every=2,
        warmup_steps=2, trace_steps=2),
}


def _toy(root, keep_check=False):
    for rel, patch in TOY.items():
        path = root / "benchmark" / rel
        real = json.loads(path.read_text())
        if keep_check:
            patch = {k: v for k, v in patch.items() if k != "check"}
        path.write_text(json.dumps({**real, **patch}))


@pytest.fixture
def tree(tmp_path, monkeypatch):
    from benchmark.harness import seedcache

    root = _copy_of_the_benchmark(tmp_path / "checkout")
    monkeypatch.setattr(seedcache, "ROOT", str(root / "benchmark" / ".cache"))
    # ``runner.measure`` sets it for its process and never takes it back:
    # set here first, it is restored when the test ends
    monkeypatch.setenv("DLS_TELEMETRY_DIR", str(tmp_path / "telemetry"))
    return root


def test_the_cell_rehearses_at_a_toy_size(tree):
    from benchmark.harness import runner

    _toy(tree)
    r = runner.measure(str(tree), CELL, seed=2 ** 31 + 7, seconds=1.0,
                       trace=True, t_process=time.perf_counter(),
                       master="local[1]")
    assert r["correct"], r["facts"]
    assert r["failed"] == 0 and r["attempted"] > 0
    ref = r["facts"]["reference"]
    assert abs(ref["loss_program"] - ref["loss_reference"]) < 1e-4
    assert ref["grad_rel_err"] < 1e-3
    assert r["facts"]["executable"]["batch_arrays"] == {
        "input_ids": [1, 256], "segment_ids": [1, 256]}
    # the counters' readers find the step's own outputs in step_metrics
    assert 1.0 <= r["metrics"]["experts_load_max_over_mean"]["value"] < 4.0
    assert 0.0 < r["metrics"]["attn_pairs_share"]["value"] <= 100.0
    assert (r["metrics"]["attn_pairs_share"]["value"]
            <= r["metrics"]["attn_blocks_walked_share"]["value"] <= 100.0)
    # one block of 256 x 256, on the diagonal: it is walked, and masked
    assert r["metrics"]["attn_blocks_masked_share"]["value"] == 100.0
    facts = r["facts"]["layer_facts"]["experts_load_max_over_mean"]
    assert facts["router_bias_abs_max_last"] > 0       # the step moved it
    # a CPU run has no device plane: the device-trace readers return nothing
    for name in NEW_METRICS[:5] + ("device_step_ms", "mfu"):
        assert name not in r["metrics"], name
    again = runner.measure(str(tree), CELL, seed=2 ** 31 + 7, seconds=0.5,
                           trace=False, t_process=time.perf_counter(),
                           master="local[1]")
    assert again["facts"]["feed"]["seed_cache_hit"] is True
    assert set(again["metrics"]) == {"throughput", "setup_s"}


def test_the_timed_forward_gives_the_terms_the_reference_gives(tree):
    """The energies read off the operators' outputs inside ``model.apply``
    (``step_parts``: the pass that gives the loss) are the reference's, and
    so are the float32 probes; a run whose held share is under the floor
    compares as infinite."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearningspark_tpu.models.moe import BIAS_COLLECTION

    _toy(tree)
    mod = _load(str(tree / "benchmark" / "configs" / "lfm2_24b_a2b.py"))
    ref = _load(str(tree / "benchmark" / "reference" / "lfm2_24b_a2b.py"))
    cfg = json.loads((tree / "benchmark" / "configs"
                      / "lfm2_24b_a2b.json").read_text())
    traffic = json.loads((tree / "benchmark" / "traffic"
                          / "fit_seg32k.json").read_text())
    built = mod.build(cfg, traffic)
    assert built["model"].cfg.train_router is False
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(rng.integers(1, 256, (2, 96)),
                                      jnp.int32),
             "segment_ids": jnp.asarray(
                 np.sort(rng.integers(0, 4, (2, 96)), axis=1), jnp.int32)}
    variables = dict(built["model"].init(jax.random.PRNGKey(0), batch))
    params = variables.pop("params")
    mutable = {BIAS_COLLECTION: jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape) * 0.05, jnp.float32),
        variables[BIAS_COLLECTION])}
    got = mod.parts(built["model"], built["loss"], params, mutable, batch)
    want = ref.parts(params, mutable, batch, cfg)
    assert set(got) - set(want) == {"rows_held_share"}
    assert set(cfg["check"]["term_weights"]) == set(want) - {"loss"}
    for name, value in want.items():
        assert float(value) > 0
        assert float(got[name]) == pytest.approx(float(value), rel=2e-5), name
    assert float(mod.compared(got)) == pytest.approx(
        float(ref.compared(want, cfg)), abs=1e-4)
    assert 0.3 < float(got["rows_held_share"]) < 0.7    # 4 of 8 held
    assert np.isinf(float(mod.compared({**got, "rows_held_share": 0.01})))
    with pytest.raises(ValueError, match="routed_scaling_factor"):
        mod.build({**cfg, "routed_scaling_factor": 2.5}, traffic)


def test_a_program_without_segment_ids_is_refused_with_a_message(
        tree, monkeypatch):
    """What the parent commit does with the new cell: it fails at once."""
    from benchmark.harness import runner
    from distributeddeeplearningspark_tpu.data import text

    _toy(tree)
    old = text.packed_token_windows
    monkeypatch.setattr(
        text, "packed_token_windows",
        lambda docs, *, seq_len, eos_id=None, num_partitions=1, key="tokens":
        old(docs, seq_len=seq_len, eos_id=eos_id,
            num_partitions=num_partitions, key=key))
    with pytest.raises(runner.Refused) as e:
        runner.measure(str(tree), CELL, seed=3, seconds=0.5, trace=False,
                       t_process=time.perf_counter(), master="local[1]")
    assert "segment ids" in e.value.args[0][0]


@pytest.fixture(scope="module")
def controls(tmp_path_factory):
    """``benchmark/controls/lfm2_24b_a2b.py`` at the toy size on
    ``local[1]``, in float32 but under the cell's OWN limits: one run of the
    cell, the harness's comparison repeated with each fault planted."""
    from benchmark.harness import seedcache

    root = _copy_of_the_benchmark(
        tmp_path_factory.mktemp("controls") / "checkout")
    _toy(root, keep_check=True)
    mod = _load(str(root / "benchmark" / "controls" / "lfm2_24b_a2b.py"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(seedcache, "ROOT", str(root / "benchmark" / ".cache"))
        mp.setenv("DLS_TELEMETRY_DIR", str(root / "telemetry"))  # restored
        return mod, mod.run(2 ** 31 + 11, 1.0, mod.ALL, master="local[1]",
                            root=str(root))


def test_the_sound_program_is_correct_under_the_cells_own_limits(controls):
    _, seen = controls
    assert seen["result"]["correct"], seen["sound"]
    assert seen["sound"]["failures"] == [] == seen["harness"]["failures"]
    # the comparison made again is the harness's own, to the digit
    for key in ("loss_program", "loss_reference", "grad_rel_err"):
        assert seen["sound"][key] == pytest.approx(seen["harness"][key],
                                                   rel=1e-6), key
    assert seen["router_bias_abs_max"] > 0
    # lap by lap, the share of the assignments on the experts held (4 of 8)
    shares = [lap["moe_rows_held_share"] for lap in seen["laps"]]
    assert len(shares) >= 2 and all(0.3 < s < 0.7 for s in shares), shares


@pytest.mark.parametrize("fault", [
    "taps_cross_documents", "attention_crosses_documents",
    "selection_without_bias", "drop_assignments", "taps_gradient_lost",
    "e4m3_weights"])
def test_a_planted_fault_is_not_correct(controls, fault):
    seen = controls[1][fault]
    assert seen["correct"] is False and seen["failures"], seen
    if fault == "taps_gradient_lost":
        assert seen["loss_abs_err"] < 1e-5


@pytest.mark.parametrize("fault, term", [
    ("taps_cross_documents", "boundary_energy"),
    ("taps_cross_documents", "boundary_probe"),
    ("selection_without_bias", "experts_energy"),
    ("selection_without_bias", "expert_probe"),
    ("drop_assignments", "experts_energy"),
    ("drop_assignments", "expert_probe")])
def test_a_fault_moves_the_term_of_the_timed_forward_and_the_probe(
        controls, fault, term):
    """Each term is printed on both sides; the sound program's differ from
    the reference's by rounding, a fault's by the fault."""
    sound = controls[1]["sound"]["terms"][term]
    assert abs(sound[2]) < 1e-4, sound
    assert abs(controls[1][fault]["terms"][term][2]) > 0.01


def test_positions_not_restarted_is_no_fault_and_the_comparison_says_so(
        controls):
    mod, seen = controls
    assert "positions_not_restarted" in mod.ALL
    assert seen["positions_not_restarted"]["correct"] is True


def test_a_planted_fault_leaves_the_program_as_it_was():
    from distributeddeeplearningspark_tpu.models import moe

    mod = _load(os.path.join(ROOT, "benchmark", "controls",
                             "lfm2_24b_a2b.py"))
    from distributeddeeplearningspark_tpu.models import hybrid_decoder

    planted = mod.faults(None, None, {})
    for name, module, attribute in (
            ("drop_assignments", moe, "_zero_past"),
            ("selection_without_bias", moe, "_held_experts"),
            ("taps_cross_documents", hybrid_decoder, "gated_short_conv"),
            ("attention_crosses_documents", hybrid_decoder,
             "dot_product_attention"),
            ("positions_not_restarted", hybrid_decoder,
             "document_positions")):
        sound = getattr(module, attribute)
        with planted[name][1]():
            assert getattr(module, attribute) is not sound, name
        assert getattr(module, attribute) is sound, name


def _ctx(events, steps=2, laps=()):
    """A traced run's context with hand-made device events (name, start ns,
    duration ns, info)."""
    peaks = _json("benchmark", "peaks.json")
    mod = _load(os.path.join(ROOT, "benchmark", "configs", "lfm2_24b_a2b.py"))
    return {"trace": {"devices": {"0": {"XLA Ops": events}}, "steps": steps,
                      "host": {}},
            "cfg": _json("benchmark", "configs", "lfm2_24b_a2b.json"),
            "traffic": _json("benchmark", "traffic", "fit_seg32k.json"),
            "peaks": peaks["TPU v5 lite"], "cell": {"config_mod": mod},
            "facts": {}, "laps": list(laps)}


def _ev(name, start_ms, dur_ms, op="fusion", result=""):
    kind = name.rsplit(".", 1)[0]
    return [name, start_ms * 1e6, dur_ms * 1e6,
            {"kind": kind, "op": op, "result": result}]


def _reader(name):
    return _load(os.path.join(ROOT, "benchmark", "layer_metrics",
                              name + ".py")).read


def _lap(**metrics):
    return {"step": 10, "metrics": metrics}


def test_stage_readers_find_kernels_by_name_and_xla_stages_by_shape():
    events = [
        _ev("while.1", 0, 100, op="while", result="(s32[], bf16[131072,2048])"),
        _ev("shortconv_fwd.1", 0, 1, op="custom-call",
            result="bf16[1,32768,2048]"),
        _ev("shortconv_bwd.2", 1, 3, op="custom-call",
            result="(bf16[1,32768,6144], f32[1,8,2048])"),
        _ev("flash_fwd.1", 10, 20, op="custom-call"),
        _ev("flash_bwd_dq.1", 30, 30, op="custom-call"),
        _ev("flash_bwd_dkv.1", 60, 40, op="custom-call"),
        _ev("ragged-dot-none.2", 100, 3, op="custom-call",
            result="bf16[131072,1536]"),
        _ev("fusion.9", 103, 4, result="bf16[131072,2048]"),
        _ev("fusion.10", 107, 1, result="f32[32768,64]"),
        _ev("fusion.12", 108, 2, result="(f32[32768,4], s32[32768,4])"),
        _ev("fusion.11", 120, 9, result="bf16[1,32768,2048]"),  # nobody's
    ]
    ctx = _ctx(events)
    assert _reader("shortconv_ms_per_step")(ctx) == pytest.approx(4 / 2)
    assert _reader("flash_causal_ms_per_step")(ctx) == pytest.approx(90 / 2)
    assert _reader("experts_ms_per_step")(ctx) == pytest.approx(10 / 2)
    # a program without kernels or counters (the parent): nothing, no raise
    bare = _ctx([_ev("fusion.1", 0, 5, result="bf16[32,512,768]")])
    for name in NEW_METRICS:
        if name != "experts_ms_per_step":
            assert _reader(name)(bare) is None, name
    for name in NEW_METRICS:
        assert _reader(name)({**bare, "trace": None}) is None, name
    # ... and in a cell of another configuration
    other = {**_ctx(events), "cfg": {"num_experts_per_tok": 8},
             "cell": {"config_mod": object()}}
    for name in ("flash_causal_ms_per_step", "flash_causal_roofline",
                 "shortconv_roofline", "experts_ms_per_step",
                 "experts_load_max_over_mean"):
        assert _reader(name)(other) is None, name


def test_rooflines_count_executions_from_the_trace_and_stay_under_100():
    from benchmark.harness import flops, flops_hybrid

    ctx = _ctx([])
    mod = ctx["cell"]["config_mod"]
    conv = flops_hybrid.shortconv_kernels(
        **mod.shortconv_shapes(ctx["cfg"], ctx["traffic"]))
    flash = flops_hybrid.flash_causal_kernels(
        **mod.flash_causal_shapes(ctx["cfg"], ctx["traffic"]), pairs_share=0.4)
    least = {k: flops.least_seconds(v["ops"], v["bytes"], ctx["peaks"])
             for k, v in {**conv, **flash}.items()}
    assert {least[k][1] for k in conv} == {"memory"}
    assert {least[k][1] for k in flash} == {"compute"}
    # every kernel at four times its least time; the forward conv runs twice
    # a layer (the remat replay), the flash forward once (its output is kept)
    events, t = [], 0.0
    for name, runs in (("shortconv_fwd", 8), ("shortconv_bwd", 4),
                       ("flash_fwd", 1), ("flash_bwd_dq", 1),
                       ("flash_bwd_dkv", 1)):
        for _ in range(runs):
            ms = 4e3 * least[name][0]
            events.append(_ev(f"{name}.1", t, ms, op="custom-call"))
            t += ms
    laps = [_lap(attn_pairs_share=0.3, moe_load_max_over_mean=2.0,
                 router_bias_abs_max=0.01, attn_blocks_walked_share=0.35,
                 attn_blocks_masked_share=0.25),
            _lap(attn_pairs_share=0.5, moe_load_max_over_mean=4.0,
                 router_bias_abs_max=0.02, attn_blocks_walked_share=0.55,
                 attn_blocks_masked_share=0.45)]
    ctx = _ctx(events, steps=1, laps=laps)
    assert _reader("shortconv_roofline")(ctx) == pytest.approx(25.0)
    assert _reader("flash_causal_roofline")(ctx) == pytest.approx(25.0)
    assert ctx["facts"]["shortconv_roofline"]["shortconv_fwd"][
        "runs_per_step"] == 8
    assert ctx["facts"]["flash_causal_roofline"][
        "pairs_share_of_the_laps"] == pytest.approx(0.4)
    assert _reader("attn_pairs_share")(ctx) == pytest.approx(40.0)
    assert _reader("attn_blocks_walked_share")(ctx) == pytest.approx(45.0)
    assert ctx["facts"]["attn_blocks_walked_share"] == {
        "laps": 2, "min": 0.35, "max": 0.55}
    assert _reader("attn_blocks_masked_share")(ctx) == pytest.approx(35.0)
    assert ctx["facts"]["attn_blocks_masked_share"] == {
        "laps": 2, "min": 0.25, "max": 0.45}
    # a program that lacks the counter (the parent) gives nothing
    assert _reader("attn_blocks_masked_share")(
        _ctx(events, steps=1, laps=[_lap(attn_pairs_share=0.3)])) is None
    assert _reader("experts_load_max_over_mean")(ctx) == pytest.approx(3.0)
    # a window packed with one document: the whole causal triangle is
    # required, and a kernel AT its roofline reads 100, not more
    full = flops_hybrid.flash_causal_kernels(
        **mod.flash_causal_shapes(ctx["cfg"], ctx["traffic"]), pairs_share=1.0)
    events = [_ev(f"{k}.1", i, 1e3 * flops.least_seconds(
        v["ops"], v["bytes"], ctx["peaks"])[0], op="custom-call")
        for i, (k, v) in enumerate(full.items())]
    ctx = _ctx(events, steps=1, laps=[_lap(attn_pairs_share=1.0)])
    assert _reader("flash_causal_roofline")(ctx) == pytest.approx(100.0)
