"""The analytic counts, the configuration file, the per-layer readers and a CPU
rehearsal of the cell ``lfm2_24b_a2b.fit_seg32k`` at a toy size; its planted
faults are in ``tests/test_hybrid_moe_controls.py``, which takes ``TOY`` from
here. A CPU run checks control flow, counts and agreement with the reference;
it yields no time, rate or utilisation."""

import json
import os
import time

import pytest

import _cells as cells
from _cells import ROOT, ev, json_of, lap, load, reader, tree  # noqa: F401

CELL = "lfm2_24b_a2b.fit_seg32k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("shortconv_ms_per_step", "shortconv_roofline",
               "flash_causal_ms_per_step", "flash_causal_roofline",
               "experts_ms_per_step", "experts_load_max_over_mean",
               "attn_pairs_share", "attn_blocks_walked_share")


def test_operations_per_token_count_required_work():
    from benchmark.harness import flops_hybrid

    cfg = json_of("benchmark", "configs", "lfm2_24b_a2b.json")
    traffic = json_of("benchmark", "traffic", "fit_seg32k.json")
    mod = load(os.path.join(ROOT, "benchmark", "configs", "lfm2_24b_a2b.py"))
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
    bench = json_of("BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == "lfm2_24b_a2b")
    cfg = json_of(conf["file"])
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
        # serve the state-space decoder's cell too, appended by PR 37, and
        # the sliding-window decoder's, appended by PR 39)
        assert metric["workloads"] == [CELL] + (
            [] if name.startswith("shortconv_")
            else ["nemotron3_nano_30b_a3b.fit_seg16k",
                  "laguna_xs2.fit_swa16k"])
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


TOY = {
    "configs/lfm2_24b_a2b.json": dict(
        vocab_size=256, hidden_size=128, intermediate_size=192,
        layer_types=["conv", "full_attention", "conv"], num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        router_width=8, num_experts=4, experts_held=[2, 4],
        num_experts_per_tok=2, moe_intermediate_size=64,
        compute_dtype="float32",
        # ten times the cell's rate. The controls' run is 242 steps
        # (``_cells.STEP_S``), each of which moves the bias; on the wall
        # clock it was the machine's speed, 8 steps on a loaded one (0.0089
        # against the 0.01 asked of a fault). The term grows by 1.1 a unit of
        # |b|: at 0.01 a step, 8 steps give 0.09, and the bias stops near
        # |b| 0.3 (the load is even)
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


def test_the_cell_rehearses_at_a_toy_size(tree):
    from benchmark.harness import runner

    with cells.run_in(tree, step_s=cells.REHEARSAL_STEP_S):
        r = runner.measure(str(tree), CELL, seed=2 ** 31 + 7, seconds=1.0,
                           trace=True, t_process=time.perf_counter(),
                           master="local[1]")
        again = runner.measure(str(tree), CELL, seed=2 ** 31 + 7,
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

    mod = load(str(tree / "benchmark" / "configs" / "lfm2_24b_a2b.py"))
    ref = load(str(tree / "benchmark" / "reference" / "lfm2_24b_a2b.py"))
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
    init = jax.jit(built["model"].init)
    variables = dict(init(jax.random.PRNGKey(0), batch))
    params = variables.pop("params")
    mutable = {BIAS_COLLECTION: jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape) * 0.05, jnp.float32),
        variables[BIAS_COLLECTION])}
    got = jax.jit(lambda p, m, b: mod.parts(built["model"], built["loss"],
                                            p, m, b))(params, mutable, batch)
    want = jax.jit(lambda p, m, b: ref.parts(p, m, b, cfg))(params, mutable,
                                                            batch)
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

    old = text.packed_token_windows
    monkeypatch.setattr(
        text, "packed_token_windows",
        lambda docs, *, seq_len, eos_id=None, num_partitions=1, key="tokens":
        old(docs, seq_len=seq_len, eos_id=eos_id,
            num_partitions=num_partitions, key=key))
    with cells.run_in(tree), pytest.raises(runner.Refused) as e:
        runner.measure(str(tree), CELL, seed=3, seconds=0.5, trace=False,
                       t_process=time.perf_counter(), master="local[1]")
    assert "segment ids" in e.value.args[0][0]


def test_stage_readers_find_kernels_by_name_and_xla_stages_by_shape():
    events = [
        ev("while.1", 0, 100, op="while", result="(s32[], bf16[131072,2048])"),
        ev("shortconv_fwd.1", 0, 1, op="custom-call",
           result="bf16[1,32768,2048]"),
        ev("shortconv_bwd.2", 1, 3, op="custom-call",
           result="(bf16[1,32768,6144], f32[1,8,2048])"),
        ev("flash_fwd.1", 10, 20, op="custom-call"),
        ev("flash_bwd_dq.1", 30, 30, op="custom-call"),
        ev("flash_bwd_dkv.1", 60, 40, op="custom-call"),
        ev("ragged-dot-none.2", 100, 3, op="custom-call",
           result="bf16[131072,1536]"),
        ev("fusion.9", 103, 4, result="bf16[131072,2048]"),
        ev("fusion.10", 107, 1, result="f32[32768,64]"),
        ev("fusion.12", 108, 2, result="(f32[32768,4], s32[32768,4])"),
        ev("fusion.11", 120, 9, result="bf16[1,32768,2048]"),  # nobody's
    ]
    ctx = cells.ctx(CELL, events)
    assert reader("shortconv_ms_per_step")(ctx) == pytest.approx(4 / 2)
    assert reader("flash_causal_ms_per_step")(ctx) == pytest.approx(90 / 2)
    assert reader("experts_ms_per_step")(ctx) == pytest.approx(10 / 2)
    # a program without kernels or counters (the parent): nothing, no raise
    bare = cells.ctx(CELL, [ev("fusion.1", 0, 5, result="bf16[32,512,768]")])
    for name in NEW_METRICS:
        if name != "experts_ms_per_step":
            assert reader(name)(bare) is None, name
    for name in NEW_METRICS:
        assert reader(name)({**bare, "trace": None}) is None, name
    # ... and in a cell of another configuration
    other = {**cells.ctx(CELL, events), "cfg": {"num_experts_per_tok": 8},
             "cell": {"config_mod": object()}}
    for name in ("flash_causal_ms_per_step", "flash_causal_roofline",
                 "shortconv_roofline", "experts_ms_per_step",
                 "experts_load_max_over_mean"):
        assert reader(name)(other) is None, name


def test_rooflines_count_executions_from_the_trace_and_stay_under_100():
    from benchmark.harness import flops, flops_hybrid

    ctx = cells.ctx(CELL, [])
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
            events.append(ev(f"{name}.1", t, ms, op="custom-call"))
            t += ms
    laps = [lap(attn_pairs_share=0.3, moe_load_max_over_mean=2.0,
                router_bias_abs_max=0.01, attn_blocks_walked_share=0.35,
                attn_blocks_masked_share=0.25),
            lap(attn_pairs_share=0.5, moe_load_max_over_mean=4.0,
                router_bias_abs_max=0.02, attn_blocks_walked_share=0.55,
                attn_blocks_masked_share=0.45)]
    ctx = cells.ctx(CELL, events, steps=1, laps=laps)
    assert reader("shortconv_roofline")(ctx) == pytest.approx(25.0)
    assert reader("flash_causal_roofline")(ctx) == pytest.approx(25.0)
    assert ctx["facts"]["shortconv_roofline"]["shortconv_fwd"][
        "runs_per_step"] == 8
    assert ctx["facts"]["flash_causal_roofline"][
        "pairs_share_of_the_laps"] == pytest.approx(0.4)
    assert reader("attn_pairs_share")(ctx) == pytest.approx(40.0)
    assert reader("attn_blocks_walked_share")(ctx) == pytest.approx(45.0)
    assert ctx["facts"]["attn_blocks_walked_share"] == {
        "laps": 2, "min": 0.35, "max": 0.55}
    assert reader("attn_blocks_masked_share")(ctx) == pytest.approx(35.0)
    assert ctx["facts"]["attn_blocks_masked_share"] == {
        "laps": 2, "min": 0.25, "max": 0.45}
    # a program that lacks the counter (the parent) gives nothing
    assert reader("attn_blocks_masked_share")(cells.ctx(
        CELL, events, steps=1, laps=[lap(attn_pairs_share=0.3)])) is None
    assert reader("experts_load_max_over_mean")(ctx) == pytest.approx(3.0)
    # a window packed with one document: the whole causal triangle is
    # required, and a kernel AT its roofline reads 100, not more
    full = flops_hybrid.flash_causal_kernels(
        **mod.flash_causal_shapes(ctx["cfg"], ctx["traffic"]), pairs_share=1.0)
    events = [ev(f"{k}.1", i, 1e3 * flops.least_seconds(
        v["ops"], v["bytes"], ctx["peaks"])[0], op="custom-call")
        for i, (k, v) in enumerate(full.items())]
    ctx = cells.ctx(CELL, events, steps=1, laps=[lap(attn_pairs_share=1.0)])
    assert reader("flash_causal_roofline")(ctx) == pytest.approx(100.0)
