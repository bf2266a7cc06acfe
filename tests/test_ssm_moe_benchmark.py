"""The analytic counts, the configuration file, the per-layer readers and a CPU
rehearsal of the cell ``nemotron3_nano_30b_a3b.fit_seg16k`` at a toy size; its
planted faults are in ``tests/test_ssm_moe_controls.py``, which takes ``TOY``
from here. A CPU run checks control flow, counts and agreement with the
reference; it yields no time, rate or utilisation."""

import json
import os
import time

import pytest

import _cells as cells
from _cells import ROOT, ev, json_of, load, reader, tree  # noqa: F401

CELL = "nemotron3_nano_30b_a3b.fit_seg16k"
NAME = "nemotron3_nano_30b_a3b"
TRAFFIC = "fit_seg16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TRACE_METRICS = ("ssd_ms_per_step", "ssd_roofline", "mamba_conv_ms_per_step")
COUNTER_METRICS = ("ssm_chunks_reset_share", "ssm_state_abs_max")
#: readers the benchmark had, whose layers this cell runs: its name is the
#: among their ``workloads`` (an accepted list may be appended to)
ACCEPTED_COUNTERS = ("experts_load_max_over_mean", "attn_pairs_share",
                     "attn_blocks_walked_share", "attn_blocks_masked_share")
ACCEPTED_TRACE = ("experts_ms_per_step", "flash_causal_ms_per_step",
                  "flash_causal_roofline")
ACCEPTED_START = ("start_import_s", "start_session_s", "start_state_init_s",
                  "start_first_batch_s", "start_first_steps_s",
                  "start_unaccounted_s")


def test_operations_per_token_count_required_work():
    from benchmark.harness import flops_hybrid, flops_ssm

    cfg = json_of("benchmark", "configs", NAME + ".json")
    traffic = json_of("benchmark", "traffic", TRAFFIC + ".json")
    mod = load(os.path.join(ROOT, "benchmark", "configs", NAME + ".py"))
    per_token = mod.flops_per_item(cfg, traffic)
    # the issue's hand count, forward: a Mamba layer 81M (55.4M in, 22.0M
    # out, 3.4M in the four products of the chunked scan), an expert layer
    # 48M (the shared expert 39.9M, the held experts 6 x 8/128 of 19.96M,
    # the router 0.69M), the head 88M, the attention layer over the
    # in-document pairs
    scan = flops_ssm.ssd_products_per_token(
        heads=64, head_dim=64, groups=8, state_size=128, chunk=128)
    assert scan == 2 * (128 * 8 * 128 + 128 * 64 * 64 + 2 * 64 * 64 * 128)
    mamba = 2 * 2688 * 10304 + scan + 2 * 4096 * 2688
    assert abs(mamba - 81e6) < 0.5e6
    experts = 2 * 2 * 2688 * (3712 + 6 * 8 / 128 * 1856)
    router = 2 * 2688 * 128
    assert abs(experts + router - 48e6) < 0.5e6
    head = 2 * 2688 * 16384
    share = flops_hybrid.in_document_pairs_share(traffic)
    assert 0.3 < share < 1.0
    attention = (2 * 2688 * (4096 + 2 * 256 + 4096)
                 + share * 16385 / 2 * 2 * 2 * 4096)
    forward = 3 * mamba + 3 * experts + attention + head
    assert per_token == pytest.approx(3 * forward + 3 * router)
    assert 1.6e9 < per_token < 2.0e9           # "about 1.8 GFLOP a token"
    assert 0.38 < 3 * 3 * mamba / per_token < 0.44   # "about 40%" in Mamba
    # a trained router has a backward pass
    with_router = mod.flops_per_item({**cfg, "train_router": True}, traffic)
    assert with_router - per_token == 2 * 3 * router
    work = flops_ssm.ssd_scan_work(**{k: v for k, v in mod.ssd_shapes(
        cfg, traffic).items() if k != "layers"})
    assert work["forward"]["ops"] == 16384 * scan
    assert work["backward"]["ops"] == 3 * work["forward"]["ops"]
    # x and y in bf16, B and C in bf16, dt in float32, a 2-MB state a chunk
    assert work["forward"]["bytes"] == (
        2 * 16384 * 4096 * 2 + 2 * 16384 * 1024 * 2 + 16384 * 64 * 4
        + 128 * 64 * 64 * 128 * 4)
    assert mod.ssd_shapes(cfg, traffic)["layers"] == 3
    assert mod.mamba_conv_width(cfg, traffic) == 6144


def test_configuration_file_states_the_catalog_and_its_cuts():
    bench = json_of("BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == NAME)
    cfg = json_of(conf["file"])
    assert conf["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                               "n_routed_experts", "vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 52
    assert len(cfg["published"]["hybrid_override_pattern"]) == 52
    assert cfg["published"]["hybrid_override_pattern"][:7] == \
        cfg["hybrid_override_pattern"] == "MEMEM*E"
    assert cfg["published"]["n_routed_experts"] == cfg["router_width"] == 128
    assert cfg["published"]["vocab_size"] == 131072 == cfg["vocab_size"] * 8
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 8]
    assert cfg["num_hidden_layers"] == 7
    assert cfg["routed_scaling_factor"] == 2.5
    assert cfg["mamba_num_heads"] * cfg["mamba_head_dim"] == 4096
    for key in ("deployment", "assumed", "check"):
        assert cfg[key], key
    for key in ("positional_embedding", "packed_documents", "time_step_limit",
                "router", "router_bias_update", "router_aux_loss",
                "router_without_exchange", "optimizer", "weights",
                "sigmoid_norm_eps"):
        assert key in cfg["assumed"], key
    assert "528,092,736" in cfg["deployment"] and "16" in cfg["deployment"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    traffic = json_of("benchmark", "traffic", TRAFFIC + ".json")
    assert {k: traffic[k] for k in (
        "feed", "num_docs", "doc_len_median", "doc_len_sigma", "doc_len_min",
        "doc_len_max", "zipf_exponent", "vocab_size", "eos_id", "seq_len",
        "per_chip_batch", "log_every", "warmup_steps", "trace_steps",
        "loss_band")} == {
        "feed": "tokens_lm_docs", "num_docs": 1024, "doc_len_median": 6000,
        "doc_len_sigma": 1.0, "doc_len_min": 64, "doc_len_max": 65536,
        "zipf_exponent": 1.0, "vocab_size": 16384, "eos_id": 0,
        "seq_len": 16384, "per_chip_batch": 1, "log_every": 5,
        "warmup_steps": 5, "trace_steps": 5, "loss_band": 0.5}
    for name in TRACE_METRICS + COUNTER_METRICS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "throughput"
        assert (metric["layer"], metric["source"]) == (
            ("ops", "device_trace") if name in TRACE_METRICS
            else ("step, model and ops", "program_counter"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    # the scan's reset share rises with the documents a window holds (and
    # attention's pairs fall); the state's size is a health reading
    better = {m["name"]: m["better"] for m in bench["per_layer"]}
    assert better["ssm_chunks_reset_share"] == "higher"
    assert better["ssm_state_abs_max"] == "lower"
    for name in ACCEPTED_COUNTERS + ACCEPTED_TRACE + ACCEPTED_START:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        # (appended once; a later cell's name may follow it)
        assert metric["workloads"].count(CELL) == 1, name
    if not os.path.exists(CATALOG):
        return
    row = next(json.loads(line) for line in open(CATALOG)
               if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
    assert conf["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert cfg[key] == value, key
        else:
            assert cfg["published"][key] == value, key


def test_the_built_model_has_the_parameters_the_file_counts():
    """528,092,736, reckoned from the model the configuration builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = json_of("benchmark", "configs", NAME + ".json")
    traffic = json_of("benchmark", "traffic", TRAFFIC + ".json")
    mod = load(os.path.join(ROOT, "benchmark", "configs", NAME + ".py"))
    model = mod.build(cfg, traffic)["model"]
    assert model.cfg.layout()[1:3] == (model.cfg.layer_types, 1)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), {"input_ids": jnp.zeros((1, 512), jnp.int32)}))
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    params = shapes["params"]
    assert count(params) == 528_092_736
    period = params["periods"]
    assert count(period["layer_0"]) == 38_744_896           # a Mamba layer
    assert count(period["layer_0"]["mixer"]["in_proj"]) == 2688 * 10304
    assert count(period["layer_5"]) == 23_399_040           # attention
    assert count(period["layer_1"]) == 100_125_312          # an expert layer
    assert "w_gate" not in period["layer_1"]["moe"]         # two matrices
    assert count(period["layer_1"]["moe"]["w_up"]) == 8 * 2688 * 1856
    assert count(period["layer_1"]["moe"]["shared_up"]) == 2688 * 3712
    assert count(params["token_embed"]) == count(params["lm_head"]) \
        == 2688 * 16384
    # every block has ONE norm
    for j in range(7):
        assert [k for k in period[f"layer_{j}"] if "norm" in k] == ["norm"]
    # the bias keeps the router's width: 128 a block, three blocks
    assert count(shapes["router_bias"]) == 3 * 128


TOY = {
    f"configs/{NAME}.json": dict(
        vocab_size=256, hidden_size=128, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, mamba_num_heads=4,
        mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=16,
        router_width=8, n_routed_experts=4, experts_held=[2, 4],
        num_experts_per_tok=2, moe_intermediate_size=64,
        moe_shared_expert_intermediate_size=128, compute_dtype="float32",
        num_hidden_layers=3, hybrid_override_pattern="M*E",
        check={"examples": 1, "loss_abs_tol": 1e-4, "grad_rel_tol": 1e-3,
               "term_weights": {"ssm_energy": 1.0, "scan_energy": 1.0,
                                "boundary_energy": 1.0,
                                "attention_energy": 1.0,
                                "experts_energy": 1.0, "expert_probe": 1.0},
               "held_share_floor": 0.0625, "why": "float32 on both sides"}),
    f"traffic/{TRAFFIC}.json": dict(
        num_docs=64, doc_len_median=40, doc_len_min=8, doc_len_max=200,
        vocab_size=256, seq_len=256, per_chip_batch=1, log_every=2,
        warmup_steps=2, trace_steps=2),
}


def test_the_cell_rehearses_at_a_toy_size(tree):
    from benchmark.harness import runner

    with cells.run_in(tree, step_s=cells.REHEARSAL_STEP_S):
        r = runner.measure(str(tree), CELL, seed=2 ** 31 + 7, seconds=0.5,
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
    assert r["facts"]["train_step"]["compiles"] == 1
    assert r["facts"]["train_step"]["aot"]
    # the step's counters reach step_metrics through the loss
    from distributeddeeplearningspark_tpu import telemetry
    laps = [e["metrics"] for e in telemetry.read_events(
        os.path.join(r["facts"]["out_dir"], "telemetry"))
        if e.get("kind") == "step_metrics"]
    for name in ("moe_load_max_over_mean", "moe_rows_held_share",
                 "router_bias_abs_max", "attn_pairs_share",
                 "ssm_chunks_reset_share", "ssm_state_abs_max"):
        assert all(name in lap for lap in laps), name
    assert laps[-1]["router_bias_abs_max"] > 0         # the step moved it
    assert all(0 < lap["ssm_chunks_reset_share"] <= 1 for lap in laps)
    assert all(lap["ssm_state_abs_max"] > 0 for lap in laps)
    # the two counters are metrics of a traced run
    share = r["metrics"]["ssm_chunks_reset_share"]["value"]
    assert 0 < share <= 100
    assert 0 < r["metrics"]["ssm_state_abs_max"]["value"] <= max(
        lap["ssm_state_abs_max"] for lap in laps)
    # ... and so are the accepted counters of the layers the cell shares
    # with its siblings (the six columns of the start read the ONE record a
    # process writes, which in a test process another test's fit may have
    # written already: tests/test_startup_spans.py holds them)
    for name in ACCEPTED_COUNTERS:
        assert name in r["metrics"], name
    assert 0 < r["metrics"]["attn_pairs_share"]["value"] < 100
    assert r["metrics"]["experts_load_max_over_mean"]["value"] >= 1
    # a CPU run has no device plane: the device-trace readers return nothing
    for name in TRACE_METRICS + ACCEPTED_TRACE + ("device_step_ms", "mfu"):
        assert name not in r["metrics"], name
    assert again["facts"]["feed"]["seed_cache_hit"] is True
    assert set(again["metrics"]) == {"throughput", "setup_s"}


def test_the_timed_forward_gives_the_terms_the_reference_gives(tree):
    """The energies read off the operators' outputs inside ``model.apply``
    (``step_parts``: the pass that gives the loss) are the reference's, and
    so is the float32 probe, where the reference's blocks of rows, queries
    and states do not divide the window; a run whose held share is under the
    floor compares as infinite."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearningspark_tpu.models.moe import BIAS_COLLECTION

    mod = load(str(tree / "benchmark" / "configs" / f"{NAME}.py"))
    ref = load(str(tree / "benchmark" / "reference" / f"{NAME}.py"))
    ref.QUERIES, ref.ROWS, ref.STATES = 32, 40, 7
    cfg = json.loads((tree / "benchmark" / "configs"
                      / f"{NAME}.json").read_text())
    traffic = json.loads((tree / "benchmark" / "traffic"
                          / f"{TRAFFIC}.json").read_text())
    built = mod.build(cfg, traffic)
    assert built["model"].cfg.train_router is False
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 256, (2, 96)), jnp.int32)
    segs = jnp.asarray(np.sort(rng.integers(0, 4, (2, 96)), axis=1), jnp.int32)
    batch = {"input_ids": ids, "segment_ids": segs}
    init = jax.jit(built["model"].init)
    variables = dict(init(jax.random.PRNGKey(0), batch))
    # (off their initial values: a zero bias and a unit D hide nothing)
    params = jax.tree.map(
        lambda a: a + jnp.asarray(0.05 * rng.normal(size=a.shape), a.dtype),
        variables.pop("params"))
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
    with pytest.raises(ValueError, match="one group"):
        mod.build({**cfg, "n_group": 8}, traffic)
    with pytest.raises(ValueError, match="no dense"):
        mod.build({**cfg, "hybrid_override_pattern": "MEM-M*E"}, traffic)


def test_every_leafs_gradient_is_the_references_in_float32(tree):
    """The program against ``benchmark/reference/nemotron3_nano_30b_a3b.py``
    on seeded weights: the loss and EVERY leaf's gradient, leaf by leaf (the
    harness compares the flattened gradient's norm)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    mod = load(str(tree / "benchmark" / "configs" / f"{NAME}.py"))
    ref = load(str(tree / "benchmark" / "reference" / f"{NAME}.py"))
    ref.STATES = 16
    cfg = json.loads((tree / "benchmark" / "configs"
                      / f"{NAME}.json").read_text())
    cfg["train_router"] = True    # the routers' leaves get a gradient too
    cfg.update(num_hidden_layers=5, hybrid_override_pattern="MEM*E")
    built = mod.build(cfg, {"vocab_size": 256, "seq_len": 64})
    rng = np.random.default_rng(1)
    batch = {"input_ids": jnp.asarray(rng.integers(1, 256, (2, 64)),
                                      jnp.int32),
             "segment_ids": jnp.asarray(
                 np.sort(rng.integers(0, 3, (2, 64)), axis=1), jnp.int32)}
    init = jax.jit(built["model"].init)
    variables = dict(init(jax.random.PRNGKey(3), batch))
    params = variables.pop("params")

    def program(p):
        out = built["model"].apply({"params": p, **variables}, batch)
        return built["loss"](out, batch)[0]

    got_loss, got = jax.jit(jax.value_and_grad(program))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.training_loss(p, variables, batch, cfg)))(params)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    leaves = jax.tree_util.tree_leaves_with_path(want)
    assert len(leaves) == len(jax.tree.leaves(got)) == 38
    for (path, b), a in zip(leaves, jax.tree.leaves(got)):
        norm = float(jnp.linalg.norm(b))
        assert norm > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) / norm < 3e-5, \
            jax.tree_util.keystr(path)


def test_a_program_without_the_layer_is_refused_with_a_message(
        tree, monkeypatch):
    """What the parent commit does with the new cell: it fails at once,
    before a device or the feed is touched."""
    import sys

    from benchmark.harness import runner

    # (what the import system says of a module that is not there)
    monkeypatch.setitem(sys.modules,
                        "distributeddeeplearningspark_tpu.ops.ssd", None)
    with cells.run_in(tree), pytest.raises(runner.Refused) as e:
        runner.measure(str(tree), CELL, seed=3, seconds=0.5, trace=False,
                       t_process=time.perf_counter(), master="local[1]")
    assert "state-space" in e.value.args[0][0]
    assert not (tree / "benchmark" / ".cache").exists()   # no feed was built


#: the scan's loops as the chip's compiler names their results (cut from the
#: step compiled for a described v5e; layouts stripped as the extract does):
#: a forward pass, a replayed forward pass (it keeps the states handed to the
#: groups), a backward pass, the masks' predicates hoisted out of all of
#: them, the inner loop that passes states inside a group, and two loops of
#: the fused head loss that are nobody's
FORWARD = ("(s32[], f32[1,8,8,64,128], bf16[16,1,8,128,8,8,64], "
           "bf16[16,1,8,128,8,8,64], f32[16,1,8,128,8,8], "
           "/*index=5*/bf16[16,1,8,128,8,128], bf16[16,1,8,128,8,128])")
REPLAY = ("(s32[], f32[1,8,8,64,128], bf16[16,1,8,128,8,8,64], "
          "f32[16,1,8,8,64,128], bf16[16,1,8,128,8,8,64], "
          "/*index=5*/f32[16,1,8,128,8,8], bf16[16,1,8,128,8,128])")
BACKWARD = ("(s32[], f32[1,8,8,64,128], f32[8,8], f32[8,8], "
            "bf16[16,1,8,128,8,8,64], /*index=5*/f32[16,1,8,128,8,8], "
            "bf16[16,1,8,128,8,128], bf16[16,1,8,128,8,128])")
MASKS = ("(s32[], pred[16,1,8,128,8,8], pred[16,1,8,128,8,8], "
         "pred[16,1,8,8,8,128,128], pred[16,1,8,128])")
INNER = ("(s32[], f32[1,8,8,64,128], bf16[8,1,8,8,64,128], f32[8,1,8,8], "
         "f32[8,1,8,8,64,128], /*index=5*/s32[])")
HEAD_LOSS = ("(s32[], f32[16383], f32[16383], bf16[16,2688,1024], "
             "pred[16,1024], /*index=5*/bf16[16383,2688])")


def _a_step(t0, scale=1.0):
    """One step's loops of three Mamba layers: forward, then replay and
    backward, each pass ``scale`` ms; the inner loops inside theirs."""
    events, t = [ev("while.483", t0, 0.5 * scale, "while", MASKS)], t0 + 1
    for kind in (FORWARD,) * 3 + (REPLAY, BACKWARD) * 3:
        events.append(ev("while.1", t, scale, "while", kind))
        events.append(ev("while.2", t + 0.1 * scale, 0.2 * scale, "while",
                         INNER))
        t += 2 * scale
    events.append(ev("while.493", t, 5.0, "while", HEAD_LOSS))
    return events


def test_stage_readers_find_the_scan_by_its_loops_and_the_conv_by_shape():
    events = _a_step(0.0) + _a_step(100.0) + [
        ev("slice_convert_fusion.1", 200, 1, result="f32[1,16384,6144]"),
        ev("multiply_convert_fusion.1", 201, 2,
           result="(bf16[1,16384,6144], f32[1,16384,6144])"),
        ev("fusion.5", 203, 3,
           result="(f32[6144,1], f32[6144,1], f32[6144,1], f32[6144,1])"),
        ev("fusion.6", 206, 4, result="bf16[16384,6144]"),
        # nobody's: the input projection, the taps' optimizer, the block
        ev("convolution.1", 210, 9, result="bf16[16384,10304]"),
        ev("fusion.7", 219, 9, result="(f32[1,6144,4], f32[1,6144,4])"),
        ev("fusion.8", 228, 9, result="bf16[1,16384,2688]"),
        ev("fusion.9", 237, 9, result="bf16[1,16384,4096]"),
    ]
    ctx = cells.ctx(CELL, events)
    # nine passes of 1 ms and the masks' loop of 0.5 ms a step
    assert reader("ssd_ms_per_step")(ctx) == pytest.approx(9.5)
    facts = ctx["facts"]["ssd_ms_per_step"]
    assert facts["forward_loops_per_step"] == 6
    assert facts["backward_loops_per_step"] == 3
    assert facts["masks_loops_per_step"] == 1
    assert reader("mamba_conv_ms_per_step")(ctx) == pytest.approx(10 / 2)
    assert ctx["facts"]["mamba_conv_ms_per_step"]["events_per_step"] == 2
    # however many chunks the program puts in a group: 32 groups of 4 here
    regrouped = cells.ctx(CELL, [ev("while.1", 0, 3, "while", kind.replace(
        "[16,1,8,", "[32,1,4,")) for kind in (FORWARD, REPLAY, BACKWARD,
                                              MASKS, INNER)], steps=1)
    assert reader("ssd_ms_per_step")(regrouped) == pytest.approx(3.0)
    assert regrouped["facts"]["ssd_ms_per_step"] == {
        "forward_loops_per_step": 2, "backward_loops_per_step": 1,
        "masks_loops_per_step": 1, "forward_ms": 3.0, "backward_ms": 3.0,
        "masks_ms": 3.0, "kernels_per_step": 0, "kernels_ms": 0.0}
    # a kernel that runs the scan, or part of it, is found by its name,
    # which starts ``ssd_``: its time is the stage's; its executions the
    # share cannot count yet, and says so
    kernels = [ev("ssd_bwd_chunk.1", 50, 2, "custom-call", "bf16[1,16384]"),
               ev("ssd_fwd.2", 60, 1, "custom-call", "bf16[1,16384]")]
    with_kernels = cells.ctx(CELL, _a_step(0.0) + kernels, steps=1)
    assert reader("ssd_ms_per_step")(with_kernels) == pytest.approx(12.5)
    assert with_kernels["facts"]["ssd_ms_per_step"]["kernels_ms"] == 3.0
    assert reader("ssd_roofline")(with_kernels) is None
    assert with_kernels["facts"]["ssd_roofline"]["kernels"] == 2
    assert reader("ssd_ms_per_step")(cells.ctx(CELL, kernels, steps=1)) == \
        pytest.approx(3.0)
    # a program without the scan (the parent): nothing, no raise
    bare = cells.ctx(CELL, [ev("fusion.1", 0, 5, result="bf16[32,512,768]")])
    for name in ("ssd_ms_per_step", "ssd_roofline"):
        assert reader(name)(bare) is None, name
    for name in TRACE_METRICS:
        assert reader(name)({**bare, "trace": None}) is None, name
    # ... and in a cell of another configuration
    other = {**cells.ctx(CELL, events), "cell": {"config_mod": object()}}
    for name in TRACE_METRICS:
        assert reader(name)(other) is None, name
    # the counters: mean and largest over the window's laps, or nothing
    laps = [{"metrics": {"ssm_chunks_reset_share": s, "ssm_state_abs_max": m}}
            for s, m in ((0.0078125, 3.0), (0.015625, 5.0), (0.0234375, 4.0))]
    ctx = cells.ctx(CELL, [], laps=laps)
    assert reader("ssm_chunks_reset_share")(ctx) == pytest.approx(1.5625)
    assert reader("ssm_state_abs_max")(ctx) == 5.0
    for name in COUNTER_METRICS:
        assert reader(name)(cells.ctx(
            CELL, [], laps=[{"metrics": {"loss": 1.0}}])) is None, name


def test_the_accepted_readers_find_this_cells_experts_and_flash_kernels():
    """The readers ``lfm2_24b_a2b.fit_seg32k`` brought serve this cell as
    they are (its ``.py`` gives them ``router_width`` through the file and
    ``flash_causal_shapes``): the grouped products and anything of 16,384 x
    6 rows, the three flash kernels by name, and their roofline on the
    in-document pairs the step counted."""
    events = [
        ev("ragged-dot.1", 0, 4, "ragged-dot", "bf16[98304,1856]"),
        ev("fusion.2", 4, 2, result="bf16[98304,2688]"),
        ev("fusion.3", 6, 1, result="f32[16384,128]"),
        ev("flash_fwd.1", 10, 30, "custom-call",
           "(bf16[32,16384,128], f32[32,16384,8])"),
        ev("flash_bwd_dq.1", 40, 24, "custom-call", "bf16[32,16384,128]"),
        ev("flash_bwd_dkv.1", 64, 30, "custom-call",
           "(bf16[2,16384,128], bf16[2,16384,128])"),
        # nobody's: a Mamba layer's B and C, the scan's loop
        ev("fusion.4", 100, 9, result="bf16[1,16384,8,128]"),
        ev("while.1", 110, 9, "while", FORWARD),
    ]
    laps = [{"metrics": {"attn_pairs_share": 0.4}}]
    ctx = cells.ctx(CELL, events, steps=1, laps=laps)
    assert reader("experts_ms_per_step")(ctx) == pytest.approx(7.0)
    assert reader("flash_causal_ms_per_step")(ctx) == pytest.approx(84.0)
    share = reader("flash_causal_roofline")(ctx)
    assert 0 < share < 100
    # (at the chip's own times, which these are, the kernels stand near a
    # quarter of their roofline)
    assert share == pytest.approx(24.0, abs=3.0)
    # without the step's counter the share has nothing to stand on
    assert reader("flash_causal_roofline")(
        cells.ctx(CELL, events, steps=1)) is None


def test_the_roofline_stays_under_100_and_refuses_a_partial_stage():
    from benchmark.harness import flops, flops_ssm

    ctx = cells.ctx(CELL, [])
    mod = ctx["cell"]["config_mod"]
    shapes = mod.ssd_shapes(ctx["cfg"], ctx["traffic"])
    layers = shapes.pop("layers")
    work = flops_ssm.ssd_scan_work(**shapes)
    least = {k: flops.least_seconds(v["ops"], v["bytes"], ctx["peaks"])
             for k, v in work.items()}

    def a_step(t0, slow):
        events, t = [], t0
        for kind in (FORWARD,) * layers + (REPLAY, BACKWARD) * layers:
            ms = slow * 1e3 * least[
                "backward" if kind is BACKWARD else "forward"][0]
            events.append(ev("while.1", t, ms, "while", kind))
            t += ms
        return events, t

    # every pass at four times its least time: 25%
    events, _ = a_step(0.0, 4.0)
    ctx = cells.ctx(CELL, events, steps=1)
    assert reader("ssd_roofline")(ctx) == pytest.approx(25.0)
    # the masks' loop is time of the stage and no execution: it reads LOWER
    ctx = cells.ctx(CELL, events + [ev("while.483", 1e4, 1.0, "while",
                                       MASKS)], steps=1)
    assert reader("ssd_roofline")(ctx) < 25.0
    # a scan AT its roofline reads 100, not more
    events, _ = a_step(0.0, 1.0)
    assert reader("ssd_roofline")(cells.ctx(CELL, events, steps=1)) == \
        pytest.approx(100.0)
    # a trace that lacks one layer's backward pass: a share of what was
    # found would read HIGH; it gives nothing, and says why
    partial = [e for e in events if e[3]["result"] != BACKWARD[:120]][:-1] + [
        e for e in events if e[3]["result"] == BACKWARD[:120]][:2]
    ctx = cells.ctx(CELL, partial, steps=1)
    assert reader("ssd_roofline")(ctx) is None
    assert "refused" in ctx["facts"]["ssd_roofline"]
    # two steps traced, the loops of one: nothing either
    assert reader("ssd_roofline")(cells.ctx(CELL, events, steps=2)) is None


def test_the_readers_on_an_extract_cut_from_the_chips_trace():
    """``tests/fixtures/ssd_loops_extract.json.gz``: the loops and the
    convolution's ops of two traced steps of the cell on the chip (my chip
    run, PR 37). The readers find two forward loops and one backward loop a
    layer and step, the masks' loop once a step, none of the inner loops or
    the head loss's; the share is of the required work and under 100; with
    one backward loop cut out of the extract the share is refused."""
    import gzip

    with gzip.open(os.path.join(ROOT, "tests", "fixtures",
                                "ssd_loops_extract.json.gz"), "rt") as f:
        extract = json.load(f)
    ctx = {**cells.ctx(CELL, []), "trace": extract}
    ms = reader("ssd_ms_per_step")(ctx)
    facts = ctx["facts"]["ssd_ms_per_step"]
    assert (facts["forward_loops_per_step"], facts["backward_loops_per_step"],
            facts["masks_loops_per_step"]) == (6, 3, 1)
    assert 55 < ms < 70                                # 61.4 over five steps
    assert ms == pytest.approx(facts["forward_ms"] + facts["backward_ms"]
                               + facts["masks_ms"], rel=1e-6)
    share = reader("ssd_roofline")(ctx)
    assert 10 < share < 18                             # 13.7 over five steps
    # 2 x 0.743 + 1.321 ms a layer, three layers, over the stage's time
    assert share == pytest.approx(100 * 3 * (2 * 0.74258 + 1.32128) / ms,
                                  rel=1e-3)
    conv = reader("mamba_conv_ms_per_step")(ctx)
    assert 40 < conv < 55                              # 47.0 over five steps
    assert ctx["facts"]["mamba_conv_ms_per_step"]["events_per_step"] == 99
    events = extract["devices"]["0"]["XLA Ops"]
    backward = next(e for e in events if "f32[8,8], f32[8,8]" in
                    e[3].get("result", ""))
    partial = {**extract, "devices": {"0": {"XLA Ops": [
        e for e in events if e is not backward]}}}
    ctx = {**cells.ctx(CELL, []), "trace": partial}
    assert reader("ssd_roofline")(ctx) is None
    assert ctx["facts"]["ssd_roofline"]["backward_loops"] == 5
    assert reader("ssd_ms_per_step")(ctx) is not None   # the time is read
