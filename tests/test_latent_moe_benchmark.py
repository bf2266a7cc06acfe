"""The analytic counts, the configuration file, the per-layer readers and a CPU
rehearsal of the cell ``joyai_llm_flash.fit_s16k`` at a toy size; its planted
faults are in ``tests/test_latent_moe_controls.py``, which takes ``TOY`` from
here. A CPU run checks control flow, counts and agreement with the reference;
it yields no time, rate or utilisation."""

import json
import os
import time

import pytest

import _cells as cells
from _cells import ROOT, ev, json_of, load, reader, tree  # noqa: F401

CELL = "joyai_llm_flash.fit_s16k"
NAME = "joyai_llm_flash"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("mla_attn_ms_per_step", "mla_attn_roofline",
               "mla_proj_ms_per_step")


def test_operations_per_token_count_required_work():
    from benchmark.harness import flops_mla

    cfg = json_of("benchmark", "configs", NAME + ".json")
    traffic = json_of("benchmark", "traffic", "fit_s16k.json")
    mod = load(os.path.join(ROOT, "benchmark", "configs", NAME + ".py"))
    per_token = mod.flops_per_item(cfg, traffic)
    # the issue's hand count: 618M of matrix products outside attention,
    # 1,007M in attention (6 blocks x S/2 x 32 heads x 2 x (192 + 128)),
    # times 3: 4.87 GFLOP, 62% of it in the latent-attention kernels
    attention = 6 * (16384 + 1) / 2 * 32 * 2 * (192 + 128)
    assert abs(attention - 1007e6) < 1e6
    with_router = mod.flops_per_item({**cfg, "train_router": True}, traffic)
    assert abs(with_router / 3 - attention - 618e6) < 2e6
    assert abs(per_token - 4.87e9) < 0.02e9
    assert 0.61 < 3 * attention / per_token < 0.63
    # the share's router is not trained: five routers' products, no backward
    assert with_router - per_token == 2 * (5 * 2 * 2048 * 256)
    # the module is required work: without it one block and one head pass less
    without = mod.flops_per_item({**cfg, "num_nextn_predict_layers": 0},
                                 traffic)
    proj, attend = flops_mla.latent_attention_flops_per_token(
        hidden_size=2048, heads=32, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        seq_len=16384)
    assert abs(proj - 2 * 26.3e6) < 0.2e6       # 26.3M of attention a layer
    experts = 3 * 2 * 2048 * 768 * (1 + 8 * 8 / 256)
    assert per_token - without == pytest.approx(
        3 * (proj + attend + experts + 2 * 2048 * 16160 + 2 * 4096 * 2048)
        + 2 * 2048 * 256)
    kernels = flops_mla.mla_attn_kernels(**mod.mla_attn_shapes(cfg, traffic))
    pairs = 32 * 16384 * 16385 / 2
    assert kernels["flash_fwd"]["ops"] == 2 * pairs * (192 + 128)
    assert kernels["flash_bwd_dq"]["ops"] == 2 * pairs * (2 * 192 + 128)
    assert kernels["flash_bwd_dkv"]["ops"] == 2 * pairs * (2 * 192 + 2 * 128)
    # q, k at 192 and v, o at 128, once each, in bf16: 0.34 GB forward
    assert kernels["flash_fwd"]["bytes"] == 16384 * 32 * 2 * (2 * 192 + 2 * 128)
    # the two backward kernels each make the scores again (FlashAttention-2):
    # nine products a layer where the model counts six
    assert sum(k["ops"] for k in kernels.values()) == \
        2 * pairs * (5 * 192 + 4 * 128)


def test_configuration_file_states_the_catalog_and_its_cuts():
    bench = json_of("BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == NAME)
    cfg = json_of(conf["file"])
    assert sorted(conf["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                       "vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["published"]["n_routed_experts"] == cfg["router_width"] == 256
    assert cfg["published"]["vocab_size"] == 129280 == cfg["vocab_size"] * 8
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 8]
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["qk_head_dim"] == (cfg["qk_nope_head_dim"]
                                  + cfg["qk_rope_head_dim"]) == 192
    assert cfg["routed_scaling_factor"] == 2.5 and cfg["v_head_dim"] == 128
    for key in ("deployment", "assumed", "check"):
        assert cfg[key], key
    for key in ("mtp_loss_weight", "mtp_concatenation_order", "router",
                "router_bias_update", "router_without_exchange", "optimizer",
                "weights", "sigmoid_norm_eps"):
        assert key in cfg["assumed"], key
    assert "491.7M" in cfg["deployment"] and "32" in cfg["deployment"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "fit_s16k"
    traffic = json_of("benchmark", "traffic", "fit_s16k.json")
    assert {k: traffic[k] for k in (
        "feed", "num_docs", "doc_len_median", "doc_len_sigma", "doc_len_min",
        "doc_len_max", "zipf_exponent", "vocab_size", "eos_id", "seq_len",
        "per_chip_batch", "log_every", "warmup_steps", "trace_steps",
        "loss_band")} == {
        "feed": "tokens_lm", "num_docs": 1024, "doc_len_median": 6000,
        "doc_len_sigma": 1.0, "doc_len_min": 64, "doc_len_max": 65536,
        "zipf_exponent": 1.0, "vocab_size": 16160, "eos_id": 0,
        "seq_len": 16384, "per_chip_batch": 1, "log_every": 5,
        "warmup_steps": 5, "trace_steps": 5, "loss_band": 0.5}
    for name in NEW_METRICS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "throughput"
        assert metric["layer"] == "ops" and metric["source"] == "device_trace"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    if not os.path.exists(CATALOG):
        return
    row = next(json.loads(line) for line in open(CATALOG)
               if '"JoyAI-LLM-Flash"' in line)
    assert conf["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert cfg[key] == value, key


def test_the_built_model_has_the_parameters_the_file_counts():
    """491.7M, reckoned from the model the configuration builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = json_of("benchmark", "configs", NAME + ".json")
    traffic = json_of("benchmark", "traffic", "fit_s16k.json")
    mod = load(os.path.join(ROOT, "benchmark", "configs", NAME + ".py"))
    model = mod.build(cfg, traffic)["model"]
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), {"input_ids": jnp.zeros((1, 512), jnp.int32)}))
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    params = shapes["params"]
    assert count(params) == 491_696_128
    assert count(params["lead_0"]["self_attn"]) == 26_347_520
    assert count(params["lead_0"]) == 70_391_808            # the dense layer
    assert count(params["periods"]) == 4 * 69_343_232       # an expert layer
    assert count(params["mtp"]) == 77_737_984
    assert count(params["token_embed"]) == count(params["lm_head"]) \
        == 2048 * 16160
    # the bias keeps the router's width: 256 a block, five blocks
    assert count(shapes["router_bias"]) == 5 * 256


TOY = {
    f"configs/{NAME}.json": dict(
        vocab_size=256, hidden_size=128, intermediate_size=192,
        num_hidden_layers=3, num_attention_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, router_width=8, n_routed_experts=4, experts_held=[2, 4],
        num_experts_per_tok=2, moe_intermediate_size=64,
        compute_dtype="float32",
        check={"examples": 1, "loss_abs_tol": 1e-4, "grad_rel_tol": 1e-3,
               "grad_leaf_weights": {"^mtp/": 10},
               "term_weights": {"attention_energy": 1.0,
                                "experts_energy": 1.0, "latents_spread": 1.0,
                                "expert_probe": 1.0},
               "held_share_floor": 0.0625, "why": "float32 on both sides"}),
    "traffic/fit_s16k.json": dict(
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
    # full causal windows and nothing else: no segment ids in this cell
    assert r["facts"]["executable"]["batch_arrays"] == {"input_ids": [1, 256]}
    assert r["facts"]["train_step"]["compiles"] == 1
    assert r["facts"]["train_step"]["aot"]
    # the step's counters reach step_metrics through the loss
    from distributeddeeplearningspark_tpu import telemetry
    laps = [e["metrics"] for e in telemetry.read_events(
        os.path.join(r["facts"]["out_dir"], "telemetry"))
        if e.get("kind") == "step_metrics"]
    for name in ("lm_loss", "mtp_nll", "moe_load_max_over_mean",
                 "moe_rows_held_share", "router_bias_abs_max"):
        assert all(name in lap for lap in laps), name
    assert laps[-1]["router_bias_abs_max"] > 0         # the step moved it
    assert laps[-1]["loss"] == pytest.approx(
        laps[-1]["lm_loss"] + 0.1 * laps[-1]["mtp_nll"], rel=1e-5)
    # one block of 256 x 256, on the diagonal: it is walked, and masked
    assert laps[-1]["attn_blocks_masked_share"] == 1.0
    assert r["metrics"]["attn_blocks_masked_share"]["value"] == 100.0
    # a CPU run has no device plane: the device-trace readers return nothing
    for name in NEW_METRICS + ("device_step_ms", "mfu"):
        assert name not in r["metrics"], name
    assert again["facts"]["feed"]["seed_cache_hit"] is True
    assert set(again["metrics"]) == {"throughput", "setup_s"}


def test_the_timed_forward_gives_the_terms_the_reference_gives(tree):
    """The energies read off the operators' outputs inside ``model.apply``
    (``step_parts``: the pass that gives the loss) are the reference's, and
    so is the float32 probe; with segment ids too, and where the reference's
    blocks of queries and rows do not divide the module's S - 1 rows; a run
    whose held share is under the floor compares as infinite."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearningspark_tpu.models.moe import BIAS_COLLECTION

    mod = load(str(tree / "benchmark" / "configs" / f"{NAME}.py"))
    ref = load(str(tree / "benchmark" / "reference" / f"{NAME}.py"))
    ref.QUERIES, ref.ROWS = 32, 40
    cfg = json.loads((tree / "benchmark" / "configs"
                      / f"{NAME}.json").read_text())
    traffic = json.loads((tree / "benchmark" / "traffic"
                          / "fit_s16k.json").read_text())
    built = mod.build(cfg, traffic)
    assert built["model"].cfg.train_router is False
    assert built["model"].cfg.layout()[2] == 2      # one scan of two periods
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 256, (2, 96)), jnp.int32)
    segs = jnp.asarray(np.sort(rng.integers(0, 4, (2, 96)), axis=1), jnp.int32)
    init = jax.jit(built["model"].init)
    variables = dict(init(jax.random.PRNGKey(0), {"input_ids": ids}))
    params = variables.pop("params")
    mutable = {BIAS_COLLECTION: jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape) * 0.05, jnp.float32),
        variables[BIAS_COLLECTION])}
    program = jax.jit(lambda p, m, b: mod.parts(built["model"],
                                                built["loss"], p, m, b))
    reference = jax.jit(lambda p, m, b: ref.parts(p, m, b, cfg))
    for batch in ({"input_ids": ids}, {"input_ids": ids, "segment_ids": segs}):
        got = program(params, mutable, batch)
        want = reference(params, mutable, batch)
        assert set(got) - set(want) == {"rows_held_share"}
        assert set(cfg["check"]["term_weights"]) == set(want) - {
            "loss", "lm_loss", "mtp_nll"}
        for name, value in want.items():
            assert float(value) > 0
            assert float(got[name]) == pytest.approx(float(value),
                                                     rel=2e-5), name
        assert float(got["loss"]) == pytest.approx(
            float(got["lm_loss"]) + 0.1 * float(got["mtp_nll"]), rel=1e-6)
        assert float(mod.compared(got)) == pytest.approx(
            float(ref.compared(want, cfg)), abs=1e-4)
    assert 0.3 < float(got["rows_held_share"]) < 0.7    # 4 of 8 held
    assert np.isinf(float(mod.compared({**got, "rows_held_share": 0.01})))
    with pytest.raises(ValueError, match="one group"):
        mod.build({**cfg, "n_group": 8}, traffic)


def test_every_leafs_gradient_is_the_references_in_float32(tree):
    """The program against ``benchmark/reference/joyai_llm_flash.py`` on
    seeded weights: the loss and EVERY leaf's gradient, leaf by leaf (the
    harness compares the flattened gradient's norm)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    mod = load(str(tree / "benchmark" / "configs" / f"{NAME}.py"))
    ref = load(str(tree / "benchmark" / "reference" / f"{NAME}.py"))
    cfg = json.loads((tree / "benchmark" / "configs"
                      / f"{NAME}.json").read_text())
    cfg["train_router"] = True    # the routers' leaves get a gradient too
    built = mod.build(cfg, {"vocab_size": 256, "seq_len": 64})
    batch = {"input_ids": jnp.asarray(
        np.random.default_rng(1).integers(1, 256, (2, 64)), jnp.int32)}
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
    assert len(leaves) == len(jax.tree.leaves(got)) >= 45
    for (path, b), a in zip(leaves, jax.tree.leaves(got)):
        norm = float(jnp.linalg.norm(b))
        assert norm > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) / norm < 2e-5, \
            jax.tree_util.keystr(path)


def test_a_program_without_the_layer_is_refused_with_a_message(
        tree, monkeypatch):
    """What the parent commit does with the new cell: it fails at once,
    before a device or the feed is touched."""
    from benchmark.harness import runner
    from distributeddeeplearningspark_tpu.models import hybrid_decoder

    monkeypatch.delattr(hybrid_decoder, "LatentAttention")
    with cells.run_in(tree), pytest.raises(runner.Refused) as e:
        runner.measure(str(tree), CELL, seed=3, seconds=0.5, trace=False,
                       t_process=time.perf_counter(), master="local[1]")
    assert "latent-attention" in e.value.args[0][0]
    assert not (tree / "benchmark" / ".cache").exists()   # no feed was built


def test_stage_readers_find_kernels_by_name_and_xla_stages_by_shape():
    events = [
        ev("while.1", 0, 100, op="while",
           result="(s32[], bf16[1,16384,32,192])"),
        ev("flash_fwd.1", 10, 20, op="custom-call"),
        ev("flash_bwd_dq.1", 30, 30, op="custom-call",
           result="bf16[32,16384,192]"),
        ev("flash_bwd_dkv.1", 60, 40, op="custom-call",
           result="(bf16[32,16384,192], bf16[32,16384,128])"),
        ev("fusion.1", 100, 1, result="bf16[16384,1536]"),
        ev("fusion.2", 101, 2, result="bf16[1,16384,576]"),
        ev("fusion.3", 103, 3, result="bf16[16384,512]"),
        ev("fusion.4", 106, 4, result="bf16[1,16384,32,192]"),
        ev("fusion.5", 110, 5, result="(bf16[1,16384,32,256], f32[16384])"),
        ev("copy.6", 115, 6, op="copy", result="bf16[32,16384,192]"),
        # nobody's: the block's width, v and o at 128, weights, the experts
        ev("fusion.7", 130, 9, result="bf16[1,16384,2048]"),
        ev("fusion.8", 139, 9, result="bf16[1,16384,32,128]"),
        ev("fusion.9", 148, 9, result="f32[1536,32,192]"),
        ev("fusion.10", 157, 9, result="bf16[131072,2048]"),
        ev("fusion.11", 166, 9, result="f32[2048,1536]"),
    ]
    ctx = cells.ctx(CELL, events)
    assert reader("mla_attn_ms_per_step")(ctx) == pytest.approx(90 / 2)
    assert reader("mla_proj_ms_per_step")(ctx) == pytest.approx(21 / 2)
    assert ctx["facts"]["mla_proj_ms_per_step"]["events_per_step"] == 3
    # a program without the kernels (the parent): nothing, no raise
    bare = cells.ctx(CELL, [ev("fusion.1", 0, 5, result="bf16[32,512,768]")])
    for name in NEW_METRICS:
        assert reader(name)(bare) is None, name
        assert reader(name)({**bare, "trace": None}) is None, name
    # ... and in a cell of another configuration
    other = {**cells.ctx(CELL, events), "cell": {"config_mod": object()}}
    for name in NEW_METRICS:
        assert reader(name)(other) is None, name


def test_the_roofline_counts_executions_from_the_trace_and_stays_under_100():
    from benchmark.harness import flops, flops_mla

    ctx = cells.ctx(CELL, [])
    mod = ctx["cell"]["config_mod"]
    kernels = flops_mla.mla_attn_kernels(
        **mod.mla_attn_shapes(ctx["cfg"], ctx["traffic"]))
    least = {k: flops.least_seconds(v["ops"], v["bytes"], ctx["peaks"])
             for k, v in kernels.items()}
    assert {b for _, b in least.values()} == {"compute"}
    # every kernel at four times its least time, six layers a step
    events, t = [], 0.0
    for name in kernels:
        for _ in range(6):
            ms = 4e3 * least[name][0]
            events.append(ev(f"{name}.1", t, ms, op="custom-call"))
            t += ms
    ctx = cells.ctx(CELL, events, steps=1)
    assert reader("mla_attn_roofline")(ctx) == pytest.approx(25.0)
    assert ctx["facts"]["mla_attn_roofline"]["flash_fwd"][
        "runs_per_step"] == 6
    # a kernel AT its roofline reads 100, not more: the count is of the
    # model's own widths, so a kernel that pads 192 to 256 in VMEM reads lower
    events = [ev(f"{k}.1", i, 1e3 * least[k][0], op="custom-call")
              for i, k in enumerate(kernels)]
    assert reader("mla_attn_roofline")(cells.ctx(CELL, events, steps=1)) == \
        pytest.approx(100.0)
    padded = flops_mla.mla_attn_kernels(**{
        **mod.mla_attn_shapes(ctx["cfg"], ctx["traffic"]),
        "qk_head_dim": 256})
    assert all(padded[k]["ops"] > kernels[k]["ops"] for k in kernels)
