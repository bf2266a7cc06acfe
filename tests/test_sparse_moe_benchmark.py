"""The token feed, the analytic counts and a CPU rehearsal of the cell
``keye_vl2_30b_a3b.fit_s8k`` at toy sizes (as
``benchmark/tests/test_driven_by_data.py`` rehearses the older cells); its
planted faults are in ``tests/test_sparse_moe_controls.py``. A CPU run checks
control flow, counts and agreement with the reference; it yields no time,
rate or utilisation."""

import json
import os
import time

import numpy as np
import pytest

import _cells as cells
from _cells import ROOT, ev, load, reader, tree  # noqa: F401
from distributeddeeplearningspark_tpu.data import records, text

CELL = "keye_vl2_30b_a3b.fit_s8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def token_records(tmp_path_factory):
    rng = np.random.default_rng(0)
    docs = [{"tokens": rng.integers(1, 100, rng.integers(3, 200))
             .astype(np.int32)} for _ in range(57)]
    d = str(tmp_path_factory.mktemp("token_records"))
    records.write_array_records(iter(docs), d, num_shards=4)
    return d


@pytest.mark.parametrize("partitions", [1, 2, 3, 7, 200])
def test_token_windows_are_byte_identical_for_any_partition_count(
        token_records, partitions):
    src = records.array_records(token_records)
    one = text.packed_token_windows(src, seq_len=64, eos_id=0).collect()
    many = text.packed_token_windows(src, seq_len=64, eos_id=0,
                                     num_partitions=partitions)
    assert many.num_partitions == partitions
    got = many.collect()
    assert len(got) == len(one) > 50
    for a, b in zip(got, one):
        assert a.keys() == b.keys() == {"input_ids"}
        assert a["input_ids"].dtype == np.int32
        assert a["input_ids"].tobytes() == b["input_ids"].tobytes()
    # the windows ARE the document stream: documents back to back, one EOS
    # after each, cut every 64 tokens, the unfilled tail left out
    stream = np.concatenate([np.concatenate((e["tokens"], [0]))
                             for i in range(src.num_partitions)
                             for e in src.iter_partition(i)])
    flat = np.concatenate([e["input_ids"] for e in got])
    assert flat.size == stream.size - stream.size % 64
    assert (flat == stream[:flat.size]).all()


def test_token_windows_through_the_worker_pool_are_the_same(token_records):
    """The packer holds views of pooled arrays (data/workers.py's contract)."""
    from distributeddeeplearningspark_tpu.data.workers import (
        WorkerMappedDataset, fork_available)

    if not fork_available():
        pytest.skip("no fork")
    src = records.array_records(token_records)
    pooled = WorkerMappedDataset(src, lambda ex: ex, 2, label="identity")
    a = text.packed_token_windows(src, seq_len=64, eos_id=0).collect()
    b = text.packed_token_windows(pooled, seq_len=64, eos_id=0).collect()
    assert len(a) == len(b)
    assert all(x["input_ids"].tobytes() == y["input_ids"].tobytes()
               for x, y in zip(a, b))


def test_operations_per_token_count_the_selection_not_s_squared():
    from benchmark.harness import flops_sparse

    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "keye_vl2_30b_a3b.json")))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "fit_s8k.json")))
    mod = load(os.path.join(ROOT, "benchmark", "configs",
                            "keye_vl2_30b_a3b.py"))
    per_token = mod.flops_per_item(cfg, traffic)
    # the issue's hand count: 437 MFLOP forward, 1.31 GFLOP a token
    assert abs(per_token / 3 - 437.7e6) < 0.5e6
    assert abs(flops_sparse.mean_selected_keys(8192, 2048) - 1792.1) < 0.1
    assert flops_sparse.mean_selected_keys(1024, 2048) == 512.5
    # attention over the dense causal prefix would count 2.3 times as much
    dense = dict(cfg["sa_config"], topk=8192)
    assert mod.flops_per_item({**cfg, "sa_config": dense}, traffic) \
        > per_token * 1.3
    kern = flops_sparse.dsa_kernels(**mod.dsa_shapes(cfg, traffic))
    tiles = 2 * 16 * 17 / 2
    assert kern["dsa_attend_fwd"]["ops"] == tiles * 2 * 2 * 512 * 512 * 128 * 32
    assert kern["dsa_index_bwd"]["ops"] == 3 * kern["dsa_index_fwd"]["ops"]
    assert set(kern) == {"dsa_index_fwd", "dsa_index_select", "dsa_index_bwd",
                         "dsa_attend_fwd",
                         "dsa_attend_bwd_dq", "dsa_attend_bwd_dkv",
                         "dsa_kl_target"}


def test_configuration_file_states_the_catalog_and_its_cuts():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    conf = next(c for c in bench["configs"] if c["name"] == "keye_vl2_30b_a3b")
    cfg = json.load(open(os.path.join(ROOT, conf["file"])))
    assert sorted(conf["reduced"]) == ["num_experts", "num_hidden_layers",
                                       "vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["published"]["num_experts"] == 128
    assert cfg["published"]["vocab_size"] == 151936
    assert cfg["experts_held"] == [0, cfg["num_experts"]]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    if not os.path.exists(CATALOG):
        return
    row = next(json.loads(line) for line in open(CATALOG)
               if '"Keye-VL-2.0-30B-A3B"' in line)
    assert conf["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert cfg[key] == value, key


TOY = {
    "configs/keye_vl2_30b_a3b.json": dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        num_local_experts=8, num_experts=4, experts_held=[2, 4],
        num_experts_per_tok=4, moe_intermediate_size=32,
        sa_config={"indexer_head_dim": 16, "indexer_num_heads": 4,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 128,
                   "q_chunk_size": 128, "topk": 64},
        compute_dtype="float32",
        check={"examples": 1, "loss_abs_tol": 1e-4, "grad_rel_tol": 1e-3,
               "why": "float32 on both sides"}),
    "traffic/fit_s8k.json": dict(
        num_docs=64, doc_len_median=100, doc_len_min=8, doc_len_max=500,
        vocab_size=256, seq_len=256, per_chip_batch=2, log_every=2,
        warmup_steps=2, trace_steps=2),
}


def test_the_cell_rehearses_at_a_toy_size(tree):
    """On the harness's own window and the wall clock: tier-1's one run of
    the window's real clock, since ``benchmark/tests/`` is not in tier-1
    (the other cells' rehearsals count steps: ``_cells.StepClock``)."""
    from benchmark.harness import runner

    with cells.run_in(tree, step_s=None):
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
    # the counter's reader finds the step's own outputs in step_metrics
    assert 1.0 <= r["metrics"]["moe_load_max_over_mean"]["value"] < 8.0
    # a CPU run has no device plane: the device-trace readers return nothing
    for name in ("dsa_index_ms_per_step", "dsa_attend_ms_per_step",
                 "moe_ms_per_step", "dsa_index_roofline",
                 "dsa_attend_roofline", "device_step_ms", "mfu"):
        assert name not in r["metrics"], name
    assert r["facts"]["feed"]["seed_cache_hit"] is False
    assert again["facts"]["feed"]["seed_cache_hit"] is True
    assert set(again["metrics"]) == {"throughput", "setup_s"}


def test_stage_readers_take_unions_and_find_xla_stages_by_shape():
    events = [
        # a scan's while spans its body: it belongs to no stage
        ev("while.1", 0, 100, op="while", result="(s32[], f32[2,8192,8192])"),
        ev("dsa_index_fwd.1", 0, 4, op="custom-call",
           result="f32[2,8192,8192]"),
        ev("dsa_index_select.1", 4, 6, op="custom-call",
           result="(f32[2,8192,8], s32[2,8192,8])"),
        # the selection's XLA part, by shape; a conditional spans its branch
        ev("conditional.3", 10, 10, op="conditional",
           result="pred[2,8192,8192]"),
        ev("fusion.7", 12, 5, result="pred[2,8192,8192]"),
        ev("dsa_attend_fwd.1", 20, 16, op="custom-call",
           result="(bf16[64,8192,128], f32[64,8192,8])"),
        ev("dsa_kl_target.1", 36, 12, op="custom-call",
           result="(f32[2,8192,8], f32[2,8192,8192])"),
        ev("ragged-dot-none.2", 50, 3, op="custom-call",
           result="bf16[131072,768]"),
        ev("fusion.9", 53, 4, result="bf16[131072,2048]"),
        ev("sort.4", 57, 1, op="sort",
           result="(f32[16384,128], s32[16384,128])"),
        ev("fusion.11", 60, 9, result="bf16[2,8192,2048]"),  # nobody's
    ]
    ctx = cells.ctx(CELL, events)
    assert reader("dsa_index_ms_per_step")(ctx) == pytest.approx(20 / 2)
    assert reader("dsa_attend_ms_per_step")(ctx) == pytest.approx(28 / 2)
    assert reader("moe_ms_per_step")(ctx) == pytest.approx(8 / 2)
    # a program without the kernels (the parent): nothing to read, no raise
    bare = cells.ctx(CELL, [ev("fusion.1", 0, 5, result="bf16[32,512,768]")])
    for name in ("dsa_index_ms_per_step", "dsa_attend_ms_per_step",
                 "dsa_index_roofline", "dsa_attend_roofline",
                 "moe_load_max_over_mean"):
        assert reader(name)(bare) is None, name
    assert reader("dsa_index_ms_per_step")({**bare, "trace": None}) is None


def test_rooflines_count_executions_from_the_trace_and_stay_under_100():
    from benchmark.harness import flops, flops_sparse

    ctx = cells.ctx(CELL, [])
    costs = flops_sparse.dsa_kernels(
        **ctx["cell"]["config_mod"].dsa_shapes(ctx["cfg"], ctx["traffic"]))
    least = {k: flops.least_seconds(v["ops"], v["bytes"], ctx["peaks"])[0]
             for k, v in costs.items()}
    # every kernel at twice its least time; the forward runs twice a step
    events, t = [], 0.0
    for name, runs in (("dsa_attend_fwd", 2), ("dsa_attend_bwd_dq", 1),
                       ("dsa_attend_bwd_dkv", 1), ("dsa_kl_target", 2),
                       ("dsa_index_fwd", 2), ("dsa_index_bwd", 1),
                       ("dsa_index_select", 1)):
        for _ in range(runs):
            ms = 2e3 * least[name]
            events.append(ev(f"jvp_{name}_.1", t, ms, op="custom-call"))
            t += ms
    ctx = cells.ctx(CELL, events, steps=1)
    assert reader("dsa_attend_roofline")(ctx) == pytest.approx(50.0)
    assert reader("dsa_index_roofline")(ctx) == pytest.approx(50.0)
    runs = ctx["facts"]["dsa_attend_roofline"]["dsa_attend_fwd"]
    assert runs["runs_per_step"] == 2 and runs["bound"] == "compute"
    assert ctx["facts"]["dsa_index_roofline"]["dsa_index_select"][
        "bound"] == "memory"
