"""A configuration, a feed, a traffic mix, a layer metric and a cell are
added by new files and list entries only, and the three cells and the one kept
for later run end to end at tiny sizes: the CPU rehearsal path. It checks
control flow and counts; it prints no result line and no number under a
device metric's name."""

import json
import os
import time

import pytest

from benchmark.harness import runner

DEVICE_METRICS = {"device_step_ms", "device_idle_share", "flash_ms_per_step",
                  "flash_roofline", "allreduce_exposed_ms", "mfu",
                  "peak_hbm_gib"}

NEW_FILES = {
    "configs/tiny_mlp.json": json.dumps({
        "name": "tiny_mlp", "source": "a test's own", "item": "row",
        "reference": "tiny_mlp", "features": 16, "hidden": 32, "classes": 4,
        "check": {"examples": 4, "loss_abs_tol": 1e-5, "grad_rel_tol": 1e-4,
                  "why": "float32 on both sides"}}),
    "configs/tiny_mlp.py": '''
import flax.linen as nn


class MLP(nn.Module):
    hidden: int
    classes: int

    @nn.compact
    def __call__(self, batch, *, train=False):
        x = nn.relu(nn.Dense(self.hidden, name="in")(batch["x"]))
        return nn.Dense(self.classes, name="out")(x)


def build(cfg, traffic):
    from distributeddeeplearningspark_tpu.train import losses, optim
    return {"model": MLP(cfg["hidden"], cfg["classes"]),
            "loss": losses.softmax_xent, "tx": optim.sgd(0.05),
            "fit_kwargs": {}}


def items_per_example(cfg, traffic):
    return 1


def flops_per_item(cfg, traffic):
    return 6.0 * (cfg["features"] * cfg["hidden"] + cfg["hidden"] * cfg["classes"])


def program_loss(model, loss_fn, params, mutable, batch):
    return loss_fn(model.apply({"params": params}, batch), batch)[0]

''',
    "reference/tiny_mlp.py": '''
import jax
import jax.numpy as jnp


def loss(params, mutable, batch, cfg):
    x = jnp.maximum(batch["x"] @ params["in"]["kernel"] + params["in"]["bias"], 0)
    logits = x @ params["out"]["kernel"] + params["out"]["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, batch["label"][:, None], axis=-1).mean()
''',
    "feeds/tiny_rows.py": '''
import numpy as np


def build(spark, traffic, seed):
    from distributeddeeplearningspark_tpu.rdd import PartitionedDataset
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((traffic["features"], 4))
    rows = []
    for _ in range(traffic["rows"]):
        x = rng.standard_normal(traffic["features"]).astype(np.float32)
        rows.append({"x": x, "label": np.int32(np.argmax(x @ w))})
    ds = PartitionedDataset.parallelize(rows, max(spark.default_parallelism, 1))
    return {"dataset": ds.repeat(), "sample_from": ds, "facts": {"rows": len(rows)}}
''',
    "traffic/tiny_fit.json": json.dumps({
        "feed": "tiny_rows", "features": 16, "rows": 256, "per_chip_batch": 8,
        "log_every": 5, "warmup_steps": 5, "trace_steps": 5,
        "loss_band": 0.5}),
    "layer_metrics/laps_in_window.py": '''
def read(ctx):
    return len(ctx["laps"])
''',
}


def add_fifth_cell(root):
    """New files, and one entry each in BENCHMARK.json's lists."""
    before = {}
    for d, _, files in os.walk(root / "benchmark"):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    for rel, text in NEW_FILES.items():
        path = root / "benchmark" / rel
        assert not path.exists()
        path.write_text(text)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny_mlp", "source": "a test's own",
        "file": "benchmark/configs/tiny_mlp.json", "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "tiny_mlp.tiny_fit", "config": "tiny_mlp",
        "traffic": "tiny_fit", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "laps_in_window", "unit": "laps", "better": "higher",
        "source": "program_counter", "layer": "loop", "moves": "throughput",
        "workloads": ["tiny_mlp.tiny_fit"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return before


def test_a_fifth_cell_needs_new_files_and_list_entries_only(tree, capsys):
    before = add_fifth_cell(tree)
    for p, content in before.items():           # no existing file was edited
        with open(p, "rb") as fh:
            assert fh.read() == content, p
    r = runner.measure(str(tree), "tiny_mlp.tiny_fit", seed=5, seconds=1.0,
                       trace=True, t_process=time.perf_counter(),
                       master="local[1]")
    assert r["correct"], r
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]["laps_in_window"]["value"] >= 1
    assert r["metrics"]["laps_in_window"]["unit"] == "laps"
    assert {"program_start_s", "compile_s", "input_wait_share",
            "loop_host_share"} <= set(r["metrics"])
    # the BERT-only and four-chip-only metrics are not this cell's
    assert not DEVICE_METRICS & set(r["metrics"])
    assert r["device"]["platform"] == "cpu"
    assert "busy_s" not in r["device"] and "breakdown" not in r
    r = runner.measure(str(tree), "tiny_mlp.tiny_fit", seed=5, seconds=1.0,
                       trace=False, t_process=time.perf_counter(),
                       master="local[1]")
    assert set(r["metrics"]) == {"throughput", "setup_s"}
    # the harness prints facts only; the result line is run.py's, on a TPU
    out = capsys.readouterr().out
    assert all(line.startswith("# ") for line in out.splitlines() if line)


SHRINK = {
    "configs/bert_base_mlm.json": dict(
        vocab_size=1024, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=128, compute_dtype="float32"),
    "configs/resnet50_imagenet.json": dict(
        width=8, stage_sizes=[1, 1, 1, 1], image_size=32,
        compute_dtype="float32"),
    "traffic/fit.json": dict(num_docs=64, seq_len=128, max_predictions=20,
                             per_chip_batch=2, log_every=2, warmup_steps=2,
                             trace_steps=2),
    "traffic/fit_dp4.json": dict(num_docs=64, seq_len=128, max_predictions=20,
                                 per_chip_batch=2, log_every=2, warmup_steps=2,
                                 trace_steps=2),
    "traffic/fit_records.json": dict(num_images=32, image_size=32,
                                     record_px=40, per_chip_batch=8,
                                     log_every=2, warmup_steps=2,
                                     trace_steps=2),
    "traffic/fit_jpeg.json": dict(num_images=32, image_size=32,
                                  per_chip_batch=8, log_every=2,
                                  warmup_steps=2, trace_steps=2),
}


# measured in PR 22 and kept out of ``workloads`` (its runs fall into two
# modes 4% apart, PERF.md section 4): its traffic file and feed are in the
# tree, so the cell is one list entry away
FIT_RECORDS = {"name": "resnet50_imagenet.fit_records",
               "config": "resnet50_imagenet", "traffic": "fit_records",
               "chips": 1, "why": "the --records-dir path: no decode"}


@pytest.mark.parametrize("cell,master", [
    ("bert_base_mlm.fit", "local[1]"),
    ("bert_base_mlm.fit_dp4", "local[4]"),
    ("resnet50_imagenet.fit_records", "local[1]"),
    ("resnet50_imagenet.fit_jpeg", "local[1]"),
])
def test_each_cell_rehearses_at_a_tiny_size(tree, cell, master):
    for rel, patch in SHRINK.items():
        path = tree / "benchmark" / rel
        path.write_text(json.dumps({**json.loads(path.read_text()), **patch}))
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    if cell == FIT_RECORDS["name"]:
        bench["workloads"].append(FIT_RECORDS)
        (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    assert cell in [w["name"] for w in bench["workloads"]]
    r = runner.measure(str(tree), cell, seed=3, seconds=1.0, trace=True,
                       t_process=time.perf_counter(), master=master)
    assert r["correct"], r["facts"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["facts"]["mesh"]["data"] == (4 if master == "local[4]" else 1)
    assert not DEVICE_METRICS & set(r["metrics"])
    assert {"input_wait_share", "input_assembly_us_per_item",
            "program_start_s"} <= set(r["metrics"])
    facts = r["facts"]
    assert facts["train_step"]["compiles"] == 1 and facts["train_step"]["aot"]
    # a fresh tree has not seen the seed: its data was made in set-up
    assert facts["setup_split_s"]["seed_cache_hit"] is False
    if master == "local[4]":
        assert facts["executable"]["collectives"]["all-reduce"] > 0
