"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, every local device (``master("tpu")``): the normal training
path, ``Session.builder…getOrCreate()`` → ``PartitionedDataset`` →
``Trainer.fit`` with the real feed running, on BERT-base at its defaults
(``models/bert.py`` ``bert_base()``), sequences of 512, 32 sequences a chip,
AdamW, gathered MLM head. The data is the body of ``examples/train_bert.py``
(synthetic Wikipedia → trained tokenizer → packed MLM windows); the model
keeps its 30,522-row vocabulary, in which the tokenizer's ids are valid.

It exits non-zero, with the reasons on stderr and no result line, unless:
the platform is ``tpu`` and its ``device_kind`` has a peaks entry; the loss
is finite on every lap; the train step compiled once and stayed on the AOT
path; the compiled executable holds Mosaic custom calls; every local device
holds part of the state and of the batch; and the model with the flash
kernel agrees with the same model on XLA attention on a small input.

Stdout is two lines of JSON. The first holds the smoke facts (does it run,
how long did set-up take: not benchmark numbers), also kept as
``chip_smoke_out/facts.json``. The LAST is the verdict, with exactly these
keys: ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as jax reports it.

There is no CPU mode. The CPU dry run of this path is
``examples/train_bert.py --variant tiny``.
"""

from __future__ import annotations

import importlib.metadata
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chip_smoke_out")

PER_CHIP_BATCH = 32
SEQ_LEN = 512
STEPS = 12
LOG_EVERY = 3          # four metric laps; the first holds init + compile
MAX_PREDICTIONS = int(SEQ_LEN * 0.15) + 4
MOSAIC_CALL = "tpu_custom_call"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


class SmokeFailure(Exception):
    """The run does not meet the contract; ``args[0]`` lists the reasons."""


class CompileWatch:
    """Counts what jax itself reports: persistent-cache hits and misses, and
    when each backend compile happened (to prove none lands after lap one)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.cache_hits = 0
        self.cache_misses = 0
        self.compile_times: list[float] = []
        self.stage_s: dict[str, float] = {}  # trace / lower / backend totals
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event: str, duration: float, **_: object) -> None:
        if not event.startswith("/jax/core/compile/"):
            return
        stage = event.rsplit("/", 1)[1].removesuffix("_duration")
        self.stage_s[stage] = self.stage_s.get(stage, 0.0) + duration
        if stage == "backend_compile":
            self.compile_times.append(time.perf_counter())


def require_tpu():
    """The devices, or SmokeFailure — without touching a backend when the
    environment already says there is no chip."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        raise SmokeFailure(["JAX_PLATFORMS=cpu: chip_smoke.py has no CPU "
                            "mode (the CPU dry run of this path is "
                            "examples/train_bert.py --variant tiny)"])
    import jax

    from distributeddeeplearningspark_tpu.metrics import spec_peak_flops

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure([f"jax found no TPU: platform is "
                            f"{devices[0].platform!r}"])
    spec_peak_flops(devices[0])  # raises for a device_kind with no peaks entry
    return devices


def build_dataset(spark, tok_vocab_size: int = 8192):
    """examples/train_bert.py's feed: docs → tokenizer → packed MLM windows."""
    from distributeddeeplearningspark_tpu.data import text as text_lib

    docs = text_lib.synthetic_wikipedia(
        2048, num_partitions=max(spark.default_parallelism, 1))
    tok = text_lib.WordPieceTokenizer.train(docs.collect(),
                                            vocab_size=tok_vocab_size)
    ds = text_lib.mlm_dataset(docs, tok, seq_len=SEQ_LEN,
                              max_predictions=MAX_PREDICTIONS, pack=True)
    return ds, tok


def train(spark, model, ds, *, batch_size: int, watch: CompileWatch) -> dict:
    """``Trainer.fit`` for STEPS steps; returns the facts the checks read."""
    from distributeddeeplearningspark_tpu import Trainer
    from distributeddeeplearningspark_tpu.train import losses, optim

    tx = optim.with_grad_clip(
        optim.adamw(optim.warmup_linear(1e-4, 100, STEPS)), 1.0)
    trainer = Trainer(spark, model, losses.masked_lm, tx)
    laps: list[dict] = []

    def on_step(step: int, metrics: dict) -> None:
        if step % LOG_EVERY == 0:  # fit has just synced on this step's metrics
            laps.append({"step": step, "t": time.perf_counter(),
                         "loss": metrics.get("loss")})

    _, summary = trainer.fit(ds.repeat(), batch_size=batch_size, steps=STEPS,
                             tokens_per_example=SEQ_LEN, log_every=LOG_EVERY,
                             callbacks=[on_step])
    return {"trainer": trainer, "summary": summary, "laps": laps,
            "late_compiles": sum(t > laps[0]["t"]
                                 for t in watch.compile_times) if laps else -1}


def check_run(run: dict, *, batch_size: int) -> tuple[list[str], dict]:
    """(failures, facts) from the trainer the run left behind."""
    import jax

    from distributeddeeplearningspark_tpu import telemetry

    trainer, laps = run["trainer"], run["laps"]
    step = trainer._train_step
    failures: list[str] = []
    n_local = jax.local_device_count()

    if len(laps) < 3:
        failures.append(f"{len(laps)} metric laps, want >= 3")
    for lap in laps:
        if lap["loss"] is None or not math.isfinite(lap["loss"]):
            failures.append(f"loss at step {lap['step']} is {lap['loss']}")

    ledger = step.compile_summary()  # "aot" only ever goes true -> false
    if (ledger["compiles"] != 1 or ledger["flagged_recompiles"]
            or not ledger["aot"]):
        failures.append(f"train step must compile once on the AOT path and "
                        f"stay there: {ledger}")
    if run["late_compiles"]:
        failures.append(f"{run['late_compiles']} backend compile(s) after "
                        f"the first lap")

    (key, compiled), = step.executables()
    text = compiled.as_text()
    mosaic_calls = text.count(MOSAIC_CALL)
    if not mosaic_calls:
        failures.append(f"no {MOSAIC_CALL} in the compiled train step: the "
                        f"flash kernel is not in the executable")

    # the executable's dispatch key holds the shapes and shardings of the
    # very arrays fit passed in, as a (state, batch) tree: every device must
    # hold part of the state and an equal share of the batch rows
    treedef, sigs, shardings = key
    _, batch_sigs = jax.tree_util.tree_unflatten(treedef, list(sigs))
    placed = jax.tree_util.tree_unflatten(treedef, list(shardings))
    for path, sh in jax.tree_util.tree_leaves_with_path(placed):
        if len(sh.device_set) != n_local:
            failures.append(f"{jax.tree_util.keystr(path)} lives on "
                            f"{len(sh.device_set)} of {n_local} devices")
    for name, (shape, _) in batch_sigs.items():
        rows = placed[1][name].shard_shape(shape)[0]
        if rows * n_local != batch_size:
            failures.append(f"batch[{name!r}] holds {rows} rows a device, "
                            f"want {batch_size // n_local}")
    mem = []
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        if not s.get("bytes_in_use", 0) > 0:
            failures.append(f"{d} holds no bytes: {s}")
        mem.append(int(s.get("peak_bytes_in_use", 0)))

    # the run's own telemetry stream: what each lap waited on
    tele_laps = [e for e in telemetry.read_events(OUT_DIR)
                 if e.get("kind") == "step_metrics"]
    steady = tele_laps[1:]
    for e in steady:
        if e.get("compile_in_lap_s"):
            failures.append(f"compile inside lap ending at step {e['step']}")
    steady_wall = sum(e["lap_s"] for e in steady)
    lap_ms = [round((b["t"] - a["t"]) / (b["step"] - a["step"]) * 1e3, 2)
              for a, b in zip(laps, laps[1:])]
    facts = {
        "steps": STEPS,
        "laps": len(laps),
        "compile_s": ledger["total_compile_s"],
        "first_lap_s": round(tele_laps[0]["lap_s"], 2) if tele_laps else None,
        "step_ms": round(run["summary"].get("step_time_ms", float("nan")), 2),
        "lap_step_ms": lap_ms,
        "input_wait_frac": (round(sum(e.get("input_wait_s", 0.0)
                                      for e in steady) / steady_wall, 3)
                            if steady_wall else None),
        "losses": [round(lap["loss"], 4) for lap in laps
                   if lap["loss"] is not None],
        "final_loss": run["summary"].get("loss"),
        "train_step": {"compiles": ledger["compiles"], "aot": ledger["aot"],
                       "flops_per_step": ledger["flops_per_step"],
                       "recompiles": ledger["flagged_recompiles"],
                       "mosaic_custom_calls": mosaic_calls,
                       "collectives": {c: text.count(f" {c}(") +
                                       text.count(f" {c}-start(")
                                       for c in COLLECTIVES}},
        "peak_hbm_bytes_per_device": mem,
    }
    with open(os.path.join(OUT_DIR, "train_step.hlo.txt"), "w") as f:
        f.write(text)
    return failures, facts


def check_reference(make_model, params, batch) -> tuple[list[str], dict]:
    """Same params, same small batch: the model on the flash kernel against
    the model on plain XLA attention, at the repo's bf16 tolerance
    (tests/test_flash_attention.py: atol = rtol = 5e-2)."""
    import jax
    import numpy as np

    def logits(**kw):
        model = make_model(**kw)
        compiled = jax.jit(
            lambda p, b: model.apply({"params": p}, b, train=False)).lower(
                params, batch).compile()
        return (np.asarray(compiled(params, batch), np.float32),
                MOSAIC_CALL in compiled.as_text())

    (got, got_mosaic), (want, want_mosaic) = logits(), logits(attention_impl="xla")
    failures = []
    if not got_mosaic or want_mosaic:
        failures.append(f"reference check compares the wrong programs: "
                        f"{MOSAIC_CALL} in the flash model {got_mosaic}, in "
                        f"the XLA-attention model {want_mosaic}")
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want)))
    facts = {"flash_vs_xla_logits_max_abs_err": round(err, 5),
             "logits_max_abs": round(scale, 3), "shape": list(got.shape)}
    if not np.all(np.isfinite(got)):
        failures.append("non-finite logits from the flash-kernel model")
    if not np.allclose(got, want, atol=5e-2 * max(scale, 1.0), rtol=5e-2):
        failures.append(f"flash-kernel model disagrees with the XLA-attention "
                        f"model: {facts}")
    return failures, facts


def verdict(devices) -> dict:
    """The last stdout line: exactly ``ok`` and ``device`` (platform, kind,
    count), the device as jax reports it. Everything else is a fact."""
    return {"ok": True,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices)}}


def main() -> int:
    devices = require_tpu()

    import jax
    import jaxlib

    from distributeddeeplearningspark_tpu import Session
    from distributeddeeplearningspark_tpu.data.feed import stack_examples
    from distributeddeeplearningspark_tpu.models import bert_base
    from distributeddeeplearningspark_tpu.utils import native

    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    # the supervisor's way of giving a run a telemetry stream
    os.environ["DLS_TELEMETRY_DIR"] = OUT_DIR
    watch = CompileWatch()

    spark = Session.builder.master("tpu").appName("chip-smoke").getOrCreate()
    cache_dir = jax.config.jax_compilation_cache_dir
    batch_size = PER_CHIP_BATCH * spark.num_devices
    ds, tok = build_dataset(spark)
    model = bert_base()
    if tok.vocab_size > model.cfg.vocab_size:
        raise SmokeFailure([f"tokenizer ids reach {tok.vocab_size}, past the "
                            f"model's {model.cfg.vocab_size} rows"])

    run = train(spark, model, ds, batch_size=batch_size, watch=watch)
    failures, facts = check_run(run, batch_size=batch_size)
    small = stack_examples(ds.take(2 * spark.num_devices))
    ref_failures, ref_facts = check_reference(
        bert_base, run["trainer"].state.params, small)
    failures += ref_failures
    mesh_shape = {k: v for k, v in spark.mesh.shape.items() if v > 1} or {"data": 1}
    spark.stop()
    if failures:
        raise SmokeFailure(failures)

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    facts = {
        "what": "smoke facts, not benchmark numbers",
        "device": verdict(devices)["device"],
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "model": "bert_base", "seq_len": SEQ_LEN, "global_batch": batch_size,
        "mesh": mesh_shape,
        **facts,
        "reference": ref_facts,
        "cache_dir": cache_dir,
        "cache_hits": watch.cache_hits,
        "cache_misses": watch.cache_misses,
        "cache_hit": watch.cache_hits > 0 and watch.cache_misses == 0,
        "jax_compile_stages_s": {k: round(v, 2)
                                 for k, v in sorted(watch.stage_s.items())},
        "native_host_lib": native.available(),
    }
    with open(os.path.join(OUT_DIR, "facts.json"), "w") as f:
        json.dump(facts, f, indent=1)
    print(json.dumps(facts))
    print(json.dumps(verdict(devices)), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        for reason in e.args[0]:
            print(f"chip_smoke: FAIL: {reason}", file=sys.stderr)
        sys.exit(1)
