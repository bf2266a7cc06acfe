"""One run of one cell: ``Session`` -> dataset -> ``Trainer`` -> ONE
``Trainer.fit`` call with the real feed running, measured from lap boundaries.

Driven by data: the cell names a configuration and a traffic mix in
``BENCHMARK.json``; everything that belongs to one of those, to a feed or to a
per-layer metric is a file found by that name under ``benchmark/``. Nothing
here knows a cell, a model or a metric by name.
"""

from __future__ import annotations

import importlib.metadata
import importlib.util
import json
import math
import os
import shutil
import sys
import time

from benchmark.harness import checks, trace_reduce
from benchmark.harness.compile_watch import CompileWatch
from benchmark.harness.window import LapWindow, WindowClosed, window_result


class Refused(Exception):
    """The run cannot be a measurement; ``args[0]`` lists the reasons."""


def load_module(path: str):
    name = "benchmark_file_" + os.path.splitext(
        os.path.basename(path))[0] + f"_{abs(hash(path)):x}"
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise Refused([f"cannot load {path}"])
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(root: str, workload: str) -> dict:
    """``BENCHMARK.json`` entry -> the files that make the cell."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused([f"no workload {workload!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})"])
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    bdir = os.path.join(root, bench["paths"][0])
    cfg = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(bdir, "traffic", cell["traffic"] + ".json"))

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload, "chips": cell["chips"], "bench_dir": bdir,
        "cfg": cfg, "traffic": traffic,
        "config_mod": load_module(os.path.splitext(
            os.path.join(root, conf["file"]))[0] + ".py"),
        "feed_mod": load_module(os.path.join(bdir, "feeds",
                                             traffic["feed"] + ".py")),
        "reference_mod": load_module(os.path.join(
            bdir, "reference", cfg["reference"] + ".py")),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "peaks": load_json(os.path.join(bdir, "peaks.json")),
    }


def say(tag: str, facts: dict) -> None:
    """A fact line: earlier than, and never, the last line."""
    print(f"# {tag}: {json.dumps(facts, default=str)}", flush=True)


def measure(root: str, workload: str, *, seed: int, seconds: float,
            trace: bool, t_process: float, master: str = "tpu") -> dict:
    """Run the cell; returns the last line's object plus ``facts``.

    ``master`` is ``"tpu"`` for every measurement. The tests' CPU rehearsal
    passes ``local[N]``; ``run.py`` never does, and refuses to print a result
    for a platform other than ``tpu``.
    """
    cell = resolve_cell(root, workload)
    traffic, cfg = cell["traffic"], cell["cfg"]
    out_dir = os.path.join(cell["bench_dir"], "out", workload,
                           f"seed{seed}-trace{int(trace)}")
    shutil.rmtree(out_dir, ignore_errors=True)
    tele_dir = os.path.join(out_dir, "telemetry")
    trace_dir = os.path.join(out_dir, "trace")
    os.makedirs(tele_dir)
    # how a supervised job runs; it is what turns on StarvationProbe and
    # StepAnatomy. The benchmark sets no other DLS_* variable.
    os.environ["DLS_TELEMETRY_DIR"] = tele_dir

    import jax

    from distributeddeeplearningspark_tpu import Session, Trainer, telemetry
    from distributeddeeplearningspark_tpu.data.feed import stack_examples

    watch = CompileWatch()
    clock = {"session_begin": time.perf_counter()}
    spark = Session.builder.master(master).appName(workload).getOrCreate()
    clock["session"] = time.perf_counter()
    devices = jax.devices()
    kind = devices[0].device_kind
    if master == "tpu":
        if kind not in cell["peaks"]:
            raise Refused([f"device_kind {kind!r} has no entry in peaks.json"])
        if len(devices) != cell["chips"]:
            raise Refused([f"{workload} is defined on {cell['chips']} chip(s); "
                           f"jax found {len(devices)}"])
    chips = spark.num_devices
    peaks = cell["peaks"].get(kind)

    feed = cell["feed_mod"].build(spark, traffic, seed)
    clock["feed_built"] = time.perf_counter()
    built = cell["config_mod"].build(cfg, traffic)
    trainer = Trainer(spark, built["model"], built["loss"], built["tx"],
                      seed=seed)
    batch_size = traffic["per_chip_batch"] * chips
    items_per_step = batch_size * cell["config_mod"].items_per_example(
        cfg, traffic)

    def start_trace() -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans yes, every Python call no
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    win = LapWindow(
        log_every=traffic["log_every"], warmup_steps=traffic["warmup_steps"],
        seconds=seconds,
        trace_steps=traffic["trace_steps"] if trace else 0,
        start_trace=start_trace, stop_trace=jax.profiler.stop_trace,
        sync=lambda: jax.block_until_ready(trainer.state.step))
    fit_error = None
    try:
        trainer.fit(feed["dataset"], batch_size=batch_size,
                    log_every=traffic["log_every"], callbacks=[win],
                    **built["fit_kwargs"])
        fit_error = "fit returned before the window closed"
    except WindowClosed:
        pass
    except Exception as e:  # noqa: BLE001 - a failed run is still reported
        fit_error = f"fit raised {type(e).__name__}: {e}"
        if win.trace_from is not None and win.trace_to is None:
            jax.profiler.stop_trace()  # fit died inside the traced laps

    res = window_result(win, items_per_step=items_per_step, chips=chips,
                        loss_band=traffic["loss_band"])
    reasons = list(res["reasons"])
    if fit_error:
        reasons.append(fit_error)
    # the TPU's allocator counts a program's temporaries as "reserved",
    # apart from the arrays "in use": the chip's peak is the two together
    mem_stats = [d.memory_stats() or {} for d in jax.local_devices()]
    mem = [int(m.get("peak_bytes_in_use", 0))
           + int(m.get("peak_bytes_reserved", 0)) for m in mem_stats]
    laps = [e for e in telemetry.read_events(tele_dir)
            if e.get("kind") == "step_metrics"]

    # verdict 3: one compile, on the AOT path, none after the window opened
    step_fn = trainer._train_step
    ledger = step_fn.compile_summary() if step_fn is not None else {}
    if win.open_at is not None:
        late = watch.compiles_after(win.open_at.t)
        reasons += checks.compile_failures(ledger, late)
        for e in laps:
            if e["step"] > win.open_at.step and e.get("compile_in_lap_s"):
                reasons.append(f"compile inside the lap ending at step "
                               f"{e['step']}")
    # verdict 4: every device holds state and its share of every batch
    place_fail, exe_facts = checks.placement(
        step_fn, batch_size=batch_size, devices=list(spark.mesh.devices.flat))
    reasons += place_fail

    traced = None
    if trace and win.trace_to is not None:
        xplane = trace_reduce.find_xplane(trace_dir)
        if xplane:
            traced = trace_reduce.extract(xplane)
            traced["window_s"] = win.trace_to.t - win.trace_from.t
            traced["steps"] = win.trace_to.step - win.trace_from.step
        # the extract is kept beside facts.json; the raw trace weighs tens of
        # megabytes a run and is not
        shutil.rmtree(trace_dir, ignore_errors=True)

    # verdict 1: the plain reference, after the window, on the trained state
    t0 = time.perf_counter()
    n = cfg["check"]["examples"]
    sample = stack_examples(feed["sample_from"].take(n))
    ref_fail, ref_facts = checks.reference(
        cell["config_mod"], cell["reference_mod"], cfg, built, trainer.state,
        sample)
    reasons += ref_fail
    ref_facts["check_s"] = time.perf_counter() - t0

    first = win.open_at.step if win.open_at else math.inf
    ctx = {
        "cell": cell, "cfg": cfg, "traffic": traffic, "peaks": peaks,
        "chips": chips, "clock": clock, "window": res,
        "first_step_t": win.first_step_t, "compile": ledger,
        "compile_stages_s": dict(watch.stage_s),
        "laps": [e for e in laps if e["step"] > first],
        "trace": traced, "memory_peak_bytes": max(mem) if mem else 0,
        "items_per_step": items_per_step, "facts": {},
    }
    metrics: dict[str, dict] = {}
    if trace:
        for m in cell["per_layer"]:
            reader = load_module(os.path.join(
                cell["bench_dir"], "layer_metrics", m["name"] + ".py"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = {"throughput": res.get("throughput"),
                  "setup_s": (win.open_at.t - t_process
                              if win.open_at else None)}
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": max(mem) if mem else 0}
    result = {"correct": not reasons, "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": device}
    if traced is not None:
        summary = trace_reduce.summarize(traced, window_s=traced["window_s"])
        if summary.get("busy_s"):
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
        say("trace", {"planes": traced["planes"],
                      "busy_s_by_device": summary["busy_s_by_device"],
                      "steps": traced["steps"], "window_s": traced["window_s"],
                      "train_step_programs_on_device0":
                          trace_reduce.steps_traced(
                              traced, summary["devices"][0], "train_step")
                          if summary["devices"] else None})
        trace_reduce.save_extract(
            traced, os.path.join(out_dir, "trace_extract.json.gz"))

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    facts = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "trace": bool(trace), "mesh": dict(spark.mesh.shape),
        "global_batch": batch_size, "items_per_step": items_per_step,
        "os_cpu_count": os.cpu_count(),
        "versions": {"jax": jax.__version__, "libtpu": libtpu,
                     "python": sys.version.split()[0]},
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "cache_hits": watch.cache_hits, "cache_misses": watch.cache_misses,
        "compile_stages_s": dict(watch.stage_s), "train_step": ledger,
        "executable": exe_facts, "feed": feed["facts"],
        "window": {k: v for k, v in res.items() if k != "reasons"},
        "setup_split_s": {
            # a seed the cache has not seen pays for its data in "feed_built"
            "seed_cache_hit": feed["facts"].get("seed_cache_hit"),
            "process_to_session_begin": clock["session_begin"] - t_process,
            "session": clock["session"] - clock["session_begin"],
            "feed_built": clock["feed_built"] - clock["session"],
            "to_first_step": (win.first_step_t or math.nan)
            - clock["feed_built"],
            "first_step_to_window": ((win.open_at.t if win.open_at
                                      else math.nan)
                                     - (win.first_step_t or math.nan)),
        },
        "peak_hbm_bytes_per_device": mem, "memory_stats_device0": mem_stats[0],
        "reference": ref_facts,
        "layer_facts": ctx["facts"], "out_dir": out_dir,
    }
    own_line = ("train_step", "executable", "setup_split_s", "reference",
                "layer_facts")
    for k in own_line:
        say(k, facts[k])
    # the whole lists are in facts.json; the line keeps their ends
    say("window", {k: (v[:3] + ["..."] + v[-3:]
                       if isinstance(v, list) and len(v) > 8 else v)
                   for k, v in facts["window"].items()})
    say("run", {k: v for k, v in facts.items()
                if k not in own_line + ("window",)})
    for r in reasons:
        say("NOT CORRECT", {"reason": r})
    with open(os.path.join(out_dir, "facts.json"), "w") as f:
        json.dump({"facts": facts, "result": result, "reasons": reasons}, f,
                  indent=1, default=str)
    spark.stop()
    result["facts"] = facts
    return result
