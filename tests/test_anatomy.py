"""Device-side performance observatory (ISSUE 10): compile ledger,
step anatomy, MFU arithmetic, memory watermarks, `dlstatus --anatomy`."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearningspark_tpu import status, telemetry
from distributeddeeplearningspark_tpu.telemetry import anatomy, spans


@pytest.fixture
def workdir(tmp_path):
    """Bind the process-global telemetry writer to a temp workdir and
    always unbind after (the ledger emits through the global writer)."""
    telemetry.configure(tmp_path)
    yield str(tmp_path)
    telemetry.reset()


# -- compile ledger -----------------------------------------------------------


def test_compile_event_schema_and_phase_span(workdir):
    fn = anatomy.instrument(jax.jit(lambda x: x * 2 + 1), name="double")
    out = fn(jnp.ones((4, 4), jnp.float32))
    assert np.allclose(np.asarray(out), 3.0)
    events = telemetry.read_events(workdir)
    comp = [e for e in events if e["kind"] == "compile"]
    assert len(comp) == 1
    e = comp[0]
    assert e["fn"] == "double"
    assert "f32[4,4]" in e["sig"]
    assert isinstance(e["sig_hash"], str) and len(e["sig_hash"]) == 16
    assert e["compile_s"] > 0
    assert e["flops"] and e["flops"] > 0          # cost analysis rode along
    assert e["bytes_accessed"] and e["bytes_accessed"] > 0
    assert e["recompile"] is False and e["aot"] is True
    assert e["sig_compiles"] == 1 and e["distinct_signatures"] == 1
    # the compile is ALSO a phase span, so goodput accounts the stall
    phases = [p for p in events
              if p["kind"] == "phase" and p.get("name") == "compile"]
    assert any(p.get("edge") == "begin" for p in phases)
    assert any(p.get("edge") == "end" for p in phases)
    assert telemetry.goodput(events)["compile_s"] >= 0.0
    # same signature again: dict hit, no new compile, same result
    fn(jnp.ones((4, 4), jnp.float32))
    comp2 = [e for e in telemetry.read_events(workdir)
             if e["kind"] == "compile"]
    assert len(comp2) == 1
    assert fn._cache_size() == 1


def test_compile_errors_propagate_and_are_paid_once(workdir):
    """A failure of lower().compile() (a Mosaic rejection, a compile-time
    OOM) surfaces from the ledger as itself: no retry through plain jit, no
    'AOT unavailable' downgrade."""
    calls = {"lower": 0, "jit": 0}

    class Rejecting:
        def lower(self, *a):
            calls["lower"] += 1
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        def __call__(self, *a, **kw):
            calls["jit"] += 1
            raise AssertionError("the failed compile was retried through jit")

    fn = anatomy.instrument(Rejecting(), name="rejected")
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        fn(jnp.ones((4,), jnp.float32))
    assert calls == {"lower": 1, "jit": 0}
    assert fn.records == [] and fn.executables() == []
    assert fn.compile_summary()["aot"] is True
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        fn.prepare(jnp.ones((4,), jnp.float32))


def test_executables_expose_key_and_program_text(workdir):
    fn = anatomy.instrument(jax.jit(lambda x: x * 2 + 1), name="double")
    x = jnp.ones((4, 4), jnp.float32)
    fn(x)
    ((treedef, sigs, shardings), compiled), = fn.executables()
    assert sigs == (((4, 4), "float32"),)
    assert shardings == (x.sharding,)
    assert jax.tree_util.tree_unflatten(treedef, ["leaf"]) == ("leaf",)
    assert "multiply" in compiled.as_text()


def test_second_shape_flags_exactly_one_recompile(workdir):
    """A shape-stable step (expected_signatures=1) forced through a second
    shape flags EXACTLY one recompile — the acceptance drill."""
    fn = anatomy.instrument(jax.jit(lambda x: x + 1), name="step")
    fn(jnp.ones((8,)))
    fn(jnp.ones((16,)))          # the forced second shape
    fn(jnp.ones((16,)))          # reuse: no further compile
    comp = [e for e in telemetry.read_events(workdir)
            if e["kind"] == "compile"]
    assert len(comp) == 2
    assert [e["recompile"] for e in comp] == [False, True]
    assert fn.compile_summary()["flagged_recompiles"] == 1
    rep = anatomy.anatomy_report(telemetry.read_events(workdir))
    assert rep["compile_ledger"]["flagged_recompiles"] == 1
    assert rep["verdicts"]["recompile"].startswith("RECOMPILES")


def test_expected_signatures_pins_a_bucket_ladder(workdir):
    """The serve-engine discipline: a pinned ladder of N shapes is clean;
    shape N+1 flags."""
    fn = anatomy.instrument(jax.jit(lambda x: x.sum()), name="fwd",
                            expected_signatures=2)
    fn(jnp.ones((2,)))
    fn(jnp.ones((4,)))
    comp = [e for e in telemetry.read_events(workdir)
            if e["kind"] == "compile"]
    assert [e["recompile"] for e in comp] == [False, False]
    fn(jnp.ones((8,)))           # beyond the pinned ladder
    comp = [e for e in telemetry.read_events(workdir)
            if e["kind"] == "compile"]
    assert [e["recompile"] for e in comp] == [False, False, True]


def test_dtype_change_is_a_new_signature(workdir):
    fn = anatomy.instrument(jax.jit(lambda x: x * 1), name="cast")
    fn(jnp.ones((4,), jnp.float32))
    fn(jnp.ones((4,), jnp.int32))
    comp = [e for e in telemetry.read_events(workdir)
            if e["kind"] == "compile"]
    assert len(comp) == 2
    assert comp[0]["sig_hash"] != comp[1]["sig_hash"]


def test_prepare_compiles_once_and_reports_flops(workdir):
    fn = anatomy.instrument(jax.jit(lambda a, b: a @ b), name="mm")
    a = jnp.ones((8, 8))
    rec = fn.prepare(a, a)
    assert rec["flops"] == pytest.approx(2 * 8 * 8 * 8, rel=0.5)
    assert fn.flops_per_step == rec["flops"]
    fn(a, a)  # dispatches on the prepared executable — no second compile
    comp = [e for e in telemetry.read_events(workdir)
            if e["kind"] == "compile"]
    assert len(comp) == 1


def test_instrument_is_idempotent_and_exposes_lower():
    fn = anatomy.instrument(jax.jit(lambda x: x), name="id")
    assert anatomy.instrument(fn, name="other") is fn
    lowered = fn.lower(jnp.ones((2,)))
    assert lowered.compile() is not None


def test_donated_state_dispatch(workdir):
    """The trainer shape: donated arg 0, repeated dispatch on the same
    executable (the donation chain must survive AOT dispatch)."""
    step = anatomy.instrument(
        jax.jit(lambda s, x: (s + x, (s * x).sum()), donate_argnums=(0,)),
        name="train_step")
    s = jnp.zeros((16,))
    x = jnp.ones((16,))
    for i in range(3):
        s, m = step(s, x)
    assert float(s[0]) == 3.0
    comp = [e for e in telemetry.read_events(workdir)
            if e["kind"] == "compile"]
    assert len(comp) == 1 and comp[0]["recompile"] is False


# -- step anatomy / MFU arithmetic -------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_step_anatomy_split_and_mfu_arithmetic(monkeypatch):
    """Hand-computed case: 10 steps of 2e9 FLOPs over a 4-chip mesh in a
    10s lap with a 1e9 FLOPs/s/chip peak → MFU = 2e9*10/10/4/1e9 = 0.5.
    The split must tile the lap: device 6s (4 dispatch + 2 drain), compile
    1s, input 0.5s, host = the 2.5s residual."""
    monkeypatch.setenv(anatomy.PEAK_FLOPS_ENV, "1e9")
    clock = FakeClock()
    anat = anatomy.StepAnatomy(clock=clock)
    anat.reset()
    anat.add("dls.step/compile", 1.0)
    anat.add("dls.step/dispatch", 4.0)
    clock.t = 8.0
    with spans.span("dls.fit/sync", anat):
        clock.t = 10.0
    rec = anat.lap(steps=10, input_wait_s=0.5, flops_per_step=2e9,
                   num_chips=4)
    assert rec["anatomy_wall_s"] == 10.0
    assert rec["device_s"] == 6.0
    assert rec["device_dispatch_s"] == 4.0
    assert rec["device_drain_s"] == 2.0
    assert rec["compile_in_lap_s"] == 1.0
    assert rec["host_s"] == pytest.approx(2.5)
    assert rec["mfu"] == pytest.approx(0.5)
    assert rec["mfu_device"] == pytest.approx(2e9 * 10 / 6.0 / 4 / 1e9)
    assert rec["peak_flops_per_chip"] == 1e9
    assert rec["peak_source"] == anatomy.PEAK_FLOPS_ENV
    # lap() reset: a second, empty lap is all host
    clock.t = 12.0
    rec2 = anat.lap(steps=0)
    assert rec2["anatomy_wall_s"] == 2.0
    assert rec2["device_s"] == 0.0 and rec2["host_s"] == 2.0
    assert "mfu" not in rec2


def test_resolve_peak_flops_order(monkeypatch):
    monkeypatch.setenv(anatomy.PEAK_FLOPS_ENV, "123.5")
    peak, source = anatomy.resolve_peak_flops()
    assert peak == 123.5 and source == anatomy.PEAK_FLOPS_ENV
    monkeypatch.delenv(anatomy.PEAK_FLOPS_ENV)
    peak, source = anatomy.resolve_peak_flops()
    # the suite runs on the CPU backend: the labeled nominal fallback
    assert peak and peak > 0 and source.startswith("nominal-cpu")
    monkeypatch.setenv(anatomy.PEAK_FLOPS_ENV, "not-a-number")
    peak2, _ = anatomy.resolve_peak_flops()
    assert peak2 == peak  # malformed override ignored, not fatal


def test_unknown_accelerator_is_an_error_not_a_default():
    """An accelerator whose device_kind has no peaks entry raises; the host
    CPU simply has no spec peak (so no utilization is computed from one)."""
    import types

    from distributeddeeplearningspark_tpu import metrics

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert metrics.spec_peak_flops(v5e) == 197e12
    assert metrics.spec_peak_flops(jax.devices()[0]) is None
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 mega")
    with pytest.raises(ValueError, match="TPU v9 mega"):
        metrics.spec_peak_flops(unknown)
    with pytest.raises(ValueError, match="TPU v9 mega"):
        metrics.device_peak_flops(unknown)


# -- memory watermarks --------------------------------------------------------


def test_memory_watermarks_cpu_fallback():
    """This backend exposes no allocator stats → the live-buffer path."""
    keep = jnp.ones((1024,), jnp.float32)  # noqa: F841 — held live
    rec = anatomy.memory_watermarks()
    assert rec["source"] == "live-buffers"
    assert rec["devices"] >= 1
    assert rec["live_bytes"] >= keep.nbytes


def test_memory_watermarks_count_reserved_bytes_and_the_hbm_rule_sees_them(
        monkeypatch):
    """The TPU allocator counts a running program's temporaries as
    ``peak_bytes_reserved`` (BERT-base at 32 a chip: 1.57 + 6.30 GB of 16):
    headroom is the limit less the sum, per device, and the ``hbm`` rule
    reads that figure, not the one five times too roomy."""
    import types

    from distributeddeeplearningspark_tpu.telemetry import health

    gb = 10 ** 9
    stats = [
        {"bytes_in_use": 1 * gb, "peak_bytes_in_use": 2 * gb,
         "peak_bytes_reserved": 13 * gb, "bytes_limit": 16 * gb},
        {"bytes_in_use": 3 * gb, "peak_bytes_in_use": 4 * gb,
         "peak_bytes_reserved": 6 * gb, "bytes_limit": 16 * gb},
    ]
    monkeypatch.setattr(jax, "local_devices", lambda: [
        types.SimpleNamespace(memory_stats=lambda s=s: s) for s in stats])
    rec = anatomy.memory_watermarks()
    assert rec["source"] == "memory_stats" and rec["devices"] == 2
    assert rec["bytes_in_use_max"] == 3 * gb
    assert rec["peak_bytes_in_use_max"] == 4 * gb
    assert rec["peak_bytes_reserved_max"] == 13 * gb
    # device 0 held 2 + 13 of its 16 GB: 1 GB of headroom, not 16 - 4
    assert rec["headroom_bytes"] == 1 * gb
    events = [{"ts": 1.0, "kind": "memory", "process": "p0", **rec}]
    mem = anatomy.anatomy_report(events)["memory"]
    assert mem["peak_bytes_reserved_max"] == 13 * gb
    assert mem["headroom_bytes"] == 1 * gb
    (alert,) = health._rule_hbm({"anatomy": {"memory": mem}})
    assert alert["severity"] == "WARN" and alert["rule"] == "hbm"
    # a backend that reports no reserved bytes: in-use peaks alone, as before
    for s in stats:
        del s["peak_bytes_reserved"]
    rec = anatomy.memory_watermarks()
    assert "peak_bytes_reserved_max" not in rec
    assert rec["headroom_bytes"] == 12 * gb
    # and a stream from before ``headroom_bytes`` counted them still folds
    old = {"ts": 1.0, "kind": "memory", "process": "p0",
           "source": "memory_stats", "bytes_in_use_max": 100,
           "peak_bytes_in_use_max": 150, "bytes_limit_min": 1000}
    assert anatomy.anatomy_report([old])["memory"]["headroom_bytes"] == 850


def test_memory_fold_prefers_stats_and_computes_headroom():
    events = [
        {"ts": 1.0, "kind": "memory", "process": "p0",
         "source": "memory_stats", "bytes_in_use_max": 100,
         "peak_bytes_in_use_max": 150, "bytes_limit_min": 1000,
         "headroom_bytes": 850},
        {"ts": 2.0, "kind": "memory", "process": "p1",
         "source": "memory_stats", "bytes_in_use_max": 200,
         "peak_bytes_in_use_max": 300, "bytes_limit_min": 900,
         "headroom_bytes": 600},
        {"ts": 3.0, "kind": "memory", "process": "bench",
         "source": "live-buffers", "live_bytes": 7},
    ]
    rep = anatomy.anatomy_report(events)
    mem = rep["memory"]
    assert mem["source"] == "memory_stats"
    assert mem["bytes_in_use_max"] == 200
    assert mem["peak_bytes_in_use_max"] == 300
    assert mem["bytes_limit_min"] == 900
    assert mem["headroom_bytes"] == 600
    # live-buffer-only stream falls back
    rep2 = anatomy.anatomy_report([events[-1]])
    assert rep2["memory"] == {"source": "live-buffers", "live_bytes": 7}


# -- reader fold / dlstatus ---------------------------------------------------


def _lap_event(proc, ts, *, steps=10, wall=10.0, device=6.0, dispatch=4.0,
               drain=2.0, host=2.5, compile_s=1.0, input_wait=0.5,
               flops=2e9, peak=1e9, chips=4, mfu=0.5):
    return {"ts": ts, "kind": "step_metrics", "process": proc, "step": steps,
            "steps": steps, "lap_s": wall, "input_wait_s": input_wait,
            "anatomy_wall_s": wall, "device_s": device,
            "device_dispatch_s": dispatch, "device_drain_s": drain,
            "host_s": host, "compile_in_lap_s": compile_s,
            "num_chips": chips, "peak_flops_per_chip": peak,
            "peak_source": "DLS_PEAK_FLOPS", "flops_per_step": flops,
            "mfu": mfu}


def test_anatomy_report_fold_totals_and_verdicts():
    events = [
        {"ts": 0.0, "kind": "compile", "process": "p0", "fn": "train_step",
         "sig": "f32[8]", "sig_hash": "aa", "compile_s": 2.0, "flops": 2e9,
         "bytes_accessed": 1e6, "recompile": False, "aot": True},
        _lap_event("p0", 10.0),
        _lap_event("p0", 20.0),
    ]
    rep = anatomy.anatomy_report(events)
    st = rep["steps"]
    assert st["laps"] == 2 and st["steps"] == 20
    assert st["wall_s"] == 20.0 and st["device_s"] == 12.0
    assert st["coverage"] == pytest.approx(1.0)
    assert st["fractions"]["device"] == pytest.approx(0.6)
    # aggregate MFU: 2e9*20 flops over 20s on 4 chips at 1e9 peak = 0.5
    assert rep["mfu"]["mfu"] == pytest.approx(0.5)
    assert rep["mfu"]["num_chips"] == 4
    assert rep["verdicts"]["recompile"].startswith("OK")
    assert rep["verdicts"]["bound"].startswith("device-bound")
    assert rep["per_process"]["p0"]["laps"] == 2
    # an empty stream has no report at all
    assert anatomy.anatomy_report([{"ts": 0, "kind": "heartbeat"}]) is None


def test_anatomy_report_folds_the_loops_named_sections(tmp_path, capsys):
    """The new keys fold beside host_s, and ``dlstatus --anatomy`` prints the
    loop's split on one line; a stream without them prints no such line."""
    split = {"input_put_s": 0.25, "emit_s": 0.5, "callbacks_s": 0.125,
             "checkpoint_s": 1.0, "eval_s": 0.0, "unaccounted_s": 0.625}
    laps = [{**_lap_event("p0", 10.0), **split},
            {**_lap_event("p0", 20.0), **split}]
    st = anatomy.anatomy_report(laps)["steps"]
    assert {k: st[k] for k in split} == {k: 2 * v for k, v in split.items()}
    assert tuple(split) == anatomy.LOOP_SPLIT_KEYS
    assert st["host_s"] == 5.0 and st["coverage"] == pytest.approx(1.0)
    w = telemetry.EventWriter(tmp_path, process="p0", clock=FakeClock(),
                              host=0)
    for e in laps:
        w.emit("step_metrics", **{k: v for k, v in e.items()
                                  if k not in ("ts", "kind", "process")})
    w.close()
    assert status.main([str(tmp_path), "--anatomy"]) == 0
    out = capsys.readouterr().out
    (line,) = [ln for ln in out.splitlines() if "of it:" in ln]
    assert line.split("of it: ")[1] == (
        "put 0.50s  emit 1.00s  callbacks 0.25s  checkpoint 2.00s  "
        "eval 0.00s  unaccounted 1.25s")
    lines = status.render_anatomy(anatomy.anatomy_report(
        [_lap_event("p0", 10.0)]))
    assert not any("of it:" in ln for ln in lines)


def test_anatomy_report_cross_process_duplicates_are_not_flagged():
    """A restart re-pays the compile of the SAME signature: reported as a
    duplicate (restarts re-pay jit), not flagged as a recompile storm."""
    ev = {"kind": "compile", "fn": "train_step", "sig": "f32[8]",
          "sig_hash": "aa", "compile_s": 1.0, "recompile": False,
          "aot": True}
    events = [{"ts": 0.0, "process": "p0", **ev},
              {"ts": 10.0, "process": "p0", **ev}]
    rep = anatomy.anatomy_report(events)
    assert rep["compile_ledger"]["flagged_recompiles"] == 0
    assert rep["compile_ledger"]["duplicate_signatures"] == 1
    assert "re-paid" in rep["verdicts"]["recompile"]


def test_dlstatus_anatomy_json_schema(tmp_path, capsys):
    w = telemetry.EventWriter(tmp_path, process="p0", clock=FakeClock(),
                              host=0)
    w.emit("compile", fn="train_step", sig="f32[8]", sig_hash="ab",
           compile_s=2.0, flops=2e9, bytes_accessed=1e6, recompile=False,
           aot=True)
    w.emit("step_metrics", **{k: v for k, v in
                              _lap_event("p0", 0.0).items()
                              if k not in ("ts", "kind", "process")})
    w.emit("memory", source="live-buffers", devices=8, live_bytes=4096)
    w.close()
    rc = status.main([str(tmp_path), "--anatomy", "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    an = rep["anatomy"]
    for key in ("compile_ledger", "steps", "mfu", "memory", "per_process",
                "verdicts"):
        assert key in an, key
    cl = an["compile_ledger"]
    for key in ("compiles", "distinct_signatures", "flagged_recompiles",
                "duplicate_signatures", "total_compile_s", "by_fn",
                "events"):
        assert key in cl, key
    for key in ("laps", "steps", "wall_s", "device_s", "device_dispatch_s",
                "device_drain_s", "host_s", "compile_s", "input_wait_s",
                "coverage", "fractions"):
        assert key in an["steps"], key
    for key in ("mfu", "mfu_last_lap", "flops_per_step",
                "peak_flops_per_chip", "peak_source", "num_chips"):
        assert key in an["mfu"], key
    assert an["memory"]["live_bytes"] == 4096
    # the human rendering carries the section too
    rc = status.main([str(tmp_path), "--anatomy"])
    out = capsys.readouterr().out
    assert rc == 0 and "device anatomy:" in out and "compile ledger:" in out


def test_dlstatus_watch_mode(tmp_path, capsys):
    """--watch re-reads and re-renders; bounded by --watch-count for tests,
    and an empty workdir waits instead of exiting 1."""
    rc = status.main([str(tmp_path), "--watch", "--watch-count", "2",
                      "--interval", "0.11"])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.count("no telemetry events yet") == 2
    w = telemetry.EventWriter(tmp_path, process="p0", clock=FakeClock())
    w.heartbeat(step=3)
    w.close()
    rc = status.main([str(tmp_path), "--watch", "--watch-count", "1",
                      "--interval", "0.11", "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["last_step"] == 3


def test_chrome_trace_memory_counter_track(tmp_path):
    from distributeddeeplearningspark_tpu.telemetry import trace as trace_lib

    events = [
        {"ts": 1.0, "kind": "phase", "process": "p0", "name": "compile",
         "edge": "begin"},
        {"ts": 3.0, "kind": "phase", "process": "p0", "name": "compile",
         "edge": "end", "dur_s": 2.0},
        {"ts": 2.0, "kind": "memory", "process": "p0",
         "source": "live-buffers", "live_bytes": 1234},
    ]
    data = trace_lib.chrome_trace(events)
    counters = [e for e in data["traceEvents"] if e.get("ph") == "C"]
    assert len(counters) == 1
    assert counters[0]["args"] == {"live_bytes": 1234}
    spans = [e for e in data["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "compile"]
    assert len(spans) == 1  # the compile phase lowered into the export
    # memory events alone still produce a loadable trace
    data2 = trace_lib.chrome_trace([events[-1]])
    assert any(e.get("ph") == "C" for e in data2["traceEvents"])


# -- serve-side compile visibility (satellite) --------------------------------


def test_engine_warmup_emits_compile_phases(tmp_path):
    """engine.warmup()'s bucket-ladder compiles must land as `compile`
    phases + ledger events — warmup seconds were silently misattributed
    before (ISSUE 10 satellite)."""
    from distributeddeeplearningspark_tpu.serve.engine import InferenceEngine

    def forward(params, batch):
        return {"y": batch["x"] * params["w"]}

    eng = InferenceEngine(forward, {"w": jnp.float32(2.0)}, max_batch=4,
                          workdir=str(tmp_path), name="anat")
    try:
        n = eng.warmup({"x": np.float32(1.0)})
        assert n == len(eng.batch_sizes)
        events = telemetry.read_events(tmp_path)
        comp = [e for e in events if e["kind"] == "compile"]
        assert len(comp) == len(eng.batch_sizes)
        assert all(e["fn"] == "serve-anat" for e in comp)
        assert not any(e["recompile"] for e in comp)
        phases = [e for e in events if e["kind"] == "phase"
                  and e.get("name") == "compile" and e.get("edge") == "end"]
        assert len(phases) == len(eng.batch_sizes)
        # goodput now accounts the warmup stall as compile time
        assert telemetry.goodput(events)["compile_s"] > 0
        # the pinned-compile-set stat still reads through the wrapper
        assert eng.stats()["compiled_batch_shapes"] == len(eng.batch_sizes)
        # traffic through a warmed bucket adds NO compile
        with eng:
            eng.infer({"x": np.float32(3.0)})
        comp2 = [e for e in telemetry.read_events(tmp_path)
                 if e["kind"] == "compile"]
        assert len(comp2) == len(comp)
    finally:
        eng.stop()
        telemetry.reset()
