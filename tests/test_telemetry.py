"""Run telemetry: event bus, goodput accounting, starvation probe, dlstatus.

Everything here runs on fake clocks — no sleeps, no real-time dependence —
because the goodput accountant is a pure fold over timestamped records and
the probe takes an injectable clock. The gang-level acceptance drill
(supervised crash → dlstatus report) lives in test_chaos.py with the other
recovery drills.
"""

import json
import logging
import os

import numpy as np
import pytest

from distributeddeeplearningspark_tpu import status, telemetry
from distributeddeeplearningspark_tpu.data.prefetch import (
    StarvationProbe,
    prefetch_to_device,
)
from distributeddeeplearningspark_tpu.metrics import Meter, MetricLogger, _log_value


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def _writer(tmp_path, process="p0", t0=0.0):
    clock = FakeClock(t0)
    return telemetry.EventWriter(tmp_path, process=process, clock=clock), clock


# -- event bus ---------------------------------------------------------------


def test_writer_appends_typed_records(tmp_path):
    w, clock = _writer(tmp_path)
    w.step_metrics(10, steps=10, lap_s=2.5, metrics={"loss": 1.0})
    clock.tick(1.0)
    w.recovery(10, "skip", skipped_steps=1)
    w.heartbeat(step=10)
    w.close()
    events = telemetry.read_events(tmp_path)
    assert [e["kind"] for e in events] == ["step_metrics", "recovery",
                                           "heartbeat"]
    assert events[0]["metrics"] == {"loss": 1.0}
    assert events[0]["process"] == "p0"
    assert events[1]["event"] == "skip" and events[1]["ts"] == 1.0
    # the file lands where the contract says
    assert os.path.exists(tmp_path / "telemetry" / "events-p0.jsonl")


def test_multi_process_merge_is_ts_ordered_and_stable(tmp_path):
    """Files from different processes interleave by timestamp; equal
    timestamps keep per-file (append) order — the merge contract dlstatus
    timelines rely on."""
    a, ca = _writer(tmp_path, "p0")
    b, cb = _writer(tmp_path, "supervisor")
    ca.t = 1.0
    a.emit("heartbeat", seq="a1")
    cb.t = 0.5
    b.emit("attempt", edge="begin", ordinal=0, seq="b1")
    ca.t = 2.0
    a.emit("heartbeat", seq="a2")
    cb.t = 2.0  # ties: file order (events-p0 sorts before events-supervisor)
    b.emit("attempt", edge="end", ordinal=0, seq="b2")
    a.close(), b.close()
    seqs = [e["seq"] for e in telemetry.read_events(tmp_path)]
    assert seqs == ["b1", "a1", "a2", "b2"]


def test_torn_tail_and_garbage_lines_are_skipped(tmp_path):
    """A SIGKILL'd writer can leave a half-written last line; a crashed-run
    stream must still parse (minus only the torn record)."""
    w, clock = _writer(tmp_path)
    w.emit("heartbeat", step=1)
    clock.tick(1.0)
    w.emit("heartbeat", step=2)
    w.close()
    path = tmp_path / "telemetry" / "events-p0.jsonl"
    with open(path, "a") as f:
        f.write("not json at all\n")
        f.write('{"ts": 3.0, "kind": "heartbeat", "step": 3')  # torn, no \n
    events = telemetry.read_events(tmp_path)
    assert [e["step"] for e in events] == [1, 2]


def test_reader_accepts_workdir_or_telemetry_dir(tmp_path):
    w, _ = _writer(tmp_path)
    w.emit("heartbeat", step=1)
    w.close()
    assert len(telemetry.read_events(tmp_path)) == 1
    assert len(telemetry.read_events(tmp_path / "telemetry")) == 1


def test_singleton_configure_reset(tmp_path):
    assert telemetry.get() is None
    telemetry.emit("heartbeat")  # unconfigured: silent no-op
    w = telemetry.configure(tmp_path)
    assert telemetry.configure(tmp_path) is w  # idempotent per workdir
    telemetry.emit("heartbeat", step=7)
    with telemetry.phase("checkpoint"):
        pass
    telemetry.reset()
    assert telemetry.get() is None
    kinds = [e["kind"] for e in telemetry.read_events(tmp_path)]
    assert kinds == ["heartbeat", "phase", "phase"]


# -- goodput accounting ------------------------------------------------------


def _ev(ts, kind, **f):
    return {"ts": ts, "kind": kind, "process": f.pop("process", "p0"), **f}


def test_goodput_components_sum_to_wall():
    events = [
        _ev(0.0, "phase", name="run", edge="begin"),
        _ev(0.0, "phase", name="compile", edge="begin"),
        _ev(10.0, "phase", name="compile", edge="end", dur_s=10.0),
        _ev(20.0, "step_metrics", step=10, steps=10, lap_s=10.0,
            input_wait_s=2.0),
        _ev(20.0, "phase", name="checkpoint", edge="begin"),
        _ev(25.0, "phase", name="checkpoint", edge="end", dur_s=5.0),
        _ev(30.0, "phase", name="eval", edge="begin"),
        _ev(34.0, "phase", name="eval", edge="end", dur_s=4.0),
        _ev(100.0, "phase", name="run", edge="end"),
    ]
    g = telemetry.goodput(events)
    assert g["wall_s"] == 100.0
    assert g["compile_s"] == 10.0
    assert g["checkpoint_s"] == 5.0
    assert g["eval_s"] == 4.0
    assert g["input_starved_s"] == 2.0
    assert g["productive_s"] == 100.0 - 10.0 - 5.0 - 4.0 - 2.0
    assert g["goodput_frac"] == pytest.approx(0.79)
    total = sum(g[k] for k in telemetry.GOODPUT_COMPONENTS)
    assert total == pytest.approx(g["wall_s"])


def test_goodput_overlapping_spans_count_once():
    """Within a category overlaps merge by union; across categories the
    productive residual subtracts the union of ALL spans, so a span nested
    inside another is never deducted twice."""
    events = [
        _ev(0.0, "heartbeat"),
        # two overlapping compile spans: [0,10] + [5,15] -> 15s, not 20
        _ev(0.0, "phase", name="compile", edge="begin"),
        _ev(10.0, "phase", name="compile", edge="end", dur_s=10.0),
        _ev(5.0, "phase", name="compile", edge="begin", process="p1"),
        _ev(15.0, "phase", name="compile", edge="end", dur_s=10.0,
            process="p1"),
        # restore nested inside the compile window
        _ev(8.0, "phase", name="restore", edge="begin"),
        _ev(12.0, "phase", name="restore", edge="end", dur_s=4.0),
        _ev(30.0, "heartbeat"),
    ]
    g = telemetry.goodput(events)
    assert g["compile_s"] == 15.0
    assert g["restore_s"] == 4.0
    # union of everything is [0,15] -> productive = 30 - 15
    assert g["productive_s"] == 15.0
    assert g["goodput_frac"] == pytest.approx(0.5)


def test_goodput_crashed_run_partial_stream():
    """A phase whose end never arrived (the process died inside it) is
    accounted up to the last event seen — a crashed stream under-reports
    nothing silently."""
    events = [
        _ev(0.0, "heartbeat"),
        _ev(10.0, "phase", name="compile", edge="begin"),
        _ev(30.0, "heartbeat"),  # last sign of life
    ]
    g = telemetry.goodput(events)
    assert g["wall_s"] == 30.0
    assert g["compile_s"] == 20.0
    assert g["productive_s"] == 10.0


def test_goodput_orphaned_phase_capped_at_attempt_end():
    """A phase left open by a SIGKILL mid-checkpoint must be accounted up
    to the supervisor reaping that attempt — NOT to the end of the merged
    stream, which would swallow the relaunch's hour of productive time."""
    events = [
        _ev(0.0, "attempt", edge="begin", ordinal=0, process="supervisor"),
        _ev(60.0, "phase", name="checkpoint", edge="begin"),
        # SIGKILL here: no end ever arrives for p0's checkpoint span
        _ev(65.0, "attempt", edge="end", ordinal=0, process="supervisor"),
        _ev(70.0, "attempt", edge="begin", ordinal=1, process="supervisor"),
        _ev(3600.0, "attempt", edge="end", ordinal=1, process="supervisor"),
    ]
    g = telemetry.goodput(events)
    assert g["checkpoint_s"] == 5.0  # 60 -> 65, not 60 -> 3600
    assert g["restart_overhead_s"] == 5.0
    assert g["productive_s"] == 3600.0 - 10.0


def test_goodput_orphaned_phase_unsupervised_caps_at_process_silence():
    """Without a supervisor, the orphan is bounded by the opening process's
    own last event — the moment it went silent."""
    events = [
        _ev(0.0, "heartbeat", process="p0"),
        _ev(10.0, "phase", name="compile", edge="begin", process="p0"),
        _ev(30.0, "heartbeat", process="p0"),
        _ev(100.0, "heartbeat", process="p1"),  # another process lives on
    ]
    g = telemetry.goodput(events)
    assert g["compile_s"] == 20.0  # 10 -> 30 (p0's silence), not 10 -> 100


def test_goodput_restart_gap_between_attempts():
    events = [
        _ev(0.0, "attempt", edge="begin", ordinal=0, process="supervisor"),
        _ev(50.0, "attempt", edge="end", ordinal=0, process="supervisor"),
        _ev(60.0, "attempt", edge="begin", ordinal=1, process="supervisor"),
        _ev(100.0, "attempt", edge="end", ordinal=1, process="supervisor"),
    ]
    g = telemetry.goodput(events)
    assert g["restart_overhead_s"] == 10.0
    assert g["productive_s"] == 90.0


def test_goodput_idle_between_sessions_not_productive():
    """Stop today, resume tomorrow into the same workdir: the gap between
    run spans is idle_s, not a 99%-goodput lie."""
    events = [
        _ev(0.0, "phase", name="run", edge="begin"),
        _ev(100.0, "phase", name="run", edge="end"),
        _ev(1100.0, "phase", name="run", edge="begin"),  # resumed much later
        _ev(1200.0, "phase", name="run", edge="end"),
    ]
    g = telemetry.goodput(events)
    assert g["idle_s"] == 1000.0
    assert g["productive_s"] == 200.0
    assert g["goodput_frac"] == pytest.approx(200.0 / 1200.0)
    assert sum(g[k] for k in telemetry.GOODPUT_COMPONENTS) == \
        pytest.approx(g["wall_s"])


def test_goodput_crashed_then_resumed_gap_is_idle():
    """A SIGKILL'd run never closes its run span; when the workdir is
    resumed later, the dead gap must land in idle_s, not inflate the
    productive residual toward goodput_frac ~1.0."""
    events = [
        _ev(0.0, "phase", name="run", edge="begin"),
        _ev(95.0, "heartbeat", step=10),  # last sign of life, then SIGKILL
        _ev(1000.0, "phase", name="run", edge="begin"),  # resumed next day
        _ev(1100.0, "phase", name="run", edge="end"),
    ]
    g = telemetry.goodput(events)
    assert g["idle_s"] == 1000.0 - 95.0
    assert g["productive_s"] == pytest.approx(95.0 + 100.0)
    assert sum(g[k] for k in telemetry.GOODPUT_COMPONENTS) == \
        pytest.approx(g["wall_s"])


def test_goodput_supervised_relaunch_gap_is_restart_not_idle():
    """A clean-exit worker relaunched by the supervisor closes its run span
    before the restart gap; the supervisor's gap stays restart_overhead_s
    and only the teardown/startup tails outside it count as idle."""
    events = [
        _ev(0.0, "attempt", edge="begin", ordinal=0, process="supervisor"),
        _ev(1.0, "phase", name="run", edge="begin"),
        _ev(49.0, "phase", name="run", edge="end"),
        _ev(50.0, "attempt", edge="end", ordinal=0, process="supervisor"),
        _ev(60.0, "attempt", edge="begin", ordinal=1, process="supervisor"),
        _ev(61.0, "phase", name="run", edge="begin"),
        _ev(99.0, "phase", name="run", edge="end"),
        _ev(100.0, "attempt", edge="end", ordinal=1, process="supervisor"),
    ]
    g = telemetry.goodput(events)
    assert g["restart_overhead_s"] == 10.0
    # run-end 49 -> run-begin 61 minus the restart interval [50, 60]:
    # 1s worker teardown + 1s relaunch startup, not double-counted
    assert g["idle_s"] == pytest.approx(2.0)
    assert sum(g[k] for k in telemetry.GOODPUT_COMPONENTS) == \
        pytest.approx(g["wall_s"])


def test_goodput_hang_dwell_not_productive():
    """A hang: the worker goes silent at t=100, the watchdog reaps it at
    t=400, relaunch runs on. The 300s dwell plus the startup tail must not
    land in the productive residual (only trimmed of the restart gap)."""
    events = [
        _ev(0.0, "attempt", edge="begin", ordinal=0, process="supervisor"),
        _ev(1.0, "phase", name="run", edge="begin"),
        _ev(100.0, "heartbeat", step=10),  # last sign of life; hang
        _ev(400.0, "attempt", edge="end", ordinal=0, process="supervisor"),
        _ev(401.0, "attempt", edge="begin", ordinal=1, process="supervisor"),
        _ev(405.0, "phase", name="run", edge="begin"),
        _ev(500.0, "phase", name="run", edge="end"),
        _ev(501.0, "attempt", edge="end", ordinal=1, process="supervisor"),
    ]
    g = telemetry.goodput(events)
    assert g["restart_overhead_s"] == 1.0
    assert g["idle_s"] == pytest.approx(304.0)  # (100,400) + (401,405)
    assert g["productive_s"] == pytest.approx(501.0 - 1.0 - 304.0)


def test_goodput_two_supervisor_sessions_gap_is_idle_not_restart():
    """dlsupervise run today, again tomorrow: the overnight gap between
    sessions (ordinal restarts at 0) is idle, not an 86000s 'restart'."""
    events = [
        _ev(0.0, "attempt", edge="begin", ordinal=0, process="supervisor"),
        _ev(1.0, "phase", name="run", edge="begin"),
        _ev(100.0, "phase", name="run", edge="end"),
        _ev(101.0, "attempt", edge="end", ordinal=0, process="supervisor"),
        _ev(86400.0, "attempt", edge="begin", ordinal=0, process="supervisor"),
        _ev(86401.0, "phase", name="run", edge="begin"),
        _ev(86500.0, "phase", name="run", edge="end"),
        _ev(86501.0, "attempt", edge="end", ordinal=0, process="supervisor"),
    ]
    g = telemetry.goodput(events)
    assert g["restart_overhead_s"] == 0.0
    assert g["idle_s"] == pytest.approx(86401.0 - 100.0)
    assert g["goodput_frac"] < 0.01


def test_goodput_multi_process_starvation_is_max_not_sum():
    """Lockstep SPMD: the slowest host's input wait gates the gang, so
    gang-level starvation is the max across processes — summing 4 hosts'
    waits would over-count 4x and could exceed wall-clock."""
    events = [_ev(0.0, "heartbeat")]
    for proc, wait in (("p0", 30.0), ("p1", 28.0), ("p2", 31.0),
                       ("p3", 29.0)):
        events.append(_ev(50.0, "step_metrics", step=10, steps=10,
                          lap_s=50.0, input_wait_s=wait, process=proc))
    events.append(_ev(100.0, "heartbeat"))
    g = telemetry.goodput(events)
    assert g["input_starved_s"] == 31.0
    assert g["productive_s"] == 69.0


def test_goodput_empty_and_single_event():
    assert telemetry.goodput([])["goodput_frac"] == 0.0
    g = telemetry.goodput([_ev(5.0, "heartbeat")])
    assert g["wall_s"] == 0.0 and g["goodput_frac"] == 0.0


# -- starvation probe --------------------------------------------------------


def test_probe_timed_counts_waits_with_fake_clock():
    clock = FakeClock()
    probe = StarvationProbe(clock=clock)

    def slow_source():
        for i in range(4):
            clock.tick(0.5 if i % 2 else 2.0)  # alternating slow/fast
            yield {"x": i}

    out = list(probe.timed(slow_source()))
    assert [b["x"] for b in out] == [0, 1, 2, 3]
    snap = probe.snapshot()
    assert snap["input_waits"] == 4
    assert snap["input_wait_s"] == pytest.approx(5.0)
    assert snap["input_wait_max_s"] == pytest.approx(2.0)
    # snapshot(reset=True) cleared the counters
    assert probe.snapshot()["input_waits"] == 0


def test_probe_timed_accepts_plain_iterables():
    probe = StarvationProbe()
    assert [b for b in probe.timed([{"x": 1}, {"x": 2}])] == \
        [{"x": 1}, {"x": 2}]
    assert probe.snapshot()["input_waits"] == 2


def test_probe_through_prefetch_no_background():
    """prefetch_to_device(probe=...) attributes the synchronous host-side
    assembly to consumer wait — the no-thread path every test can rely on
    deterministically."""
    clock = FakeClock()
    probe = StarvationProbe(clock=clock)

    def source():
        for i in range(3):
            clock.tick(1.0)
            yield {"x": np.full((2,), i)}

    batches = list(prefetch_to_device(
        source(), mesh=None, put=lambda b, m: b, background=False,
        probe=probe, buffer_size=2))
    assert len(batches) == 3
    snap = probe.snapshot()
    assert snap["input_waits"] == 3
    assert snap["input_wait_s"] == pytest.approx(3.0)


def test_probe_through_device_batches():
    """The unbuffered feed path (device_batches) times every host-batch
    assembly as consumer wait — same probe, no prefetch ring."""
    from distributeddeeplearningspark_tpu import PartitionedDataset, Session
    from distributeddeeplearningspark_tpu.data.feed import device_batches

    sess = Session.builder.master("local[1]").getOrCreate()
    examples = [{"x": np.float32(i)} for i in range(8)]
    ds = PartitionedDataset.parallelize(examples, 2)
    probe = StarvationProbe()
    batches = list(device_batches(ds, sess.mesh, 4, probe=probe))
    assert len(batches) == 2
    snap = probe.snapshot()
    assert snap["input_waits"] == 2  # one per yielded batch
    assert snap["input_wait_s"] >= 0.0


def test_probe_background_records_depth_and_assembly():
    """The background path samples queue depth per consumer get and times
    producer-side assembly separately from consumer-side waits."""
    probe = StarvationProbe()
    src = ({"x": i} for i in range(5))
    batches = list(prefetch_to_device(
        src, mesh=None, put=lambda b, m: b, background=True, probe=probe))
    assert len(batches) == 5
    snap = probe.snapshot()
    assert snap["input_waits"] == 5
    assert snap["input_assembly_s"] >= 0.0
    assert "prefetch_depth_mean" in snap and "prefetch_depth_min" in snap


# -- Meter / MetricLogger satellites ----------------------------------------


def test_meter_lap_coerces_and_quarantines_nonfinite():
    m = Meter(examples_per_step=8, warmup_laps=0)
    m.start()
    rec1 = m.lap(2, {"loss": np.float32(1.5), "acc": np.array(0.5)})
    assert rec1 == {"loss": 1.5, "acc": 0.5}
    # a NaN lap: returned record keeps the NaN (divergence detection needs
    # it) but the history feeding summary() takes only the finite subset
    rec2 = m.lap(2, {"loss": float("nan"), "acc": 0.75,
                     "junk": "not-a-number",
                     "per_class": np.array([1.0, float("nan")]),
                     "finite_vec": np.array([1.0, 2.0])})
    assert np.isnan(rec2["loss"]) and rec2["acc"] == 0.75
    assert "junk" not in rec2
    # a NaN hiding in a non-scalar metric must stay LOUD in the returned
    # record (divergence detection reads it); an all-finite vector just
    # stays out of the scalar stream
    assert np.isnan(rec2["per_class"])
    assert "finite_vec" not in rec2
    s = m.summary()
    assert s["acc"] == 0.75       # last finite value won
    assert "loss" not in s or np.isfinite(s["loss"])
    assert np.isfinite(s["step_time_ms"])
    assert m.last_lap is not None and m.last_lap[1] == 2


def test_meter_all_nan_lap_keeps_last_finite_summary():
    m = Meter(warmup_laps=0)
    m.start()
    m.lap(1, {"loss": 2.0})
    m.lap(1, {"loss": float("inf")})
    assert m.summary()["loss"] == 2.0


def test_log_value_counters_not_mangled():
    # large counters arrive as floats; round(v, 6) keeps them floats and
    # json renders 1e+16-style — ints must print exactly
    assert _log_value(1.2e16) == 12000000000000000
    assert json.dumps(_log_value(float(10**15 + 1))) == str(10**15 + 1)
    assert _log_value(0.1234567891) == 0.123457
    assert _log_value(float("nan")) != _log_value(1.0)  # NaN passes through
    assert _log_value("label") == "label"


def test_metric_logger_log_formats_counters(caplog):
    mlog = MetricLogger()
    with caplog.at_level(logging.INFO,
                         logger="distributeddeeplearningspark_tpu.metrics"):
        mlog.log(3, {"step": 3.0, "tokens": 1.2e16, "loss": 0.5})
    line = caplog.records[-1].getMessage()
    assert "12000000000000000" in line and "e+16" not in line


def test_metric_logger_event_mirrors_to_telemetry(tmp_path):
    w, _ = _writer(tmp_path)
    mlog = MetricLogger(telemetry=w)
    mlog.event(42, "rollback", to_step=40, window=2)
    w.close()
    events = telemetry.read_events(tmp_path)
    assert len(events) == 1
    e = events[0]
    assert (e["kind"], e["event"], e["step"]) == ("recovery", "rollback", 42)
    assert e["to_step"] == 40 and e["window"] == 2


# -- dlstatus ----------------------------------------------------------------


def _synth_run(tmp_path):
    w, clock = _writer(tmp_path, "p0")
    sup, sclock = _writer(tmp_path, "supervisor")
    sup.attempt("begin", 0)
    clock.t = 1.0
    w.emit("phase", name="run", edge="begin", step=0)
    with w.phase("compile"):
        clock.t = 9.0
    clock.t = 20.0
    w.step_metrics(10, steps=10, lap_s=11.0, metrics={"loss": 0.9},
                   input_wait_s=1.5)
    w.heartbeat(step=10)
    w.recovery(10, "skip", skipped_steps=1)
    sclock.t = 30.0
    sup.attempt("end", 0, returncodes=[-9], duration_s=30.0,
                classification="training-crash", made_progress=True)
    sup.recovery(None, "restart", ordinal=0, classification="training-crash")
    sclock.t = 35.0
    sup.attempt("begin", 1)
    sclock.t = 60.0
    sup.attempt("end", 1, returncodes=[0], duration_s=25.0,
                classification="clean", made_progress=True)
    w.close(), sup.close()


def test_status_report_fields(tmp_path):
    _synth_run(tmp_path)
    rep = status.report(str(tmp_path), now=70.0)
    assert rep["num_events"] == 11
    assert rep["last_step"] == 10
    assert rep["last_heartbeat_age_s"] == pytest.approx(50.0)
    assert [a["ordinal"] for a in rep["attempts"]] == [0, 1]
    assert rep["attempts"][0]["classification"] == "training-crash"
    assert rep["attempts"][1]["classification"] == "clean"
    assert {e["event"] for e in rep["recovery_events"]} == {"skip", "restart"}
    g = rep["goodput"]
    assert g["wall_s"] == 60.0
    assert g["compile_s"] == 8.0
    assert g["restart_overhead_s"] == 5.0
    total = sum(g[k] for k in telemetry.GOODPUT_COMPONENTS)
    assert total == pytest.approx(g["wall_s"], rel=0.05)


def test_status_cli_renders_and_exits_zero(tmp_path, capsys):
    _synth_run(tmp_path)
    assert status.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    for needle in ("goodput breakdown", "attempts", "training-crash",
                   "recovery events", "restart", "last heartbeat"):
        assert needle in out, out
    assert status.main([str(tmp_path), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["goodput"]["goodput_frac"] > 0


def test_status_json_is_strict_json_with_nan_incidents(tmp_path, capsys):
    """Divergence incidents put real NaNs in the stream; --json must still
    emit STRICT JSON (no bare NaN literals jq would choke on)."""
    w, clock = _writer(tmp_path)
    w.step_metrics(4, steps=2, lap_s=1.0, metrics={"loss": float("nan")})
    clock.tick(1.0)
    w.recovery(4, "skip", skipped_steps=1,
               nonfinite={"loss": float("nan"), "grad_norm": float("inf")})
    w.close()
    assert status.main([str(tmp_path), "--json"]) == 0
    out = capsys.readouterr().out
    assert "NaN" not in out and "Infinity" not in out
    rep = json.loads(out)
    assert rep["recovery_events"][0]["nonfinite"]["loss"] is None


def test_status_last_step_is_most_recent_not_max(tmp_path):
    """After a rollback the step counter legitimately rewinds; 'last step'
    must be the most recent position, not the pre-rollback max."""
    w, clock = _writer(tmp_path)
    w.heartbeat(step=20)
    clock.tick(5.0)
    w.step_metrics(12, steps=2, lap_s=1.0, metrics={})  # post-rollback lap
    w.close()
    assert status.report(str(tmp_path))["last_step"] == 12


def test_status_attempts_from_two_supervisor_sessions(tmp_path):
    """A second dlsupervise invocation on the same workdir restarts
    ordinals at 0; the first session's rows must survive in the timeline,
    not be overwritten."""
    sup, clock = _writer(tmp_path, "supervisor")
    for session_cls in ("restore-failure", "clean"):
        sup.attempt("begin", 0)
        clock.tick(10.0)
        sup.attempt("end", 0, returncodes=[1 if session_cls != "clean" else 0],
                    duration_s=10.0, classification=session_cls)
        clock.tick(100.0)
    sup.close()
    rows = status.attempts_from(telemetry.read_events(tmp_path))
    assert [(r["session"], r["ordinal"], r["classification"])
            for r in rows] == [(0, 0, "restore-failure"), (1, 0, "clean")]


def test_status_backoff_only_attempt_says_never_launched(tmp_path, capsys):
    """Supervisor killed during the backoff sleep: the next attempt has a
    backoff record but never began — the report must say so instead of the
    'in-flight' label that sends operators hunting a nonexistent gang."""
    sup, clock = _writer(tmp_path, "supervisor")
    sup.attempt("begin", 0)
    clock.tick(10.0)
    sup.attempt("end", 0, returncodes=[1], duration_s=10.0,
                classification="training-crash")
    sup.attempt("backoff", 1, delay_s=30.0)
    sup.close()  # SIGTERM'd during the sleep; attempt 1 never launched
    assert status.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "never launched" in out
    assert "in-flight" not in out


def test_status_cli_no_telemetry_exits_nonzero(tmp_path, capsys):
    assert status.main([str(tmp_path)]) == 1
    assert "no telemetry" in capsys.readouterr().err


# -- end-to-end: Trainer.fit emits a readable run ---------------------------


def test_fit_emits_telemetry_and_dlstatus_reads_it(tmp_path, monkeypatch):
    """The integration contract: a plain fit() with DLS_TELEMETRY_DIR set
    leaves a stream from which dlstatus reports compile/productive time,
    step metrics, heartbeats, and a goodput_frac > 0."""
    import optax

    from distributeddeeplearningspark_tpu import (
        PartitionedDataset,
        Session,
        Trainer,
    )
    from distributeddeeplearningspark_tpu.models import LeNet5
    from distributeddeeplearningspark_tpu.train import losses

    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path))
    monkeypatch.delenv("DLS_FAULT", raising=False)
    rng = np.random.default_rng(0)
    examples = [
        {"image": rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
         "label": np.int32(i % 10)}
        for i in range(64)
    ]
    sess = Session.builder.master("local[2]").getOrCreate()
    ds = PartitionedDataset.parallelize(examples, 2).repeat()
    t = Trainer(sess, LeNet5(), losses.softmax_xent, optax.sgd(0.05), seed=0)
    t.fit(ds, batch_size=16, steps=6, log_every=2)

    events = telemetry.read_events(str(tmp_path))
    kinds = {e["kind"] for e in events}
    assert {"phase", "step_metrics", "heartbeat"} <= kinds
    names = {e.get("name") for e in events if e["kind"] == "phase"}
    assert {"run", "compile"} <= names
    laps = [e for e in events if e["kind"] == "step_metrics"]
    assert [e["step"] for e in laps] == [2, 4, 6]
    assert all("input_wait_s" in e for e in laps)
    rep = status.report(str(tmp_path))
    assert rep["goodput"]["goodput_frac"] > 0
    assert rep["goodput"]["compile_s"] > 0
    assert status.main([str(tmp_path)]) == 0
