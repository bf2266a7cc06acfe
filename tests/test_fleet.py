"""Pod-level observability: per-host tagging, fleet aggregation, skew,
straggler/hang verdicts, dlstatus --hosts, and the supervisor's culprit
naming (ISSUE 3).

All synthetic streams run on fake clocks (the fleet fold is a pure function
of event dicts); the one real-process test is the supervisor hang drill,
whose worker is plain python (no jax) so it stays in the fast tier.
"""

import json
import os
import sys

import pytest

from distributeddeeplearningspark_tpu import status, telemetry
from distributeddeeplearningspark_tpu.telemetry import fleet

FIXTURE_3HOST = os.path.join(os.path.dirname(__file__), "fixtures",
                             "fleet_3host")


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _writer(tmp_path, host, *, hosts=3, t0=0.0):
    clock = FakeClock(t0)
    w = telemetry.EventWriter(tmp_path, process=f"p{host}", clock=clock,
                              host=host, hosts=hosts)
    return w, clock


def _ev(ts, kind, host, **f):
    return {"ts": ts, "kind": kind, "process": f"p{host}", "host": host, **f}


# -- writer-side host tagging & heartbeat enrichment -------------------------


def test_writer_tags_events_with_host_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("DLS_PROCESS_ID", "2")
    monkeypatch.setenv("DLS_NUM_PROCESSES", "4")
    w = telemetry.EventWriter(tmp_path, clock=FakeClock())
    w.heartbeat(step=5)
    w.close()
    (e,) = telemetry.read_events(tmp_path)
    assert e["host"] == 2 and e["hosts"] == 4
    assert e["process"] == "p2"


def test_writer_host_none_opts_out(tmp_path):
    """Non-host processes (the supervisor) carry no host field and
    stay out of the fleet table."""
    w = telemetry.EventWriter(tmp_path, process="supervisor",
                              clock=FakeClock(), host=None)
    w.attempt("begin", 0)
    w.close()
    (e,) = telemetry.read_events(tmp_path)
    assert "host" not in e
    assert fleet.split_hosts([e]) == {}


def test_heartbeat_enriched_with_innermost_open_phase(tmp_path):
    w, clock = _writer(tmp_path, 0)
    w.emit("phase", name="run", edge="begin")
    w.heartbeat(step=1)
    with w.phase("restore"):
        clock.t = 5.0
        w.heartbeat(step=1)
    clock.t = 9.0
    w.heartbeat(step=2)
    w.close()
    hbs = [e for e in telemetry.read_events(tmp_path)
           if e["kind"] == "heartbeat"]
    assert [h["phase"] for h in hbs] == ["run", "restore", "run"]


def test_legacy_streams_fall_back_to_process_name(tmp_path):
    """Streams written before the host field exist still aggregate via the
    p<k> process-name convention."""
    events = [{"ts": 1.0, "kind": "heartbeat", "process": "p3", "step": 7}]
    assert list(fleet.split_hosts(events)) == [3]


# -- host table ---------------------------------------------------------------


def _three_host_stream(*, stall_host=None, crash_host=None, jitter=0.0):
    """Synthetic gang: steps 10..40 at ~1s/step per lap boundary, per-host
    clock offset ``jitter * host``. ``stall_host`` enters restore after
    step 20 and goes silent; ``crash_host`` dies right after step 10
    (stream just ends). Hosts keep heartbeating until t=50."""
    events = []
    for h in range(3):
        off = jitter * h
        events.append(_ev(0.0 + off, "phase", h, name="run", edge="begin"))
        events.append(_ev(0.1 + off, "heartbeat", h, step=0, phase="run"))
        for step in (10, 20, 30, 40):
            t = step + off
            if crash_host == h and step > 10:
                break
            if stall_host == h and step > 20:
                break
            events.append(_ev(t, "step_metrics", h, step=step, steps=10,
                              lap_s=10.0, metrics={}))
            events.append(_ev(t + 0.01, "heartbeat", h, step=step,
                              phase="run"))
        if stall_host == h:
            events.append(_ev(21.0 + off, "phase", h, name="restore",
                              edge="begin"))
        elif crash_host != h:
            events.append(_ev(50.0 + off, "heartbeat", h, step=40,
                              phase="run"))
    return sorted(events, key=lambda e: e["ts"])


def test_host_table_uneven_lengths_and_ages():
    events = _three_host_stream(stall_host=2, jitter=0.05)
    rows = fleet.host_table(events)
    assert [r["host"] for r in rows] == [0, 1, 2]
    assert [r["last_step"] for r in rows] == [40, 40, 20]
    # ages anchor on the merged stream's end by default
    assert rows[0]["heartbeat_age_s"] == pytest.approx(0.05, abs=0.02)
    assert rows[2]["heartbeat_age_s"] == pytest.approx(30.0, abs=0.5)
    assert rows[2]["phase"] == "restore"
    assert rows[2]["silence_s"] > 25.0
    # healthy hosts report the outer run phase, not the stalled one's
    assert rows[0]["phase"] == "run"


def test_host_table_comms_wait_column():
    events = [
        _ev(0.0, "heartbeat", 0, step=0),
        _ev(1.0, "collective", 0, op="barrier", axis="data", wait_s=0.5),
        _ev(2.0, "collective", 0, op="all_gather", axis="data", wait_s=0.25),
        _ev(2.0, "heartbeat", 1, step=0),
    ]
    rows = fleet.host_table(events)
    assert rows[0]["comms_wait_s"] == pytest.approx(0.75)
    assert rows[0]["collectives"] == 2
    assert rows[1]["comms_wait_s"] == 0.0


def test_host_table_per_host_goodput():
    events = [
        _ev(0.0, "heartbeat", 0),
        _ev(0.0, "phase", 0, name="compile", edge="begin"),
        _ev(4.0, "phase", 0, name="compile", edge="end", dur_s=4.0),
        _ev(10.0, "heartbeat", 0),
        _ev(0.0, "heartbeat", 1),
        _ev(10.0, "heartbeat", 1),
    ]
    rows = fleet.host_table(events)
    assert rows[0]["goodput"]["compile_s"] == 4.0
    assert rows[0]["goodput"]["goodput_frac"] == pytest.approx(0.6)
    assert rows[1]["goodput"]["goodput_frac"] == pytest.approx(1.0)


def test_stale_phase_from_crashed_attempt_does_not_leak():
    """A worker killed mid-restore never writes the restore end; its
    relaunch appends a fresh run begin to the SAME file. The stale open
    restore must not be reported as the new attempt's current phase."""
    events = [
        _ev(0.0, "phase", 0, name="run", edge="begin"),
        _ev(5.0, "phase", 0, name="restore", edge="begin"),
        # SIGKILL; relaunch appends:
        _ev(20.0, "phase", 0, name="run", edge="begin"),
        _ev(21.0, "heartbeat", 0, step=10, phase="run"),
    ]
    (row,) = fleet.host_table(events)
    assert row["phase"] == "run"
    assert row["phase_since_ts"] is None  # run umbrella is not a dwell


def test_hb_phase_fallback_cleared_when_phase_ends():
    """A heartbeat's self-reported phase must stop being 'current' once
    that phase's end edge arrives — a cleanly finished run is not 'in
    restore' just because its last heartbeat happened during one."""
    events = [
        _ev(0.0, "phase", 0, name="run", edge="begin"),
        _ev(1.0, "phase", 0, name="restore", edge="begin"),
        _ev(2.0, "heartbeat", 0, step=5, phase="restore"),
        _ev(3.0, "phase", 0, name="restore", edge="end", dur_s=2.0),
        _ev(4.0, "phase", 0, name="run", edge="end"),
    ]
    (row,) = fleet.host_table(events)
    assert row["phase"] is None  # everything closed: no current phase


def test_supervisor_writer_stays_out_of_fleet_table(tmp_path):
    """The supervisor's own events (reap-time attempt ends, restarts) must
    not refresh host 0's liveness — it describes the gang, it isn't in it."""
    from distributeddeeplearningspark_tpu.supervisor import Supervisor

    sup = Supervisor(["true"], telemetry_dir=str(tmp_path))
    sup._telemetry().attempt("begin", 0)
    sup._tele.close()
    (e,) = telemetry.read_events(str(tmp_path))
    assert e["process"] == "supervisor" and "host" not in e
    assert fleet.host_table([e]) == []


# -- step skew & straggler ----------------------------------------------------


def test_step_skew_numbers_with_clock_jitter():
    events = _three_host_stream(jitter=0.2)
    sk = fleet.step_skew(events)
    assert sk["num_hosts"] == 3
    steps = [w["step"] for w in sk["per_step"]]
    assert steps == [0, 10, 20, 30, 40]
    # constant 0.2s/host offset → 0.4s spread, host 2 always "slowest"
    assert sk["max_skew_s"] == pytest.approx(0.4, abs=0.01)
    assert sk["median_skew_s"] == pytest.approx(0.4, abs=0.01)
    assert sk["last_common_step"] == 40
    assert sk["step_lag"] == 0


def test_step_skew_step_lag_when_one_host_stops():
    sk = fleet.step_skew(_three_host_stream(stall_host=1))
    assert sk["last_common_step"] == 20
    assert sk["step_lag"] == 20  # host 1 stopped at 20, others reached 40


def test_straggler_verdict_persistent_slow_host():
    events = []
    for h in range(3):
        for step in (10, 20, 30, 40):
            lag = 2.5 if h == 1 else 0.05 * h
            events.append(_ev(step + lag, "step_metrics", h, step=step,
                              steps=10, lap_s=10.0, metrics={}))
    sk = fleet.step_skew(events)
    verdict = fleet.straggler_verdict(sk)
    assert verdict is not None
    assert verdict["host"] == 1
    assert verdict["slow_windows"] == 4 and verdict["windows"] == 4
    assert verdict["median_skew_s"] == pytest.approx(2.5, abs=0.01)
    assert "host 1 slowest in 4/4" in verdict["verdict"]


def test_straggler_none_on_rotating_or_small_skew():
    # skew below min_skew_s: clock jitter, not a sick machine
    sk = fleet.step_skew(_three_host_stream(jitter=0.1))
    assert fleet.straggler_verdict(sk) is None
    # rotating slowest host: no single culprit
    events = []
    for i, step in enumerate((10, 20, 30, 40)):
        for h in range(3):
            lag = 3.0 if h == i % 3 else 0.0
            events.append(_ev(step + lag, "step_metrics", h, step=step,
                              steps=10, lap_s=10.0, metrics={}))
    assert fleet.straggler_verdict(fleet.step_skew(events)) is None


# -- hang localization --------------------------------------------------------


def test_localize_hang_names_stalled_host_and_phase():
    events = _three_host_stream(stall_host=2, jitter=0.05)
    loc = fleet.localize_hang(events)
    assert loc["host"] == 2
    assert loc["phase"] == "restore"
    assert loc["others_at_step"] == 40
    # stalled-for measures from the open phase begin to the stream end
    assert loc["stalled_for_s"] == pytest.approx(50.05 - 21.1, abs=0.2)
    assert "host 2 stuck in phase=restore" in loc["verdict"]
    assert "waiting at step 40" in loc["verdict"]


def test_localize_hang_crashed_host_attributed():
    """A host whose stream just ends (crash, no phase open) is still the
    culprit — silence attribution doesn't need a phase record."""
    loc = fleet.localize_hang(_three_host_stream(crash_host=1))
    assert loc["host"] == 1
    assert loc["others_at_step"] == 40


def test_localize_hang_simultaneous_silence_is_unattributed():
    """The whole gang dying within the jitter margin (network partition)
    must NOT name an arbitrary host."""
    events = _three_host_stream(jitter=0.1)  # all end ~50.0..50.2
    assert fleet.localize_hang(events) is None


def test_localize_hang_single_host_gang():
    events = [
        _ev(0.0, "phase", 0, name="run", edge="begin"),
        _ev(5.0, "phase", 0, name="checkpoint", edge="begin"),
    ]
    loc = fleet.localize_hang(events, now=60.0)
    assert loc["host"] == 0 and loc["phase"] == "checkpoint"
    assert loc["stalled_for_s"] == pytest.approx(55.0)
    # the same stream inspected stream-anchored (silence 0 — a live or
    # finished run) must NOT be flagged: one host has no one to lag behind
    assert fleet.localize_hang(events) is None


def test_finished_run_with_trailing_supervisor_events_not_flagged():
    """The supervisor's reap records land seconds after the worker's last
    event on every CLEAN run; that lag is teardown, not silence — the
    stream-anchored hang gate must ignore non-host events."""
    events = [
        _ev(0.0, "phase", 0, name="run", edge="begin"),
        _ev(10.0, "heartbeat", 0, step=12),
        _ev(10.1, "phase", 0, name="run", edge="end", step=12),
        {"ts": 12.5, "kind": "attempt", "process": "supervisor",
         "edge": "end", "ordinal": 0, "returncodes": [0]},
    ]
    assert fleet.localize_hang(events) is None
    (row,) = fleet.host_table(events)
    assert row["silence_s"] == pytest.approx(0.0)  # host-stream anchored


def test_localize_hang_margin_scales_with_observed_skew():
    """A gang whose normal per-step skew is large must not have its
    slowest-but-healthy host named on a gap the skew baseline explains."""
    events = _three_host_stream(jitter=2.0)  # median step skew = 4s
    # hosts end at 50, 52, 54 — 2s lead < 3×4s margin → no culprit
    assert fleet.localize_hang(events) is None
    # but an explicit margin below the lead names the earliest-silent host
    assert fleet.localize_hang(events, margin_s=1.0)["host"] == 0


# -- fleet report & dlstatus --hosts -----------------------------------------


def test_fleet_report_missing_hosts_from_writer_stamp():
    """A host that never wrote an event still shows as missing: the other
    writers' own `hosts` stamp says how many there should be."""
    events = [
        {"ts": 1.0, "kind": "heartbeat", "process": "p0", "host": 0,
         "hosts": 3, "step": 4},
        {"ts": 1.1, "kind": "heartbeat", "process": "p1", "host": 1,
         "hosts": 3, "step": 4},
    ]
    rep = fleet.fleet_report(events)
    assert rep["num_hosts"] == 2
    assert rep["expected_hosts"] == 3
    assert rep["missing_hosts"] == [2]


def test_dlstatus_hosts_json_schema(tmp_path, capsys):
    """The acceptance shape: on a 3-host fixture with one host stalled
    mid-phase, --hosts --json reports per-host last-step/heartbeat-age/
    phase, a step-skew figure, and names the stalled host + phase."""
    assert status.main([FIXTURE_3HOST, "--hosts", "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    fl = rep["fleet"]
    assert fl["num_hosts"] == 3 and fl["expected_hosts"] == 3
    by_host = {r["host"]: r for r in fl["hosts"]}
    assert set(by_host) == {0, 1, 2}
    for r in fl["hosts"]:
        assert {"last_step", "heartbeat_age_s", "phase", "comms_wait_s",
                "silence_s", "goodput"} <= set(r)
    assert by_host[2]["phase"] == "restore"
    assert by_host[2]["heartbeat_age_s"] > 0
    assert by_host[0]["last_step"] == 40
    assert fl["skew"]["max_skew_s"] > 0
    assert fl["skew"]["per_step"]
    hang = fl["hang"]
    assert hang["host"] == 2 and hang["phase"] == "restore"
    assert hang["others_at_step"] == 40


def test_dlstatus_hosts_renders_table_and_verdict(capsys):
    assert status.main([FIXTURE_3HOST, "--hosts"]) == 0
    out = capsys.readouterr().out
    assert "fleet: 3/3 host(s) reporting" in out
    assert "step skew" in out
    assert "host 2 stuck in phase=restore" in out


def test_dlstatus_without_hosts_flag_has_no_fleet(tmp_path, capsys):
    w, _ = _writer(tmp_path, 0)
    w.heartbeat(step=1)
    w.close()
    assert status.main([str(tmp_path), "--json"]) == 0
    assert "fleet" not in json.loads(capsys.readouterr().out)


# -- supervisor hang path names the culprit ----------------------------------


_STALL_WORKER = """\
import os, time
from distributeddeeplearningspark_tpu import telemetry
if os.environ.get("DLS_RESTART", "0") != "0":
    raise SystemExit(0)  # the relaunch after the hang succeeds
w = telemetry.EventWriter(os.environ["DLS_TELEMETRY_DIR"])
w.emit("phase", name="run", edge="begin", step=0)
w.heartbeat(step=3)
w.emit("phase", name="restore", edge="begin")
open(os.environ["DLS_HEARTBEAT_FILE"], "w").write("x")  # progress, then stall
time.sleep(120)
"""


def test_supervisor_hang_recovery_names_culprit(tmp_path):
    """The acceptance contract's supervisor half: a hang's recovery event
    carries the fleet-localized culprit host + phase, not a bare 'hang'."""
    from distributeddeeplearningspark_tpu.supervisor import Supervisor

    script = tmp_path / "stall_worker.py"
    script.write_text(_STALL_WORKER)
    wd = tmp_path / "run"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sup = Supervisor(
        [sys.executable, str(script)],
        num_processes=1, max_restarts=1, poll_interval=0.05,
        restart_backoff_s=0.01, backoff_jitter=0.0,
        # dwell must clear fleet.MIN_STALL_MARGIN_S (1s) so the single-host
        # localization has real silence evidence at reap time
        hang_timeout_s=1.5, startup_grace_s=30.0,
        progress_path=str(wd), telemetry_dir=str(wd),
        env={"PYTHONPATH": repo_root + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
    )
    result = sup.run()
    assert result.ok, [(a.returncodes, a.classification)
                       for a in result.attempts]
    hung = result.attempts[0]
    assert hung.classification == "hang"
    assert hung.culprit is not None
    assert hung.culprit["host"] == 0
    assert hung.culprit["phase"] == "restore"

    events = telemetry.read_events(str(wd))
    restarts = [e for e in events if e.get("kind") == "recovery"
                and e.get("event") == "restart"]
    assert len(restarts) == 1
    assert restarts[0]["classification"] == "hang"
    assert restarts[0]["culprit_host"] == 0
    assert restarts[0]["culprit_phase"] == "restore"
    assert restarts[0]["stalled_for_s"] > 0
    ends = [e for e in events if e.get("kind") == "attempt"
            and e.get("edge") == "end" and e.get("ordinal") == 0]
    assert ends[0]["culprit_host"] == 0


def test_supervisor_hang_without_telemetry_stays_bare():
    """No telemetry dir → the hang path degrades to the bare
    classification (no crash, no culprit fields)."""
    from distributeddeeplearningspark_tpu.supervisor import Supervisor

    sup = Supervisor(["true"], num_processes=1)
    assert sup._localize_hang() is None


# -- satellite: collective probes --------------------------------------------


def test_barrier_probe_emits_collective_event(tmp_path):
    from distributeddeeplearningspark_tpu.parallel import collectives
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec().build()
    telemetry.configure(tmp_path)
    wait = collectives.barrier_probe(mesh)
    assert wait >= 0.0
    collectives.barrier_probe(mesh)
    events = [e for e in telemetry.read_events(tmp_path)
              if e["kind"] == "collective"]
    assert len(events) == 2
    assert events[0]["op"] == "barrier" and events[0]["wait_s"] >= 0.0
    # the fleet table folds them into the comms-wait column
    rows = fleet.host_table(telemetry.read_events(tmp_path))
    assert rows[0]["collectives"] == 2


def test_is_tracing_sees_every_kind_of_trace():
    """True under jit, grad and shard_map, false eagerly — asked of jax's
    trace state, not guessed from the operands."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributeddeeplearningspark_tpu.parallel import collectives
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    seen = {}

    def probe(tag):
        def f(x):
            seen[tag] = collectives._is_tracing()
            return x * 2.0
        return f

    x = jnp.ones((8,), jnp.float32)
    assert collectives._is_tracing() is False
    probe("eager")(x)
    jax.jit(probe("jit"))(x)
    jax.grad(lambda v: probe("grad")(v).sum())(x)
    jax.shard_map(probe("shard_map"), mesh=MeshSpec().build(),
                  in_specs=P("data"), out_specs=P("data"))(x)
    assert seen == {"eager": False, "jit": True, "grad": True,
                    "shard_map": True}
    assert collectives._is_tracing() is False


def test_probed_collectives_transparent_under_tracing(tmp_path):
    """The opt-in wrappers must not change traced semantics or emit from
    inside a trace — XLA owns scheduling there."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributeddeeplearningspark_tpu.parallel import collectives
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec().build()
    telemetry.configure(tmp_path)
    collectives.enable_collective_probes(True)
    try:
        f = jax.jit(jax.shard_map(
            lambda x: collectives.all_reduce_sum(x, ("data",)),
            mesh=mesh, in_specs=P("data"), out_specs=P()))
        out = f(jnp.ones((8,), jnp.float32))
        assert float(out[0]) == 8.0
        assert [e for e in telemetry.read_events(tmp_path)
                if e["kind"] == "collective"] == []
    finally:
        collectives.enable_collective_probes(False)
