"""Chaos drills — deterministic fault injection through the full recovery
chain (faults.py → checkpoint manifests → supervisor classification →
trainer divergence policies).

Each drill is the end-to-end shape of one production failure mode:

- **kill-mid-checkpoint-finalize** (``DLS_FAULT=truncate_ckpt@N``): the
  latest step is torn after its manifest committed; the relaunch must walk
  back to the newest *verified* step, quarantine the torn one, and finish.
- **restore-poisoned checkpoint**: a step that verifies byte-for-byte but
  crashes restore (sentinel exit 13); the supervisor must quarantine it and
  fall back instead of burning every restart on it.
- **hang** (``DLS_FAULT=hang@N``): progress stops without an exit; the
  watchdog must kill, classify, and relaunch to completion.
- **NaN spike** (``DLS_FAULT=nan@N``): ``fit(on_nonfinite=...)`` must
  contain the divergence (skip) or rewind past it (rollback).

Run via ``bash tools/ci.sh chaos`` (prints its own row).
"""

import os
import re
import sys

import numpy as np
import pytest

from distributeddeeplearningspark_tpu import faults, status, telemetry
from distributeddeeplearningspark_tpu.supervisor import (
    RESTORE_FAILED_EXIT,
    Supervisor,
)

WORKER = os.path.join(os.path.dirname(__file__), "workers", "worker.py")

# Workers are single-device gang members; they must not inherit the test
# process's 8-fake-device XLA_FLAGS (same contract as test_supervisor.py).
_CLEAN_ENV = {"XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"}


def _corrupt_dirs(path):
    return [d for d in os.listdir(path) if re.match(r"\d+\.corrupt-\d+$", d)]


def _attempt_ends(workdir):
    """{ordinal: classification} from the run's attempt telemetry — every
    drill asserts its fault left the matching audit record behind."""
    return {e["ordinal"]: e["classification"]
            for e in telemetry.read_events(workdir)
            if e["kind"] == "attempt" and e.get("edge") == "end"}


def _recovery_events(workdir):
    return [e for e in telemetry.read_events(workdir)
            if e["kind"] == "recovery"]


# -- fault spec parsing (fast tier: no gangs) --------------------------------


def test_fault_parse():
    f = faults.parse("truncate_ckpt@20")
    assert (f.kind, f.step) == ("truncate_ckpt", 20)
    for bad in ("nan", "nan@", "nan@x", "frobnicate@3", "crash@0"):
        with pytest.raises(ValueError):
            faults.parse(bad)


def test_fault_gating(monkeypatch):
    monkeypatch.setenv("DLS_FAULT", "nan@3")
    monkeypatch.delenv("DLS_RESTART", raising=False)
    assert faults.get() == faults.Fault("nan", 3)
    monkeypatch.setenv("DLS_RESTART", "1")  # relaunch attempts run clean
    assert faults.get() is None
    monkeypatch.setenv("DLS_FAULT_ALL_ATTEMPTS", "1")
    assert faults.get() == faults.Fault("nan", 3)
    monkeypatch.delenv("DLS_FAULT")
    assert faults.get() is None


def test_die_host_fault_gating(monkeypatch):
    """die_host targets by stable host identity, persists across attempts
    by default (a dead machine stays dead), and validates its env knobs
    with the same loud ladder as the spec itself."""
    monkeypatch.setenv("DLS_FAULT", "die_host@7")
    monkeypatch.setenv("DLS_PROCESS_ID", "1")
    monkeypatch.delenv("DLS_RESTART", raising=False)
    monkeypatch.delenv("DLS_HOST_ID", raising=False)
    monkeypatch.delenv("DLS_FAULT_HOST", raising=False)
    assert faults.get() == faults.Fault("die_host", 7)
    # persists across attempts (unlike crash's first-attempt-only rule) …
    monkeypatch.setenv("DLS_RESTART", "2")
    assert faults.get() == faults.Fault("die_host", 7)
    # … unless the drill opts back into one-shot
    monkeypatch.setenv("DLS_FAULT_ONCE", "1")
    assert faults.get() is None
    monkeypatch.delenv("DLS_FAULT_ONCE")
    # DLS_HOST_ID (stable across elastic renumbering) wins over the rank
    monkeypatch.setenv("DLS_PROCESS_ID", "0")
    monkeypatch.setenv("DLS_HOST_ID", "1")
    assert faults.get() == faults.Fault("die_host", 7)
    # surviving hosts run clean
    monkeypatch.setenv("DLS_HOST_ID", "0")
    assert faults.get() is None
    # validation ladder: bad host env and 0-step specs fail loudly
    monkeypatch.setenv("DLS_HOST_ID", "1")
    monkeypatch.setenv("DLS_FAULT_HOST", "frobnicate")
    with pytest.raises(ValueError, match="DLS_FAULT_HOST"):
        faults.get()
    monkeypatch.setenv("DLS_FAULT_HOST", "-1")
    with pytest.raises(ValueError, match=">= 0"):
        faults.get()
    for bad in ("die_host@0", "die_host@", "die_host@x"):
        with pytest.raises(ValueError):
            faults.parse(bad)


def test_sigterm_fault_scoping(monkeypatch):
    """sigterm is a preemption NOTICE, not a crash: faults.get() never
    returns it (non-trainer callers must not mistake it for a kill), the
    trainer's scoped accessor does — on attempt 0 only, with the doomed
    host's env knob validated eagerly."""
    monkeypatch.setenv("DLS_FAULT", "sigterm@9")
    monkeypatch.delenv("DLS_RESTART", raising=False)
    monkeypatch.delenv("DLS_FAULT_HOST", raising=False)
    monkeypatch.delenv("DLS_FAULT_ALL_ATTEMPTS", raising=False)
    assert faults.get() is None
    assert faults.sigterm_fault() == faults.Fault("sigterm", 9)
    # the shrunk relaunch must run clean …
    monkeypatch.setenv("DLS_RESTART", "1")
    assert faults.sigterm_fault() is None
    # … unless the drill opts into give-up testing
    monkeypatch.setenv("DLS_FAULT_ALL_ATTEMPTS", "1")
    assert faults.sigterm_fault() == faults.Fault("sigterm", 9)
    monkeypatch.delenv("DLS_FAULT_ALL_ATTEMPTS")
    monkeypatch.delenv("DLS_RESTART")
    # other kinds don't leak through the scoped accessor
    monkeypatch.setenv("DLS_FAULT", "crash@3")
    assert faults.sigterm_fault() is None
    # a typo'd doomed-host knob fails loudly at consult time
    monkeypatch.setenv("DLS_FAULT", "sigterm@9")
    monkeypatch.setenv("DLS_FAULT_HOST", "frobnicate")
    with pytest.raises(ValueError, match="DLS_FAULT_HOST"):
        faults.sigterm_fault()
    for bad in ("sigterm@0", "sigterm@", "sigterm@x"):
        with pytest.raises(ValueError):
            faults.parse(bad)


def test_drain_evidence_roundtrip_and_classification(tmp_path):
    """The DRAIN evidence file protocol: written atomically, read back as
    (host, step), consumed to a forensic rename — and it overrides BOTH the
    all-zero "clean" read and the non-zero "training-crash" read in the
    supervisor's classifier (a drain is a handoff, not a completion)."""
    import sys as _sys

    from distributeddeeplearningspark_tpu import supervisor as sup_lib

    assert sup_lib.read_drain_evidence(tmp_path) is None
    sup_lib.write_drain_evidence(tmp_path, host=1, step=9)
    assert sup_lib.read_drain_evidence(tmp_path) == (1, 9)

    sup = Supervisor([_sys.executable, "-c", "pass"], num_processes=2,
                     ckpt_dir=str(tmp_path))
    # all-zero exits would otherwise read "clean" and END the run
    assert sup._classify([0, 0], ordinal=0, hang=False,
                         made_progress=True) == "graceful-shutdown"
    # a drain raced by the kill path must not burn a backoff slot either
    assert sup._classify([0, -15], ordinal=0, hang=False,
                         made_progress=True) == "graceful-shutdown"
    attempt = sup_lib.Attempt(ordinal=0, returncodes=[0, 0], duration_s=1.0,
                              classification="graceful-shutdown")
    assert not attempt.ok  # a handoff is not a completion

    sup_lib.consume_drain_evidence(tmp_path, ordinal=0)
    assert sup_lib.read_drain_evidence(tmp_path) is None
    assert os.path.exists(tmp_path / "DRAIN.consumed-0")
    assert sup._classify([0, 0], ordinal=0, hang=False,
                         made_progress=True) == "clean"


# -- drill 1: SIGKILL mid-checkpoint-finalize --------------------------------


def test_kill_mid_finalize_recovers_from_verified_step(tmp_path):
    """THE acceptance drill: a worker dies mid-checkpoint-finalize leaving a
    partial latest step (torn bytes, manifest already committed); the
    supervised relaunch restores from the newest VERIFIED earlier step —
    quarantining the torn one — and completes within max_restarts."""
    sup = Supervisor(
        [sys.executable, WORKER, "train", "--ckpt-dir", str(tmp_path),
         "--steps", "30", "--checkpoint-every", "10"],
        num_processes=1, max_restarts=2, restart_backoff_s=0.05,
        env={**_CLEAN_ENV, "DLS_FAULT": "truncate_ckpt@20"},
        progress_path=str(tmp_path),
    )
    result = sup.run()
    assert result.ok, f"attempts: {[(a.ordinal, a.returncodes, a.classification) for a in result.attempts]}"
    assert result.restarts == 1
    # attempt 0 died by SIGKILL right after tearing step 20
    assert -9 in result.attempts[0].returncodes
    step, attempt = open(tmp_path / "DONE").read().split()
    assert int(step) == 30 and int(attempt) == 1
    # the torn step 20 was quarantined, not retried and not GC-counted
    quarantined = _corrupt_dirs(tmp_path)
    assert any(d.startswith("20.corrupt-") for d in quarantined), (
        quarantined, sorted(os.listdir(tmp_path)))
    # training continued past the tear on the relaunch: step 30 committed
    assert os.path.isdir(tmp_path / "30")
    # the audit trail survived the SIGKILL: attempt lifecycle + the restart
    # decision + the relaunch's quarantine of the torn step are all on disk
    ends = _attempt_ends(tmp_path)
    assert ends[0] == "training-crash" and ends[1] == "clean", ends
    recov = _recovery_events(tmp_path)
    assert any(e["event"] == "restart"
               and e["classification"] == "training-crash" for e in recov)
    assert any(e["event"] == "quarantine" and e["step"] == 20
               for e in recov), recov


# -- drill 2: verified-but-poisoned restore → supervisor fallback ------------


def test_restore_failure_falls_back_to_previous_step(tmp_path):
    """A checkpoint whose BYTES verify but whose restore crashes (sentinel
    exit 13) must not burn max_restarts: the supervisor quarantines the
    latest step and the relaunch succeeds on the previous one. Workers are
    plain python (no jax) so this drill stays in the fast tier."""
    (tmp_path / "10").mkdir()
    (tmp_path / "10" / "ok").write_text("good step")
    (tmp_path / "20").mkdir()
    (tmp_path / "20" / "ok").write_text("poisoned step")
    script = (
        "import os, sys\n"
        "root = sys.argv[1]\n"
        "steps = sorted(int(d) for d in os.listdir(root) if d.isdigit())\n"
        f"if steps[-1] == 20: sys.exit({RESTORE_FAILED_EXIT})\n"
        "open(os.path.join(root, 'DONE'), 'w').write(str(steps[-1]))\n"
    )
    sup = Supervisor(
        [sys.executable, "-c", script, str(tmp_path)],
        num_processes=1, max_restarts=2, restart_backoff_s=0.01,
        backoff_jitter=0.0, ckpt_dir=str(tmp_path),
    )
    result = sup.run()
    assert result.ok, [(a.returncodes, a.classification) for a in result.attempts]
    assert result.restarts == 1
    assert result.attempts[0].classification == "restore-failure"
    assert result.attempts[1].classification == "clean"
    assert _corrupt_dirs(tmp_path) == ["20.corrupt-0"]
    assert open(tmp_path / "DONE").read() == "10"
    # supervisor telemetry: the classification and the destructive fallback
    # are auditable from the run dir alone
    ends = _attempt_ends(tmp_path)
    assert ends == {0: "restore-failure", 1: "clean"}, ends
    assert any(e["event"] == "restore-fallback" and e["step"] == 20
               for e in _recovery_events(tmp_path))


def test_restore_failure_without_fallback_burns_restarts(tmp_path):
    """Control for the drill above: fallback disabled → every attempt dies
    on the same poisoned step (the pre-PR behavior the ISSUE describes)."""
    (tmp_path / "20").mkdir()
    script = f"import sys; sys.exit({RESTORE_FAILED_EXIT})\n"
    sup = Supervisor(
        [sys.executable, "-c", script],
        num_processes=1, max_restarts=2, restart_backoff_s=0.01,
        backoff_jitter=0.0, ckpt_dir=str(tmp_path),
        fallback_on_restore_failure=False,
    )
    result = sup.run()
    assert not result.ok
    assert [a.classification for a in result.attempts] == ["restore-failure"] * 3
    assert _corrupt_dirs(tmp_path) == []
    assert _attempt_ends(tmp_path) == {i: "restore-failure" for i in range(3)}


# -- drill 3: hang -----------------------------------------------------------


def test_hang_is_killed_classified_and_relaunched(tmp_path):
    """DLS_FAULT=hang@8: attempt 0 stops progressing mid-run; the watchdog
    kills it, the attempt is classified 'hang', and the relaunch (fault
    disarmed by DLS_RESTART=1) resumes from the step-5 checkpoint."""
    sup = Supervisor(
        [sys.executable, WORKER, "train", "--ckpt-dir", str(tmp_path),
         "--steps", "15", "--checkpoint-every", "5"],
        num_processes=1, max_restarts=2, restart_backoff_s=0.05,
        env={**_CLEAN_ENV, "DLS_FAULT": "hang@8"},
        hang_timeout_s=8.0, startup_grace_s=240.0,
        progress_path=str(tmp_path),
    )
    result = sup.run()
    assert result.ok, f"attempts: {[(a.ordinal, a.returncodes, a.classification) for a in result.attempts]}"
    assert result.restarts == 1
    assert result.attempts[0].classification == "hang"
    step, attempt = open(tmp_path / "DONE").read().split()
    assert int(step) == 15 and int(attempt) == 1
    # the hang classification is in the durable attempt timeline
    ends = _attempt_ends(tmp_path)
    assert ends[0] == "hang" and ends[1] == "clean", ends


# -- drill 3b: crash + dlstatus — the run is explainable from its dir alone --


def test_crash_drill_dlstatus_reports_attempts_and_goodput(tmp_path):
    """ISSUE 2 acceptance: after a supervised DLS_FAULT=crash run,
    ``dlstatus <workdir>`` reports the attempt timeline, the recovery
    event, and a goodput breakdown whose components sum to wall-clock
    within 5% — and exits 0."""
    sup = Supervisor(
        [sys.executable, WORKER, "train", "--ckpt-dir", str(tmp_path),
         "--steps", "20", "--checkpoint-every", "5"],
        num_processes=1, max_restarts=2, restart_backoff_s=0.05,
        env={**_CLEAN_ENV, "DLS_FAULT": "crash@12"},
        progress_path=str(tmp_path),
    )
    result = sup.run()
    assert result.ok, f"attempts: {[(a.ordinal, a.returncodes, a.classification) for a in result.attempts]}"
    assert result.restarts == 1

    rep = status.report(str(tmp_path))
    # attempt timeline: the crash and the clean relaunch, with durations
    assert [a["ordinal"] for a in rep["attempts"]] == [0, 1]
    assert rep["attempts"][0]["classification"] == "training-crash"
    assert -9 in rep["attempts"][0]["returncodes"]
    assert rep["attempts"][1]["classification"] == "clean"
    assert all(a["duration_s"] > 0 for a in rep["attempts"])
    # the recovery event tying the fault to the restart decision
    assert any(e["event"] == "restart"
               and e["classification"] == "training-crash"
               for e in rep["recovery_events"]), rep["recovery_events"]
    # both attempts' trainer streams merged: laps from before AND after
    steps_seen = [e["step"] for e in telemetry.read_events(str(tmp_path))
                  if e["kind"] == "step_metrics"]
    assert any(s <= 10 for s in steps_seen) and 20 in steps_seen, steps_seen
    # goodput breakdown: components sum to wall-clock within 5%
    g = rep["goodput"]
    assert g["wall_s"] > 0 and g["goodput_frac"] > 0
    assert g["compile_s"] > 0          # both attempts jit-compiled
    assert g["restart_overhead_s"] > 0  # the backoff + teardown gap
    total = sum(g[k] for k in telemetry.GOODPUT_COMPONENTS)
    assert total == pytest.approx(g["wall_s"], rel=0.05), (total, g)
    # the CLI renders the same report and exits 0
    assert status.main([str(tmp_path)]) == 0


# -- drill 5: kill-a-host — elastic shrink-to-survive ------------------------


def _geometry_changes(workdir):
    return [e for e in _recovery_events(workdir)
            if e["event"] == "geometry_change"]


def _losses_by_step(workdir, *, after_ts=None):
    out = {}
    for e in telemetry.read_events(workdir):
        if e.get("kind") != "step_metrics":
            continue
        if after_ts is not None and float(e["ts"]) <= after_ts:
            continue
        loss = (e.get("metrics") or {}).get("loss")
        if loss is not None:
            out[int(e["step"])] = float(loss)
    return out


def test_die_host_shrinks_gang_and_training_continues(tmp_path):
    """THE elastic acceptance drill: DLS_FAULT=die_host@12 kills host 1 of a
    2-host gang mid-run and keeps it dead across attempts. After 2
    consecutive failures blaming the same host, the supervisor re-plans the
    gang onto the surviving host (shrink-to-survive), relaunches from the
    last verified checkpoint, and training runs to completion on 1 host —
    with a loss trajectory matching a clean 1-host run restored from the
    same step, and the shrink recorded as a first-class geometry_change
    event that ``dlstatus`` renders.

    (On builds whose CPU backend cannot run cross-process collectives the
    gang uses the worker's ``elastic`` mode — rank 0 trains, rank 1 is a
    stand-in host agent; the supervisor machinery under test is identical.
    The real-gang variant below additionally proves the resharded restore
    when multiprocess collectives exist.)"""
    import shutil

    wd = tmp_path / "run"
    wd.mkdir()
    sup = Supervisor(
        [sys.executable, WORKER, "elastic", "--ckpt-dir", str(wd),
         "--steps", "24", "--checkpoint-every", "6"],
        num_processes=2, max_restarts=4, restart_backoff_s=0.05,
        backoff_jitter=0.0, shrink_after=2,
        env={**_CLEAN_ENV, "DLS_FAULT": "die_host@12"},
        progress_path=str(wd),
    )
    result = sup.run()
    assert result.ok, (
        f"attempts: {[(a.ordinal, a.returncodes, a.classification) for a in result.attempts]}")
    # attempt 0: host 1 died at the step-12 checkpoint; attempt 1: host 1
    # died at startup (a dead host stays dead); attempt 2: 1-host gang
    assert result.restarts == 2
    assert [a.num_processes for a in result.attempts] == [2, 2, 1]
    assert result.attempts[0].dead_host == 1
    assert result.attempts[1].dead_host == 1
    step, attempt, nprocs = open(wd / "DONE").read().split()
    assert (int(step), int(attempt), int(nprocs)) == (24, 2, 1)

    # the shrink is a first-class durable event naming evidence and action
    geo = _geometry_changes(wd)
    assert len(geo) == 1, geo
    assert geo[0]["dead_host"] == 1
    assert geo[0]["from_processes"] == 2 and geo[0]["to_processes"] == 1
    assert geo[0]["hosts"] == [0]
    assert geo[0]["batch_policy"] == "preserve_global"
    assert geo[0]["evidence_attempts"] == 2

    # dlstatus explains the whole incident from the run dir alone
    rep = status.report(str(wd))
    assert any(e["event"] == "geometry_change"
               for e in rep["recovery_events"])
    nps = [a.get("num_processes") for a in rep["attempts"]]
    assert nps == [2, 2, 1], nps
    rendered = status.render(rep)
    assert "geometry" in rendered and "np=1" in rendered, rendered

    # loss trajectory: the post-shrink attempt must match a CLEAN 1-host run
    # restored from the same checkpoint step, batch for batch
    events = telemetry.read_events(wd)
    restores = [e for e in events
                if e.get("kind") == "phase" and e.get("name") == "restore"
                and e.get("edge") == "end"]
    assert restores, "the shrunk relaunch never restored a checkpoint"
    resume_step = int(restores[-1]["step"])
    geo_ts = float(next(e["ts"] for e in events
                        if e.get("kind") == "recovery"
                        and e.get("event") == "geometry_change"))
    drill_losses = _losses_by_step(wd, after_ts=geo_ts)
    assert max(drill_losses) == 24 and min(drill_losses) > resume_step

    clean = tmp_path / "clean"
    clean.mkdir()
    for d in os.listdir(wd):
        if d.isdigit() and int(d) <= resume_step:
            shutil.copytree(wd / d, clean / d)
    sup2 = Supervisor(
        [sys.executable, WORKER, "elastic", "--ckpt-dir", str(clean),
         "--steps", "24", "--checkpoint-every", "6"],
        num_processes=1, max_restarts=0, env=_CLEAN_ENV,
        progress_path=str(clean),
    )
    assert sup2.run().ok
    clean_losses = _losses_by_step(clean)
    common = sorted(set(drill_losses) & set(clean_losses))
    assert common and common[-1] == 24, (drill_losses, clean_losses)
    for s in common:
        assert drill_losses[s] == pytest.approx(clean_losses[s], rel=1e-6), (
            s, drill_losses[s], clean_losses[s])


def test_sigterm_drains_and_continues_from_current_step(tmp_path):
    """THE graceful-preemption drill (ISSUE 16): DLS_FAULT=sigterm@9 is a
    preemption NOTICE for host 1 of a 2-host gang. The doomed rank drains
    its in-flight step, the state is re-gathered live and handed off, the
    gang exits clean — and the supervisor classifies it graceful-shutdown
    (not training-crash), shrinks IMMEDIATELY (no repeat-evidence wait, no
    backoff slot), and the relaunch continues from the CURRENT step via
    the handoff: checkpoint-free, no walk-back, loss trajectory matching
    an unfaulted run. (die_host keeps its checkpoint walk-back — the drill
    above.)"""
    wd = tmp_path / "run"
    wd.mkdir()
    sup = Supervisor(
        [sys.executable, WORKER, "elastic", "--ckpt-dir", str(wd),
         "--steps", "18", "--checkpoint-every", "6"],
        num_processes=2, max_restarts=4, restart_backoff_s=0.05,
        backoff_jitter=0.0, shrink_after=2,
        env={**_CLEAN_ENV, "DLS_FAULT": "sigterm@9"},
        progress_path=str(wd),
    )
    result = sup.run()
    assert result.ok, (
        f"attempts: {[(a.ordinal, a.returncodes, a.classification) for a in result.attempts]}")
    # ONE drain, ONE relaunch — no dead-host repeat evidence needed
    assert result.restarts == 1
    assert [a.num_processes for a in result.attempts] == [2, 1]
    assert result.attempts[0].classification == "graceful-shutdown"
    assert result.attempts[0].returncodes == [0, 0]
    step, attempt, nprocs = open(wd / "DONE").read().split()
    assert (int(step), int(attempt), int(nprocs)) == (18, 1, 1)
    # the evidence file was consumed to its forensic rename
    assert not os.path.exists(wd / "DRAIN")
    assert os.path.exists(wd / "DRAIN.consumed-0")

    events = telemetry.read_events(wd)
    # first-class graceful_shutdown event at the drained step
    gs = [e for e in events if e.get("kind") == "recovery"
          and e.get("event") == "graceful_shutdown"]
    assert len(gs) == 1
    assert gs[0]["step"] == 9 and gs[0]["dead_host"] == 1
    assert gs[0]["drained"] is True
    # the shrink resumed from the DRAIN step via the live handoff —
    # not from a checkpoint walk-back
    geo = _geometry_changes(wd)
    assert len(geo) == 1, geo
    assert geo[0]["resume"] == "live-handoff"
    assert geo[0]["step"] == 9
    assert geo[0]["dead_host"] == 1
    assert geo[0]["from_processes"] == 2 and geo[0]["to_processes"] == 1
    # reshard telemetry: the drain's live re-gather + the relaunch's
    # handoff ingest; NOTHING walked back through a checkpoint
    rs = [e for e in events if e.get("kind") == "recovery"
          and e.get("event") == "reshard"]
    assert any(e["transport"] == "collectives"
               and e.get("reason") == "preemption-drain" for e in rs), rs
    assert any(e["transport"] == "handoff"
               and e.get("reason") == "preemption-resume" for e in rs), rs
    assert not any(e.get("walk_back") for e in rs), rs
    # no step ran twice: drain at 9, resume at 10 — checkpoint-free
    seen = [int(e["step"]) for e in events
            if e.get("kind") == "step_metrics"]
    assert len(seen) == len(set(seen)), sorted(seen)
    # no backoff slot burned on the graceful path
    assert not any(e.get("kind") == "attempt" and e.get("edge") == "backoff"
                   for e in events)

    # dlstatus explains the incident: graceful line, reshard block, np 2->1
    rep = status.report(str(wd))
    assert rep["reshard"]["live_moves"] >= 2
    assert rep["reshard"]["walk_back_moves"] == 0
    rendered = status.render(rep)
    assert "graceful shutdown: host 1" in rendered, rendered
    assert "checkpoint-free (live)" in rendered, rendered

    # loss trajectory: the whole drill run must match an unfaulted 1-host
    # run step for step (the drain/handoff must not perturb training)
    clean = tmp_path / "clean"
    clean.mkdir()
    sup2 = Supervisor(
        [sys.executable, WORKER, "elastic", "--ckpt-dir", str(clean),
         "--steps", "18", "--checkpoint-every", "6"],
        num_processes=1, max_restarts=0, env=_CLEAN_ENV,
        progress_path=str(clean),
    )
    assert sup2.run().ok
    drill_losses = _losses_by_step(wd)
    clean_losses = _losses_by_step(clean)
    common = sorted(set(drill_losses) & set(clean_losses))
    assert common and common[-1] == 18, (drill_losses, clean_losses)
    assert any(s > 9 for s in common)  # post-drain steps are compared
    for s in common:
        assert drill_losses[s] == pytest.approx(clean_losses[s], rel=1e-6), (
            s, drill_losses[s], clean_losses[s])


def test_die_host_real_gang_reshards_onto_survivor(tmp_path):
    """The same drill over a REAL jax.distributed gang (2 processes sharing
    one DP mesh): host 1's rank dies at step 12 and stays dead; the shrunk
    relaunch restores the 2-host checkpoint onto the 1-host mesh through
    the reshard-on-restore path and finishes. Skips (with evidence) on
    builds whose CPU backend cannot run multiprocess collectives."""
    from tests.test_supervisor import _gang_skip_reason

    reason = _gang_skip_reason()
    if reason:
        pytest.skip(reason)
    sup = Supervisor(
        [sys.executable, WORKER, "train", "--ckpt-dir", str(tmp_path),
         "--steps", "24", "--checkpoint-every", "6"],
        num_processes=2, max_restarts=4, restart_backoff_s=0.05,
        backoff_jitter=0.0, shrink_after=2,
        env={**_CLEAN_ENV, "DLS_FAULT": "die_host@12"},
        progress_path=str(tmp_path), hang_timeout_s=60.0,
        startup_grace_s=240.0,
    )
    result = sup.run()
    assert result.ok, (
        f"attempts: {[(a.ordinal, a.returncodes, a.classification) for a in result.attempts]}")
    assert result.attempts[-1].num_processes == 1
    step, _attempt = open(tmp_path / "DONE").read().split()
    assert int(step) == 24
    assert _geometry_changes(tmp_path), "no geometry_change event recorded"


# -- drill 4: NaN spike vs the divergence policies ---------------------------


def _mnist_trainer(checkpointer=None, seed=1):
    import optax

    from distributeddeeplearningspark_tpu import (
        PartitionedDataset,
        Session,
        Trainer,
    )
    from distributeddeeplearningspark_tpu.models import LeNet5
    from distributeddeeplearningspark_tpu.train import losses

    rng = np.random.default_rng(0)
    examples = [
        {"image": rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
         "label": np.int32(i % 10)}
        for i in range(128)
    ]
    sess = Session.builder.master("local[2]").getOrCreate()
    ds = PartitionedDataset.parallelize(examples, 2).repeat()
    t = Trainer(sess, LeNet5(), losses.softmax_xent,
                optax.sgd(0.05, momentum=0.9), checkpointer=checkpointer,
                seed=seed)
    return t, ds


def test_nan_spike_skip_policy_finishes_finite(tmp_path, monkeypatch):
    """Acceptance: fit(on_nonfinite='skip') + DLS_FAULT=nan@N finishes with
    finite final metrics and reports the skipped-step count in its summary;
    params never absorb the poisoned update."""
    import jax

    monkeypatch.setenv("DLS_FAULT", "nan@5")
    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path))
    monkeypatch.delenv("DLS_RESTART", raising=False)
    t, ds = _mnist_trainer()
    state, summary = t.fit(ds, batch_size=16, steps=10, log_every=2,
                           on_nonfinite="skip")
    assert summary["skipped_steps"] == 1.0
    assert np.isfinite(summary["loss"]) and np.isfinite(summary["grad_norm"])
    assert int(jax.device_get(state.step)) == 10
    for leaf in jax.tree.leaves(state.params):
        assert np.all(np.isfinite(np.asarray(jax.device_get(leaf))))
    # the divergence skip left its durable audit record
    assert any(e["event"] == "skip" and e.get("skipped_steps") == 1
               for e in _recovery_events(tmp_path)), \
        _recovery_events(tmp_path)


def test_nan_every_step_exhausts_skip_budget(monkeypatch):
    """Persistent divergence must not masquerade as progress: a loss that is
    non-finite from init (lr=inf blows up step 1 and never recovers) has to
    fail once the skip budget is exhausted."""
    import optax

    from distributeddeeplearningspark_tpu import PartitionedDataset, Session, Trainer
    from distributeddeeplearningspark_tpu.models import LeNet5
    from distributeddeeplearningspark_tpu.train import losses

    monkeypatch.delenv("DLS_FAULT", raising=False)
    rng = np.random.default_rng(0)
    examples = [
        {"image": rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
         "label": np.int32(i % 10)}
        for i in range(64)
    ]
    sess = Session.builder.master("local[2]").getOrCreate()
    ds = PartitionedDataset.parallelize(examples, 2).repeat()
    t = Trainer(sess, LeNet5(), losses.softmax_xent,
                optax.sgd(float("inf")), seed=1)
    with pytest.raises(FloatingPointError, match="nonfinite_budget"):
        t.fit(ds, batch_size=16, steps=50, log_every=2,
              on_nonfinite="skip", nonfinite_budget=3)


def test_nan_spike_rollback_policy(tmp_path, monkeypatch):
    """fit(on_nonfinite='rollback'): the model rewinds to the last verified
    checkpoint while the data stream keeps moving, so the poisoned window is
    fast-forwarded past and training completes with finite metrics."""
    import jax

    from distributeddeeplearningspark_tpu import Checkpointer

    monkeypatch.setenv("DLS_FAULT", "nan@6")
    monkeypatch.delenv("DLS_RESTART", raising=False)
    with Checkpointer(tmp_path / "ck") as ck:
        t, ds = _mnist_trainer(checkpointer=ck)
        state, summary = t.fit(ds, batch_size=16, steps=12, log_every=2,
                               checkpoint_every=4, on_nonfinite="rollback")
        assert summary["rollbacks"] == 1.0
        assert np.isfinite(summary["loss"])
        assert int(jax.device_get(state.step)) == 12
        for leaf in jax.tree.leaves(state.params):
            assert np.all(np.isfinite(np.asarray(jax.device_get(leaf))))
        # the final checkpoint's data_state must record the TRUE stream
        # position: 12 steps of state + the 2-batch rolled-back window the
        # feed consumed (model rewound 6→4, stream did not)
        _, data_state = ck.restore(state)
        assert data_state["examples_seen"] == (12 + 2) * 16, data_state
    # telemetry (bound to the checkpointer dir): the rollback recovery
    # record names the step the model rewound to
    recov = _recovery_events(tmp_path / "ck")
    assert any(e["event"] == "rollback" and e.get("to_step") == 4
               for e in recov), recov


def test_rollback_walks_past_nan_checkpoints(tmp_path, monkeypatch):
    """Checkpoint cadence finer than the detection window: the newest
    byte-verified checkpoints hold NaN params (divergence was saved before a
    log boundary saw it). Rollback must detect the poisoned restore, \
quarantine those steps, and walk back to the last numerically clean one."""
    import jax

    from distributeddeeplearningspark_tpu import Checkpointer

    monkeypatch.setenv("DLS_FAULT", "nan@2")
    monkeypatch.delenv("DLS_RESTART", raising=False)
    with Checkpointer(tmp_path / "ck", max_to_keep=20) as ck:
        t, ds = _mnist_trainer(checkpointer=ck)
        state, summary = t.fit(ds, batch_size=16, steps=10, log_every=5,
                               checkpoint_every=1, on_nonfinite="rollback")
        assert summary["rollbacks"] == 1.0
        assert np.isfinite(summary["loss"])
        assert int(jax.device_get(state.step)) == 10
        for leaf in jax.tree.leaves(state.params):
            assert np.all(np.isfinite(np.asarray(jax.device_get(leaf))))
    # the NaN-holding steps (2..4 — step 5's save is pre-empted by the
    # rollback itself) were quarantined; clean step 1 survived and was the
    # restore target
    quarantined = {d.split(".")[0] for d in _corrupt_dirs(tmp_path / "ck")}
    assert quarantined >= {"2", "3", "4"}, sorted(os.listdir(tmp_path / "ck"))
    assert os.path.isdir(tmp_path / "ck" / "1")


def test_rollback_without_checkpointer_raises(monkeypatch):
    monkeypatch.delenv("DLS_FAULT", raising=False)
    import optax

    from distributeddeeplearningspark_tpu import PartitionedDataset, Session, Trainer
    from distributeddeeplearningspark_tpu.models import LeNet5
    from distributeddeeplearningspark_tpu.train import losses

    rng = np.random.default_rng(0)
    examples = [
        {"image": rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
         "label": np.int32(i % 10)}
        for i in range(64)
    ]
    sess = Session.builder.master("local[2]").getOrCreate()
    ds = PartitionedDataset.parallelize(examples, 2).repeat()
    t = Trainer(sess, LeNet5(), losses.softmax_xent, optax.sgd(float("inf")),
                seed=1)
    with pytest.raises(FloatingPointError, match="checkpointer"):
        t.fit(ds, batch_size=16, steps=10, log_every=2,
              on_nonfinite="rollback")


def test_on_nonfinite_validation():
    t, ds = _mnist_trainer()
    with pytest.raises(ValueError, match="on_nonfinite"):
        t.fit(ds, batch_size=16, steps=2, on_nonfinite="retry")
