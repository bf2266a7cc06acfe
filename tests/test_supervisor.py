"""Elastic supervisor + fault injection + desync sanitizer (SURVEY.md §4/§5).

These run REAL multi-process jax.distributed gangs (gloo collectives over
localhost) — the rebuild's analogue of the reference's `local[2]` two-executor
Spark testbed, including the kill-one-process recovery drill.
"""

import functools
import os
import subprocess
import sys

import numpy as np
import pytest

from distributeddeeplearningspark_tpu.supervisor import (
    Supervisor,
    SupervisorResult,
    free_port,
)

WORKER = os.path.join(os.path.dirname(__file__), "workers", "worker.py")

# Worker processes must NOT inherit the 8-fake-device flag the test process
# uses — each gang member is one "executor" with its own single CPU device.
_CLEAN_ENV = {"XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"}

# Minimal 2-process rendezvous + one cross-process collective — exactly the
# machinery every gang drill below depends on, nothing else.
_GANG_PROBE = """\
import os
import jax
jax.distributed.initialize(coordinator_address=os.environ["DLS_COORDINATOR"],
                           num_processes=2,
                           process_id=int(os.environ["DLS_PROCESS_ID"]))
from jax.experimental import multihost_utils
multihost_utils.sync_global_devices("gang-probe")
"""


@functools.lru_cache(maxsize=1)
def _gang_skip_reason() -> str | None:
    """Capability probe, run once per session: can this jax build actually
    execute CPU multiprocess collectives? Some builds rendezvous fine and
    then die at the first cross-process psum with "Multiprocess
    computations aren't implemented on the CPU backend" — an environmental
    limit, not a supervisor bug, so the real-gang drills SKIP with the
    probe's evidence instead of failing every full-suite run on such
    builds."""
    port = free_port()
    base_env = {**os.environ, **_CLEAN_ENV,
                "DLS_COORDINATOR": f"localhost:{port}"}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _GANG_PROBE],
            env={**base_env, "DLS_PROCESS_ID": str(pid)},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for pid in range(2)
    ]
    tails = []
    for p in procs:
        try:
            _, err = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
                q.wait()
            return "2-process CPU gang probe hung at rendezvous/collective"
        if p.returncode != 0 and err.strip():
            tails.append(err.strip().splitlines()[-1])
    if all(p.returncode == 0 for p in procs):
        return None
    return ("this jax build cannot run CPU multiprocess collectives: "
            + (tails[0][:160] if tails else "probe worker died"))


@pytest.fixture()
def gang():
    """Skip (with the probe's evidence) when real multi-process gangs
    cannot run here; the probe result is cached for the session."""
    reason = _gang_skip_reason()
    if reason:
        pytest.skip(reason)


def test_gang_completes_without_faults(tmp_path, gang):
    sup = Supervisor(
        [sys.executable, WORKER, "train", "--ckpt-dir", str(tmp_path),
         "--steps", "10", "--checkpoint-every", "5"],
        num_processes=2, max_restarts=0, env=_CLEAN_ENV,
    )
    result = sup.run()
    assert result.ok and result.restarts == 0
    step, attempt = open(tmp_path / "DONE").read().split()
    assert int(step) == 10 and int(attempt) == 0


def test_kill_one_worker_recovers_from_checkpoint(tmp_path, gang):
    """Process 1 SIGKILLs itself at step 15 of 30 on attempt 0; the supervisor
    tears down the gang and relaunches; workers resume from the step-10
    checkpoint and finish."""
    sup = Supervisor(
        [sys.executable, WORKER, "train", "--ckpt-dir", str(tmp_path),
         "--steps", "30", "--checkpoint-every", "10", "--fault-step", "15"],
        num_processes=2, max_restarts=2, env=_CLEAN_ENV,
        hang_timeout_s=120.0, progress_path=str(tmp_path),
    )
    result = sup.run()
    assert result.ok, f"attempts: {[(a.ordinal, a.returncodes) for a in result.attempts]}"
    assert result.restarts == 1
    # SIGKILL shows up as -9 on the faulted attempt
    assert -9 in result.attempts[0].returncodes
    step, attempt = open(tmp_path / "DONE").read().split()
    assert int(step) == 30 and int(attempt) == 1


def test_two_process_gang_matches_single_process_numerics(tmp_path, eight_devices, gang):
    """VERDICT r4 next-#8: the DCN control-plane analog of the dryrun's
    single-process fingerprint. A 2-process × 4-device jax.distributed
    gang runs 5 deterministic DP steps; post-step params must equal a
    single-process run over the same 8-device topology numerically — the
    supervisor drills prove processes LIVE across the boundary, this
    proves the numbers CROSS it unchanged (same recipe function on both
    sides, so only the process boundary can differ)."""
    import importlib.util

    out = tmp_path / "gang.npz"
    sup = Supervisor(
        [sys.executable, WORKER, "fingerprint", "--steps", "5",
         "--batch-size", "32", "--out", str(out)],
        num_processes=2, max_restarts=0,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "JAX_PLATFORMS": "cpu"},
    )
    result = sup.run()
    assert result.ok, f"returncodes: {result.attempts[-1].returncodes}"
    gang = dict(np.load(out))

    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    spec = importlib.util.spec_from_file_location("fp_worker", WORKER)
    wmod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(wmod)
    ref = wmod.fingerprint_reference(
        5, 32, MeshSpec(data=-1).build(eight_devices))
    assert gang.keys() == ref.keys()
    for k in ref:
        np.testing.assert_allclose(gang[k], ref[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


def test_desync_sanitizer_catches_split_brain(tmp_path, gang):
    sup = Supervisor(
        [sys.executable, WORKER, "desync"],
        num_processes=2, max_restarts=0, env=_CLEAN_ENV,
    )
    result = sup.run()
    assert result.ok, f"returncodes: {result.attempts[-1].returncodes}"


def test_supervisor_gives_up_after_max_restarts():
    sup = Supervisor(
        [sys.executable, "-c", "import sys; sys.exit(7)"],
        num_processes=1, max_restarts=2, restart_backoff_s=0.01,
    )
    result = sup.run()
    assert not result.ok
    assert len(result.attempts) == 3
    assert all(a.returncodes == [7] for a in result.attempts)
    # no progress tracking configured and no checkpoint dir → a plain crash,
    # never misclassified as a restore failure
    assert all(a.classification == "training-crash" for a in result.attempts)


def test_restart_backoff_grows_exponentially_with_cap():
    """Satellite: the relaunch delay doubles per attempt from the base and
    saturates at restart_backoff_max_s (jitter disabled for determinism)."""
    s = Supervisor(["true"], restart_backoff_s=0.5, restart_backoff_max_s=3.0,
                   backoff_jitter=0.0)
    assert [s._backoff_delay(i) for i in range(5)] == [0.5, 1.0, 2.0, 3.0, 3.0]
    j = Supervisor(["true"], restart_backoff_s=1.0, restart_backoff_max_s=8.0,
                   backoff_jitter=0.25)
    for i in range(4):
        base = min(1.0 * 2 ** i, 8.0)
        d = j._backoff_delay(i)
        assert 0.75 * base <= d <= 1.25 * base, (i, d)


def test_restart_backoff_timing_observed(monkeypatch):
    """The run loop actually waits the exponential delays between attempts
    (sleep calls recorded; poll-interval sleeps are distinguishable)."""
    from distributeddeeplearningspark_tpu import supervisor as sup_mod

    sleeps: list[float] = []
    real_sleep = sup_mod.time.sleep
    monkeypatch.setattr(
        sup_mod.time, "sleep",
        lambda s: (sleeps.append(s), real_sleep(min(s, 0.01)))[1])
    result = Supervisor(
        [sys.executable, "-c", "import sys; sys.exit(7)"],
        max_restarts=2, restart_backoff_s=0.15, backoff_jitter=0.0,
        poll_interval=0.01,
    ).run()
    assert len(result.attempts) == 3
    backoffs = [s for s in sleeps if s > 0.01]
    assert backoffs == [0.15, 0.3], backoffs


def test_backoff_resets_after_observed_progress(tmp_path, monkeypatch):
    """Satellite: an attempt with OBSERVED progress evidence resets the
    backoff ladder — a run that trains for a while and then crashes backs
    off from the base delay, not from its early flaky attempts' doubled
    ceiling. (Without progress tracking the ladder still doubles — see
    test_restart_backoff_timing_observed — because made_progress is then
    only assumed.)"""
    from distributeddeeplearningspark_tpu import supervisor as sup_mod

    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        "open(os.path.join(os.environ['PROG'], 'touch'), 'w').write('x')\n"
        "sys.exit(7)\n"
    )
    prog = tmp_path / "prog"
    prog.mkdir()
    sleeps: list[float] = []
    real_sleep = sup_mod.time.sleep
    monkeypatch.setattr(
        sup_mod.time, "sleep",
        lambda s: (sleeps.append(s), real_sleep(min(s, 0.01)))[1])
    result = Supervisor(
        [sys.executable, str(script)],
        max_restarts=2, restart_backoff_s=0.15, backoff_jitter=0.0,
        poll_interval=0.01, progress_path=str(prog),
        env={"PROG": str(prog)},
    ).run()
    assert len(result.attempts) == 3
    assert all(a.made_progress for a in result.attempts)
    backoffs = [s for s in sleeps if s > 0.01]
    # every attempt made real progress → every delay is the base, no doubling
    assert backoffs == [0.15, 0.15], backoffs


def test_shrink_to_survive_drops_dead_host(tmp_path):
    """Fast-tier shrink drill (plain-python workers): host 1 dies on every
    attempt; after shrink_after=2 consecutive same-host failures the gang
    re-plans to the surviving host — which then finishes — and the
    geometry_change recovery record ties evidence to action. Host identity
    (DLS_HOST_ID) stays stable across the rank renumbering."""
    from distributeddeeplearningspark_tpu import telemetry

    (tmp_path / "10").mkdir()  # the "last checkpoint" the relaunch resumes
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys, time\n"
        "host = os.environ.get('DLS_HOST_ID', os.environ['DLS_PROCESS_ID'])\n"
        "if host == '1':\n"
        "    sys.exit(1)\n"
        "if os.environ['DLS_NUM_PROCESSES'] == '1':\n"
        "    with open(os.path.join(sys.argv[1], 'DONE'), 'w') as f:\n"
        "        f.write(os.environ['DLS_RESTART'] + ' '\n"
        "                + os.environ['DLS_HOST_ID'])\n"
        "    sys.exit(0)\n"
        "time.sleep(30)\n"  # healthy peer: killed when host 1 dies
    )
    sup = Supervisor(
        [sys.executable, str(script), str(tmp_path)],
        num_processes=2, max_restarts=3, restart_backoff_s=0.01,
        backoff_jitter=0.0, ckpt_dir=str(tmp_path), shrink_after=2,
    )
    result = sup.run()
    assert result.ok, [(a.returncodes, a.classification) for a in result.attempts]
    assert [a.num_processes for a in result.attempts] == [2, 2, 1]
    assert [a.dead_host for a in result.attempts] == [1, 1, None]
    attempt, host = open(tmp_path / "DONE").read().split()
    assert (attempt, host) == ("2", "0")  # survivor kept its host identity
    geo = [e for e in telemetry.read_events(tmp_path)
           if e.get("kind") == "recovery" and e["event"] == "geometry_change"]
    assert len(geo) == 1
    assert geo[0]["dead_host"] == 1 and geo[0]["hosts"] == [0]
    assert geo[0]["from_processes"] == 2 and geo[0]["to_processes"] == 1
    assert geo[0]["step"] == 10  # the checkpoint the survivors resume from
    assert geo[0]["batch_policy"] == "preserve_global"


def test_shrink_respects_min_processes(tmp_path):
    """The gang never shrinks below min_processes — a persistent dead host
    in a gang already at the floor burns restarts instead of amputating to
    nothing."""
    script = "import sys; sys.exit(1)\n"
    sup = Supervisor(
        [sys.executable, "-c", script],
        num_processes=2, max_restarts=3, restart_backoff_s=0.01,
        backoff_jitter=0.0, shrink_after=2, min_processes=2,
    )
    result = sup.run()
    assert not result.ok
    assert all(a.num_processes == 2 for a in result.attempts)


def test_result_shapes():
    r = SupervisorResult(attempts=[])
    assert not r.ok and r.restarts == 0


def test_startup_grace_defaults_to_5x_hang_timeout():
    """ADVICE r1: first-checkpoint latency (compile + warmup) must not be
    judged by the steady-state hang timeout."""
    s = Supervisor(["true"], hang_timeout_s=2.0)
    assert s.startup_grace_s == 10.0
    s2 = Supervisor(["true"], hang_timeout_s=2.0, startup_grace_s=30.0)
    assert s2.startup_grace_s == 30.0
    assert Supervisor(["true"]).startup_grace_s is None


def test_heartbeat_file_counts_as_progress(tmp_path):
    """ADVICE r1: a worker stamping DLS_HEARTBEAT_FILE between checkpoints
    must not be judged hung by the watchdog."""
    # worker: stamps the heartbeat every 0.2s for 2.5s, never checkpoints
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, time\n"
        "hb = os.environ['DLS_HEARTBEAT_FILE']\n"
        "for _ in range(12):\n"
        "    open(hb, 'w').write('x')\n"
        "    time.sleep(0.2)\n"
    )
    # hang_timeout (steady state) is far shorter than the worker's runtime,
    # so only the heartbeats keep it alive; startup grace stays generous —
    # python startup in this sandbox alone takes >1s (site hooks)
    s = Supervisor([sys.executable, str(script)], num_processes=1,
                   max_restarts=0, hang_timeout_s=1.0, startup_grace_s=30.0,
                   progress_path=str(tmp_path / "ckpt-does-not-exist"))
    result = s.run()
    assert result.ok, f"healthy heartbeating worker was killed: {result}"
    assert result.attempts[0].returncodes == [0]
