"""Tracing/profiling — the rebuild of the reference's Spark-UI/torch-profiler.

The reference's observability is Spark stage timelines plus (optionally) the
torch profiler inside the mapPartitions closure (SURVEY.md §5
'Tracing/profiling'). TPU-first, the device timeline lives in XLA/PJRT, so the
native story is:

- ``jax.profiler`` traces (host Python + device HLO timeline) written in
  TensorBoard 'profile' plugin format — ``ProfileSpec`` captures a window of
  steps mid-training from the Trainer without stopping the job;
- the program's own host spans (``dls.feed/*``, ``dls.step/*``,
  ``dls.fit/*``, ``dls.phase/*``: :mod:`..telemetry.spans`) and the ``train``
  step marker (:func:`step_annotation`) are written into whatever trace is
  running, so an idle gap of the device is attributable in the trace viewer;
- XLA HLO dumps (``enable_xla_dump``) for compiler-level inspection of what
  GSPMD did to the step function — set BEFORE the first compile.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import logging
import os

import jax

from distributeddeeplearningspark_tpu import telemetry

logger = logging.getLogger("distributeddeeplearningspark_tpu.profiling")


@dataclasses.dataclass(frozen=True)
class ProfileSpec:
    """Capture ``num_steps`` steps starting at ``start_step`` into ``dir``.

    ``start_step`` defaults past warmup so the window sees steady-state steps,
    not the first compile.
    """

    dir: str
    start_step: int = 10
    num_steps: int = 5


class StepProfiler:
    """Drives a jax.profiler trace window across a training loop.

    Call ``observe(step)`` once per loop iteration; the profiler starts and
    stops itself around the configured window. Trace capture is process-local;
    on a pod every host writes its own trace (process 0's is the one usually
    inspected).
    """

    def __init__(self, spec: ProfileSpec | None, *, start_offset: int = 0,
                 sync=None):
        """``start_offset`` shifts the window to be relative to the loop's
        first step (a job resumed at step 1000 with start_step=10 traces
        steps 1010+, not the post-restore recompile). ``sync`` is a zero-arg
        callable that blocks until the dispatched steps' device work is done —
        REQUIRED for a faithful trace under async dispatch; the Trainer passes
        one that blocks on the live train state."""
        self.spec = spec
        self.start_offset = start_offset
        self._sync = sync
        self._active = False
        self._done = spec is None
        self._breakdown_thread = None

    def observe(self, step: int) -> None:
        if self._done:
            return
        assert self.spec is not None
        if not self._active and step >= self.spec.start_step + self.start_offset:
            os.makedirs(self.spec.dir, exist_ok=True)
            jax.profiler.start_trace(self.spec.dir)
            self._active = True
            self._stop_at = step + self.spec.num_steps
            # mark the window in the run's event stream (informational —
            # "profile-trace" is not a goodput overhead category) so a
            # dlstatus reader knows which steps carry tracing overhead
            telemetry.emit("phase", name="profile-trace", edge="begin",
                           step=step, dir=self.spec.dir)
            logger.info("profiler: tracing steps %d..%d → %s",
                        step, self._stop_at, self.spec.dir)
        elif self._active and step >= self._stop_at:
            self.stop()

    def stop(self) -> None:
        if self._active:
            # block on the real step outputs so the trace includes the
            # windowed steps' device work (async dispatch runs ahead)
            if self._sync is not None:
                self._sync()
            jax.profiler.stop_trace()
            self._active = False
            telemetry.emit("phase", name="profile-trace", edge="end",
                           dir=self.spec.dir)
            logger.info("profiler: trace written to %s", self.spec.dir)
            # Spark-UI moment: surface where the captured steps' device time
            # went without requiring TensorBoard (whose profile converter is
            # broken in mismatched installs — see op_breakdown/xplane.py).
            # In a DAEMON THREAD: the parse is a subprocess that can take
            # seconds, and stop() fires mid-training-loop — a synchronous
            # parse would stall the loop and corrupt the enclosing metrics
            # lap's step timing.
            import threading

            def _log_budget(d: str) -> None:
                rec = op_breakdown(d, top=5)
                if rec.get("ops"):
                    budget = ", ".join(
                        f"{o['name']} {o['pct']:.1f}%" for o in rec["ops"])
                    logger.info("profiler: device-time budget (%s, %.1f ms): %s",
                                rec.get("line"), rec.get("total_ms", 0.0), budget)
                else:
                    logger.info("profiler: no device-time budget: %s",
                                rec.get("error", "trace had no op events"))

            self._breakdown_thread = threading.Thread(
                target=_log_budget, args=(self.spec.dir,), daemon=True,
                name="op-breakdown",
            )
            self._breakdown_thread.start()
        self._done = True

    def join_breakdown(self, timeout_s: float = 150.0) -> None:
        """Wait for the async device-time-budget log (call AFTER the training
        loop — e.g. Trainer does, once timing laps are closed — so short jobs
        still surface the budget without the parse ever stalling a step).

        Default exceeds op_breakdown's 120 s subprocess timeout so the wait
        can't silently abandon a parse that was about to finish; if the
        thread is somehow still alive afterwards, say so instead of letting
        the promised budget line vanish without a trace."""
        if self._breakdown_thread is not None:
            self._breakdown_thread.join(timeout_s)
            if self._breakdown_thread.is_alive():
                logger.warning(
                    "profiler: device-time budget parse still running after "
                    "%.0fs — abandoning (trace remains at %s)",
                    timeout_s, self.spec.dir)


def step_annotation(step: int):
    """Mark one train step so the profile tool computes per-step stats."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


def enable_xla_dump(dump_dir: str) -> None:
    """Route XLA HLO dumps (post-GSPMD, post-fusion) to ``dump_dir``.

    Must run before the first jit compilation; appends to XLA_FLAGS so it
    composes with the fake-device flag used in tests.
    """
    os.makedirs(dump_dir, exist_ok=True)
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_dump_to" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} --xla_dump_to={dump_dir}".strip()


def trace_files(profile_dir: str) -> list[str]:
    """The .xplane.pb trace files a capture produced (for tooling/tests)."""
    return sorted(
        glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    )


def op_breakdown(profile_dir_or_file: str, *, top: int = 25,
                 timeout_s: float = 120.0) -> dict:
    """Per-op device-time budget from a captured trace — "where did the step
    go?" without TensorBoard (whose profile-plugin converter is broken by a
    protobuf mismatch in common installs; see utils/xplane.py).

    Accepts a profile directory (uses the newest ``.xplane.pb`` capture) or a
    single xplane file. Returns ``{"plane", "line", "total_ms",
    "event_count", "ops": [{"name", "ms", "pct", "count", "top_instance"}]}``
    with ops aggregated by HLO op class and sorted by total time, or
    ``{"error": ...}``.

    Runs the parse in a subprocess under the pure-python protobuf runtime —
    the env's stale generated protos cannot load under the C++ runtime, and
    the runtime choice is frozen at first protobuf import, so it must happen
    in a fresh interpreter.
    """
    import json
    import subprocess
    import sys

    path = profile_dir_or_file
    if not os.path.exists(path):
        return {"error": f"no such file or directory: {path}"}
    if os.path.isdir(path):
        files = trace_files(path)
        if not files:
            return {"error": f"no .xplane.pb under {path}"}
        path = max(files, key=os.path.getmtime)
    env = dict(os.environ, PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION="python")
    try:
        out = subprocess.run(
            [sys.executable, "-m",
             "distributeddeeplearningspark_tpu.utils.xplane", path, str(top)],
            capture_output=True, text=True, timeout=timeout_s, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"xplane parse exceeded {timeout_s:.0f}s"}
    try:
        rec = json.loads(out.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": f"xplane parser produced no JSON: "
                         f"{(out.stderr or out.stdout)[-300:]}"}
    return rec


def profile_cli(argv=None) -> int:
    """``dlprofile <trace-dir-or-xplane.pb>`` — print the device-time budget.

    The terminal counterpart of the Spark UI stage table: point it at any
    ``--profile-dir`` capture (or a bare ``.xplane.pb``) and read where the
    step went, without TensorBoard. Its sibling ``dlstatus`` answers the
    wall-clock question (goodput, attempts, recovery) from the run's
    telemetry stream — see docs/OBSERVABILITY.md.
    """
    import argparse
    import json

    ap = argparse.ArgumentParser(prog="dlprofile", description=profile_cli.__doc__)
    ap.add_argument("path", help="profile dir (newest capture used) or .xplane.pb")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    args = ap.parse_args(argv)
    rec = op_breakdown(args.path, top=args.top)
    if args.json:
        print(json.dumps(rec))
        return 0 if rec.get("ops") else 1
    if not rec.get("ops"):
        print(f"error: {rec.get('error', 'trace contains no op events')}")
        return 1
    print(f"{rec['plane']}  [{rec['line']}]  total {rec['total_ms']:.1f} ms "
          f"over {rec['event_count']} events")
    for o in rec["ops"]:
        print(f"{o['pct']:6.2f}%  {o['ms']:9.2f} ms  x{o['count']:<6d} {o['name']}")
        if o.get("top_instance"):
            print(f"         └─ {o['top_instance'][:100]}")
    return 0


@contextlib.contextmanager
def trace(profile_dir: str):
    """Context-manager capture: everything inside the block is traced."""
    os.makedirs(profile_dir, exist_ok=True)
    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
