"""Device-side performance observatory: compile ledger, step anatomy, MFU.

Every observability layer so far watches the host side — goodput wall-clock
(:mod:`..telemetry`), fleet skew (:mod:`.fleet`), request spans
(:mod:`.trace`). Nothing watched the device/compiler dimension: a silent
recompile storm, shrinking HBM headroom, or a 15% step-time regression was
invisible until a human reread BENCH files. This module closes that gap
with four instruments that all land on the same JSONL bus:

- **Compile ledger** (:func:`instrument` / :class:`InstrumentedFunction`):
  a wrapper around a jitted callable that owns the lower→compile path via
  AOT dispatch. Every executable it builds emits one ``compile`` event —
  shape/dtype signature, compile seconds, ``cost_analysis()`` FLOPs and
  bytes accessed, ``memory_analysis()`` buffer sizes — wrapped in a
  ``compile`` *phase* span so goodput accounts the stall. Recompile
  detection generalizes the serve engine's pinned ``compiled_batch_shapes``
  discipline: a signature compiling more than once, or the distinct-
  signature count exceeding the wrapper's ``expected_signatures`` (1 for a
  shape-stable train step; the bucket-ladder size for the serve forwards),
  flags the event ``recompile=True``.
- **Step anatomy** (:class:`StepAnatomy`): splits each training lap's
  wall-clock into *device* (timed dispatch on the compiled executable +
  the lap-boundary drain the host blocks on), *compile* (in-lap ledger
  compiles), *input-wait* (the starvation probe's number), and *host* (the
  measured residual: python bookkeeping, transfers, checkpoint/eval work),
  and names the parts of that residual (``input_put_s``, ``emit_s``,
  ``callbacks_s``, ``checkpoint_s``, ``eval_s``, ``unaccounted_s``): every
  part is a section of :func:`.spans.span`, which also writes it into the
  profiler's trace.
  Per-lap **MFU** is computed from the ledger's analytical FLOPs over a
  per-backend peak-FLOPs table (``DLS_PEAK_FLOPS`` override; a labeled
  nominal figure on CPU so host drills still get a finite, comparable
  number). The gauges ride each ``step_metrics`` record.
- **Startup ledger** (:class:`StartupLedger`, the process's one
  :data:`STARTUP`): the ``dls.start/*`` sections from the package's import
  to the close of the first lap, which tile that time and ride ONE
  ``startup`` record a process.
- **HBM watermarks** (:func:`memory_watermarks`): jax device memory stats
  (``bytes_in_use`` / ``peak_bytes_in_use`` / ``peak_bytes_reserved`` /
  ``bytes_limit``) where the backend exposes them, live-buffer byte totals
  as the CPU fallback —
  emitted as ``memory`` events per metrics lap, the headroom trendline
  ``dlstatus --anatomy`` renders and the Chrome exporter draws as a
  counter track.

The reader side (:func:`anatomy_report`) is a pure jax-free fold over the
event stream, like every other ``dlstatus`` section — jax imports in this
module are all function-local so the CLI never pays (or requires) a
backend.
"""

from __future__ import annotations

import hashlib
import logging
import os
import threading
import time
from typing import Any, Callable, Iterable

from distributeddeeplearningspark_tpu import _T_IMPORT
from distributeddeeplearningspark_tpu import telemetry as telemetry_lib
from distributeddeeplearningspark_tpu.telemetry import spans

logger = logging.getLogger("distributeddeeplearningspark_tpu.telemetry.anatomy")

#: Env override for the per-chip peak FLOPs/s the MFU denominator uses —
#: wins over the spec-sheet table (calibrate CPU drills, price a derated
#: clock, or pin a projection's denominator explicitly).
PEAK_FLOPS_ENV = "DLS_PEAK_FLOPS"

#: Nominal per-core peak for the CPU backend (order-of-magnitude: ~8 f32
#: lanes × 2 FMA flops × ~1.25 GHz). CPU MFU exists so host-side drills and
#: CI produce a finite, run-to-run comparable number — the ``peak_source``
#: label says it is nominal, and DLS_PEAK_FLOPS calibrates it.
CPU_NOMINAL_PEAK_PER_CORE = 2.0e10

_SIG_LEAVES_SHOWN = 4  # leaves spelled out in the human-readable signature

#: newest compile events kept verbatim in the ``--anatomy`` report — a
#: recompile storm emits one per step, and the report must stay renderable
#: mid-incident (totals/rollups always cover everything).
MAX_LEDGER_EVENTS_REPORTED = 50


def resolve_peak_flops() -> tuple[float, str]:
    """(peak FLOPs/s per chip, source label) for the MFU denominator.

    Resolution order: ``DLS_PEAK_FLOPS`` env → the bf16 spec table in
    :mod:`..metrics` by device kind → a labeled nominal figure on the host
    CPU. An accelerator whose ``device_kind`` is not in the table raises
    (:func:`..metrics.spec_peak_flops`) — a default there would put a made-up
    denominator under a device metric.
    """
    from distributeddeeplearningspark_tpu.metrics import (
        env_peak_flops_override,
        spec_peak_flops,
    )

    v = env_peak_flops_override()
    if v is not None:
        return v, PEAK_FLOPS_ENV
    import jax

    d = jax.devices()[0]
    peak = spec_peak_flops(d)
    if peak:
        return peak, f"spec table ({d.device_kind})"
    cores = os.cpu_count() or 1
    return (cores * CPU_NOMINAL_PEAK_PER_CORE,
            f"nominal-cpu ({cores} cores; set {PEAK_FLOPS_ENV} to "
            f"calibrate)")


def _leaf_sig(x: Any) -> tuple[tuple[int, ...], str]:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        import numpy as np

        a = np.asarray(x)
        shape, dtype = a.shape, a.dtype
    return tuple(int(s) for s in shape), str(dtype)


_DTYPE_SHORT = {"float32": "f32", "float16": "f16", "bfloat16": "bf16",
                "float64": "f64", "int32": "i32", "int64": "i64",
                "int8": "i8", "uint8": "u8", "bool": "b1"}


def _human_sig(leaf_sigs: list[tuple[tuple[int, ...], str]]) -> str:
    parts = [f"{_DTYPE_SHORT.get(dt, dt)}[{','.join(map(str, sh))}]"
             for sh, dt in leaf_sigs[:_SIG_LEAVES_SHOWN]]
    extra = len(leaf_sigs) - _SIG_LEAVES_SHOWN
    return " ".join(parts) + (f" …+{extra} leaves" if extra > 0 else "")


class InstrumentedFunction:
    """Compile-ledger wrapper around a jitted callable (AOT dispatch).

    Owns the lower→compile path the wrapped ``jax.jit`` would otherwise
    hide: calls are dispatched on explicitly compiled executables keyed by
    the arguments' (structure, shape, dtype, sharding) signature, so every
    compile is an *observed event* — timed, cost-analyzed, emitted to
    telemetry (a ``compile`` event + a ``compile`` phase span for goodput)
    — instead of an anonymous first-call stall. Same-signature calls hit
    the executable dict; the compiled program set is exactly
    ``_cache_size()`` (the serve engine's ``compiled_batch_shapes`` pin).

    ``expected_signatures`` is the recompile contract: 1 for a shape-stable
    train step, the bucket-ladder length for a serve forward. A signature
    compiling twice, or the distinct count exceeding the expectation, flags
    the event ``recompile=True`` — the ``dlstatus --anatomy`` verdict and
    ``compile_summary()["flagged_recompiles"]`` read that flag.

    A failure of ``lower().compile()`` (a Mosaic rejection, a compile-time
    out-of-memory) propagates to the caller: retrying it through plain jit
    would pay it twice and report it from the wrong place. Only the typed
    AOT *dispatch* mismatch ("compiled for different types/shardings")
    degrades to calling the wrapped jit directly, with compiles still
    detected (jit-cache growth) and timed, minus the cost analysis
    (``aot: false`` on the ledger's events from then on).
    """

    def __init__(self, jitted: Callable, *, name: str,
                 expected_signatures: int = 1, clock=time.perf_counter,
                 plan=None):
        self._jitted = jitted
        self.name = name
        # originating compile Plan (parallel/plan.py — duck-typed: anything
        # with .name and .signature()): every ledger record and compile
        # phase span carries it, so `dlstatus --anatomy` rows and the
        # chrome_trace export attribute each compile to its layout
        self.plan_name = getattr(plan, "name", None) if plan is not None else None
        self.plan_sig = (plan.signature()
                         if plan is not None and hasattr(plan, "signature")
                         else None)
        self.expected_signatures = max(1, int(expected_signatures))
        self._clock = clock
        self._lock = threading.Lock()
        self._compiled: dict[Any, Any] = {}     # dispatch key → executable
        self._sig_compiles: dict[str, int] = {}  # sig_hash → compile count
        self.records: list[dict[str, Any]] = []  # ledger, oldest first
        self._anatomy: "StepAnatomy | None" = None
        self._aot = True
        #: newest executable's analytical FLOPs per call (global, XLA cost
        #: analysis — same convention/caveats as
        #: :func:`..metrics.compiled_flops_per_step`)
        self.flops_per_step: float | None = None
        self.bytes_per_step: float | None = None

    # -- wiring ---------------------------------------------------------------

    def attach_anatomy(self, anatomy: "StepAnatomy | None") -> None:
        """Route per-call dispatch/compile timings into a lap anatomy."""
        self._anatomy = anatomy

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def executables(self) -> list[tuple[tuple, Any]]:
        """(dispatch key, compiled executable) pairs, oldest first. The key
        is ``(treedef, (shape, dtype) per leaf, sharding per leaf)`` of the
        call that compiled it; the executable answers ``as_text()``."""
        with self._lock:
            return list(self._compiled.items())

    def _cache_size(self) -> int:
        """Compiled-executable count (AOT dict and/or inner jit cache)."""
        inner = 0
        try:
            inner = int(self._jitted._cache_size())
        except Exception:  # jit cache introspection is best-effort
            pass
        return max(len(self._compiled), inner)

    # -- signature ------------------------------------------------------------

    def _dispatch_key(self, args: tuple) -> tuple:
        """The per-call executable-dict key: (treedef, shape/dtype sigs,
        shardings). This runs on EVERY dispatch — the serving decode step
        pays it per token — so it is tuple-building only; the expensive
        rendering (str(treedef), blake2b, the human signature) happens
        once per compile in :meth:`_reported_sig`.

        The key includes per-leaf shardings (an AOT executable is
        layout-committed); the *reported* signature is shape/dtype only —
        a sharding flap recompiling the same shapes is exactly the event
        the ledger exists to expose, so both compiles share one sig hash
        and the second one flags."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(args)
        sigs = tuple(_leaf_sig(x) for x in leaves)
        shardings = []
        for x in leaves:
            s = getattr(x, "sharding", None)
            try:
                hash(s)
            except TypeError:
                s = str(s)
            shardings.append(s)
        return (treedef, sigs, tuple(shardings))

    @staticmethod
    def _reported_sig(key: tuple) -> tuple[str, str, int]:
        """(human sig, sig hash, nleaves) for one ledger record — the
        compile-miss-path half of :meth:`_dispatch_key`."""
        treedef, sigs = key[0], list(key[1])
        sig_hash = hashlib.blake2b(
            repr((str(treedef), sigs)).encode(), digest_size=8).hexdigest()
        return _human_sig(sigs), sig_hash, len(sigs)

    # -- ledger ---------------------------------------------------------------

    def _record_compile(self, sig: str, sig_hash: str, nleaves: int,
                        compile_s: float, *, lower_s: float | None = None,
                        compiled=None) -> dict:
        """One ledger record. ``lower_s`` is the part of ``compile_s`` spent
        tracing and lowering (``None`` on the jit fallback, which cannot
        tell it from the backend's part)."""
        flops = bytes_accessed = None
        mem_fields: dict[str, int] = {}
        if compiled is not None:
            try:
                cost = compiled.cost_analysis()
                flops = float(cost.get("flops", 0.0)) or None
                bytes_accessed = float(cost.get("bytes accessed", 0.0)) or None
            except Exception:  # cost analysis unsupported on some backends
                pass
            try:
                ma = compiled.memory_analysis()
                mem_fields = {
                    "argument_bytes": int(ma.argument_size_in_bytes),
                    "output_bytes": int(ma.output_size_in_bytes),
                    "temp_bytes": int(ma.temp_size_in_bytes),
                }
            except Exception:
                pass
        with self._lock:
            n = self._sig_compiles.get(sig_hash, 0) + 1
            self._sig_compiles[sig_hash] = n
            distinct = len(self._sig_compiles)
            recompile = n > 1 or distinct > self.expected_signatures
            rec = {
                "fn": self.name, "sig": sig, "sig_hash": sig_hash,
                "nleaves": nleaves, "compile_s": round(compile_s, 6),
                **({"lower_s": round(lower_s, 6),
                    "backend_s": round(compile_s - lower_s, 6)}
                   if lower_s is not None else {}),
                "flops": flops, "bytes_accessed": bytes_accessed,
                **mem_fields,
                **({"plan": self.plan_name, "plan_sig": self.plan_sig}
                   if self.plan_name else {}),
                "sig_compiles": n, "distinct_signatures": distinct,
                "expected_signatures": self.expected_signatures,
                "recompile": recompile, "aot": self._aot,
            }
            self.records.append(rec)
            if flops:
                self.flops_per_step = flops
            if bytes_accessed:
                self.bytes_per_step = bytes_accessed
        if recompile:
            logger.warning(
                "%s recompiled (signature %s seen %d time(s), %d distinct "
                "vs %d expected): %s", self.name, sig_hash, n, distinct,
                self.expected_signatures, sig)
        telemetry_lib.emit("compile", **rec)
        return rec

    def _compile(self, key: Any, args: tuple):
        """Lower + compile one signature, inside a ``compile`` phase span
        (goodput accounts the stall even mid-traffic)."""
        sig, sig_hash, nleaves = self._reported_sig(key)
        with telemetry_lib.phase(
                "compile", fn=self.name,
                **({"plan": self.plan_name} if self.plan_name else {})):
            # the ledger keeps the two times whether or not a lap anatomy
            # is attached, so it reads its own clock around each section
            t0 = self._clock()
            with spans.span("dls.step/lower", self._anatomy):
                lowered = self._jitted.lower(*args)
            t1 = self._clock()
            with spans.span("dls.step/compile", self._anatomy):
                compiled = lowered.compile()
            t2 = self._clock()
        self._record_compile(sig, sig_hash, nleaves, t2 - t0,
                             lower_s=t1 - t0, compiled=compiled)
        with self._lock:
            self._compiled[key] = compiled
        return compiled

    def prepare(self, *args) -> dict | None:
        """Compile for ``args``' signature without executing (returns the
        ledger record, or the existing one). Benches and
        ``Trainer.compiled_cost`` use this so "get the FLOPs" and "warm the
        executable" are ONE compile, not two."""
        if not self._aot:
            return self.records[-1] if self.records else None
        key = self._dispatch_key(args)
        with self._lock:
            have = key in self._compiled
        if not have:
            self._compile(key, args)
        sig_hash = self._reported_sig(key)[1]
        for rec in reversed(self.records):
            if rec["sig_hash"] == sig_hash:
                return rec
        return None

    # -- dispatch -------------------------------------------------------------

    def __call__(self, *args, **kwargs):
        if kwargs or not self._aot:
            return self._fallback_call(args, kwargs)
        try:
            key = self._dispatch_key(args)
            compiled = self._compiled.get(key)
        except Exception:  # unhashable/exotic args: let jit handle them
            return self._fallback_call(args, kwargs)
        if compiled is None:
            compiled = self._compile(key, args)
        try:
            with spans.span("dls.step/dispatch", self._anatomy):
                out = compiled(*args)
        except (TypeError, ValueError) as e:
            # the typed AOT mismatch errors ("compiled for different
            # types/shardings") mean our key missed a compile-relevant
            # property (weak types, committedness): degrade, don't die.
            # Anything else is a real runtime error — re-raise.
            if "compiled" not in str(e):
                raise
            logger.warning("%s: AOT dispatch rejected a call (%s) — "
                           "degrading to jit dispatch", self.name, e)
            self._aot = False
            return self._fallback_call(args, kwargs)
        return out

    def _fallback_call(self, args: tuple, kwargs: dict):
        """Plain jit dispatch with jit-cache-growth compile detection: the
        ledger stays populated (signature, timed first call) minus the cost
        analysis an AOT executable would carry."""
        pre = None
        try:
            pre = int(self._jitted._cache_size())
        except Exception:
            pass
        # which section this call was (a compile or a dispatch) is known
        # only afterwards, so the span is bare and the anatomy is told below
        t0 = self._clock()
        with spans.span("dls.step/dispatch"):
            out = self._jitted(*args, **kwargs)
        dt = self._clock() - t0
        grew = False
        if pre is not None:
            try:
                grew = int(self._jitted._cache_size()) > pre
            except Exception:
                pass
        if grew:
            try:
                sig, sig_hash, nleaves = self._reported_sig(
                    self._dispatch_key(args))
            except Exception:
                sig, sig_hash, nleaves = "?", "?", 0
            # the first call's wall-clock IS the compile span (trace +
            # XLA; the step's own execute is a rounding error next to it).
            # An end-only phase record reconstructs the interval for
            # goodput (t0 = ts - dur_s) without a retroactive begin.
            telemetry_lib.emit("phase", name="compile", edge="end",
                               dur_s=dt, fn=self.name,
                               **({"plan": self.plan_name}
                                  if self.plan_name else {}))
            self._record_compile(sig, sig_hash, nleaves, dt)
        if self._anatomy is not None:
            self._anatomy.add(
                "dls.step/compile" if grew else "dls.step/dispatch", dt)
        return out

    # -- summaries ------------------------------------------------------------

    def compile_summary(self) -> dict[str, Any]:
        """The wrapper-lifetime rollup (the benchmark's ``correct`` reads it)."""
        with self._lock:
            recs = list(self.records)
        return {
            "compiles": len(recs),
            "distinct_signatures": len({r["sig_hash"] for r in recs}),
            "flagged_recompiles": sum(bool(r["recompile"]) for r in recs),
            "total_compile_s": round(sum(r["compile_s"] for r in recs), 6),
            "total_lower_s": round(
                sum(r.get("lower_s", 0.0) for r in recs), 6),
            "total_backend_s": round(
                sum(r.get("backend_s", 0.0) for r in recs), 6),
            "flops_per_step": self.flops_per_step,
            "bytes_per_step": self.bytes_per_step,
            "aot": self._aot,
            **({"plan": self.plan_name, "plan_sig": self.plan_sig}
               if self.plan_name else {}),
        }


def instrument(jitted: Callable, *, name: str,
               expected_signatures: int = 1,
               plan=None) -> InstrumentedFunction:
    """Wrap a jitted callable in the compile ledger (see
    :class:`InstrumentedFunction`). Idempotent on already-wrapped inputs.

    ``plan``: the originating compile Plan (``parallel/plan.py``) —
    ledger records, compile phase spans, and the chrome_trace export then
    carry its name/signature."""
    if isinstance(jitted, InstrumentedFunction):
        return jitted
    return InstrumentedFunction(jitted, name=name,
                                expected_signatures=expected_signatures,
                                plan=plan)


# -- step anatomy -------------------------------------------------------------


class StepAnatomy:
    """Per-lap wall-clock split of the loop thread into named sections.

    The loop thread's sink of :func:`~.spans.span`: the instrumented step
    adds each dispatch (``dls.step/dispatch``) and each in-lap compile
    (``dls.step/lower``, ``dls.step/compile``), the trainer the lap-boundary
    ``device_get`` (``dls.fit/sync``), its emits, callbacks, checkpoint
    saves and evals (``dls.fit/*``), and closes the lap with :meth:`lap`.
    A section's own time is counted, without the sections nested in it.
    Attribution model (async dispatch):

    - ``device_s`` = dispatch + drain — the host time *surrendered to the
      device*: enqueue cost plus the boundary block where the host stood
      waiting for the step's results. On an async backend this is the
      honest wall-clock the device cost the loop (overlapped device work
      the host never waited on costs nothing, correctly).
    - ``compile_in_lap_s`` — ledger compiles that landed inside the lap
      (they are their own goodput category).
    - input wait and ``put`` stay the starvation probe's numbers (they ride
      the same ``step_metrics`` record) and are handed to :meth:`lap`.
    - ``host_s`` — the residual of the lap's wall after device, compile and
      input wait, as it always was. ``emit_s``, ``callbacks_s``,
      ``checkpoint_s``, ``eval_s`` and ``input_put_s`` are the named parts
      of it; ``unaccounted_s`` is what no section covers (the loop's own
      Python between them).

    The sections and ``unaccounted_s`` tile the lap by construction; the CI
    smoke checks the wall against the *independently measured* ``Meter`` lap
    time (two different clock paths must agree within 5%).
    """

    #: this sink's counters, in the order ``step_metrics`` carries them
    _KEYS = ("device_dispatch_s", "device_drain_s", "compile_in_lap_s",
             "emit_s", "callbacks_s", "checkpoint_s", "eval_s")

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        """Restart the current lap's clock and counters — called at the
        same instant the Meter starts, so the two independently measured
        walls cover the same window (the CI smoke pins them within 5%)."""
        with self._lock:
            self._lap_t0 = self.clock()
            self._seconds = dict.fromkeys(self._KEYS, 0.0)

    def add(self, name: str, dt: float, inner_s: float = 0.0) -> None:
        """One closed section (:func:`~.spans.span`'s sink side): its own
        time, without the ``inner_s`` of sections nested in it."""
        key = spans.COUNTERS[name]
        with self._lock:
            self._seconds[key] += dt - inner_s

    def now(self) -> float:
        """The anatomy clock (pass to :meth:`lap` as its close timestamp
        when work — e.g. the starvation-probe snapshot — must run between
        the true lap boundary and the lap() call)."""
        return self.clock()

    def lap(self, *, steps: int, input_wait_s: float = 0.0,
            input_put_s: float = 0.0,
            flops_per_step: float | None = None,
            num_chips: int = 1, now: float | None = None) -> dict[str, Any]:
        """Close the current lap; returns the gauge dict the trainer merges
        into the lap's ``step_metrics`` record. ``now`` pins the lap's
        close timestamp to the true sync boundary (default: the call)."""
        if now is None:
            now = self.clock()
        with self._lock:
            wall = max(0.0, now - self._lap_t0)
            sec = self._seconds
            self._lap_t0 = now
            self._seconds = dict.fromkeys(self._KEYS, 0.0)
        device = sec["device_dispatch_s"] + sec["device_drain_s"]
        feed = float(input_wait_s or 0.0)
        host = max(0.0, wall - device - sec["compile_in_lap_s"] - feed)
        rec: dict[str, Any] = {
            "anatomy_wall_s": round(wall, 6),
            "device_s": round(device, 6),
            "host_s": round(host, 6),
            **{k: round(v, 6) for k, v in sec.items()},
            # signed: a section still open at the boundary closes into the
            # next lap, so the two laps' figures cancel
            "unaccounted_s": round(
                wall - sum(sec.values()) - feed - float(input_put_s or 0.0),
                6),
            "num_chips": int(num_chips),
        }
        peak, source = resolve_peak_flops()
        rec["peak_flops_per_chip"] = peak
        rec["peak_source"] = source
        if flops_per_step:
            rec["flops_per_step"] = float(flops_per_step)
            if peak and wall > 0 and steps > 0:
                per_chip = flops_per_step * steps / wall / max(1, num_chips)
                rec["mfu"] = round(per_chip / peak, 6)
                if device > 0:
                    rec["mfu_device"] = round(
                        flops_per_step * steps / device / max(1, num_chips)
                        / peak, 6)
        return rec


# -- the start of the process -------------------------------------------------


class StartupLedger:
    """The start of a process, from the package's import to the close of its
    first lap, split into named sections.

    The ``dls.start/*`` sink of :func:`~.spans.span`, with
    :class:`StepAnatomy`'s contract: a section's own time is counted, without
    the sections nested in it, so the sections tile the time they cover. The
    first lap's own split is not measured twice: :meth:`first_lap` takes it
    from that lap's :class:`StepAnatomy` record, the feed's snapshot and the
    step's compile records (all of them inside ``dls.start/fit``, whose own
    time is what is left over), and closes the ledger with the one
    ``startup`` record:

    - every ``dls.start/*`` counter of :data:`.spans.COUNTERS`, in seconds;
    - ``first_lower_s`` / ``first_backend_s`` (the train step's compiles up
      to that lap: tracing and lowering, XLA or the cache load),
      ``first_batch_s`` (the lap's ``input_wait_s`` + ``input_put_s``: the
      feed's threads start, the first assembly, the first put),
      ``first_dispatch_s`` and ``first_drain_s``;
    - ``caller_s``: the caller's own code, which is the time between the
      program's outer sections and the first lap's ``dls.fit/callbacks``;
    - ``to_first_lap_s`` (anchor to the lap's close), the ``steps`` of that
      lap and the ``attempt``.

    The counters and ``caller_s`` sum to ``to_first_lap_s`` by construction:
    ``caller_s`` is the residual, so a fault of the accounting shows as a
    ``caller_s`` or a ``fit_unaccounted_s`` below zero. A ``fit`` without a
    telemetry writer has no lap record and no feed snapshot to hand over
    (it builds no accumulator): its ``first_batch_s``, ``first_dispatch_s``
    and ``first_drain_s`` read 0 and that time, with the callbacks', stays
    in ``fit_unaccounted_s``; the compile records exist either way. From
    then on :meth:`sink` is ``None`` (a later ``Trainer`` or ``fit`` is
    a bare span) and :meth:`add` adds nothing. No jax import.
    """

    def __init__(self, clock=time.perf_counter, t0: float | None = None):
        self.clock = clock
        self._t0 = clock() if t0 is None else t0
        self._lock = threading.Lock()
        self._seconds = {key: 0.0 for name, key in spans.COUNTERS.items()
                         if name.startswith(spans.START_PREFIX)}
        self._record: dict[str, Any] | None = None

    def sink(self) -> "StartupLedger | None":
        """This ledger while the start is open, ``None`` once it is on
        record: what the ``dls.start/*`` sections pass to ``span``."""
        return self if self._record is None else None

    def add(self, name: str, dt: float, inner_s: float = 0.0) -> None:
        """One closed section (:func:`~.spans.span`'s sink side): its own
        time, without the ``inner_s`` of sections nested in it."""
        key = spans.COUNTERS[name]
        with self._lock:
            if self._record is None:
                self._seconds[key] += dt - inner_s

    def first_lap(self, *, steps: int, lap: dict[str, Any],
                  feed: dict[str, Any], compiles: Iterable[dict],
                  attempt: int = 0, now: float | None = None
                  ) -> dict[str, Any]:
        """Close the start at the first lap's close and return the record.

        ``lap`` is that lap's :meth:`StepAnatomy.lap` record, ``feed`` the
        probe's snapshot of it, ``compiles`` the compile-ledger records of
        the train step up to it; ``now`` pins the close to the true sync
        boundary (default: the call). ``dls.start/fit`` has been left."""
        if now is None:
            now = self.clock()
        compiles = list(compiles)
        first = {
            "first_lower_s": sum(c.get("lower_s", 0.0) for c in compiles),
            # the jit fallback cannot tell its lowering from the backend's part
            "first_backend_s": sum(c.get("backend_s", c.get("compile_s", 0.0))
                                   for c in compiles),
            "first_batch_s": (float(feed.get("input_wait_s", 0.0) or 0.0)
                              + float(feed.get("input_put_s", 0.0) or 0.0)),
            "first_dispatch_s": float(lap.get("device_dispatch_s", 0.0)),
            "first_drain_s": float(lap.get("device_drain_s", 0.0)),
        }
        with self._lock:
            if self._record is not None:
                return dict(self._record)
            rec: dict[str, Any] = {**self._seconds, **first}
            # all of it ran inside dls.start/fit; the callbacks are the
            # caller's, and the residual below hands them to caller_s
            rec["fit_unaccounted_s"] -= (sum(first.values())
                                         + float(lap.get("callbacks_s", 0.0)))
            to_first_lap_s = now - self._t0
            rec["caller_s"] = to_first_lap_s - sum(rec.values())
            rec.update(to_first_lap_s=to_first_lap_s, steps=int(steps),
                       attempt=int(attempt))
            self._record = rec
            return dict(rec)

    def summary(self) -> dict[str, Any] | None:
        """The ``startup`` record, or ``None`` while the start is open."""
        with self._lock:
            return None if self._record is None else dict(self._record)


#: the process's one ledger, anchored at the first line the package ran
STARTUP = StartupLedger(t0=_T_IMPORT)


# -- HBM watermarks -----------------------------------------------------------


def memory_watermarks() -> dict[str, Any]:
    """Device memory gauges for one ``memory`` event.

    Uses each local device's ``memory_stats()`` where the backend exposes
    it (TPU/GPU: ``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit``
    — aggregated as max in-use / max peak / min limit, the conservative
    per-chip view); falls back to the live-buffer byte total
    (``jax.live_arrays()``) on backends without allocator stats (CPU), so
    the watermark trendline exists everywhere even if its ceiling doesn't.

    The TPU allocator counts a running program's temporaries as
    ``peak_bytes_reserved``, apart from the arrays "in use" (BERT-base at 32
    a chip: 1.57 + 6.30 GB), so what a chip held at most is the two
    together: ``headroom_bytes`` is the limit less the largest such sum.
    """
    import jax

    devs = jax.local_devices()
    in_use: list[int] = []
    peaks: list[int] = []
    reserved: list[int] = []
    held: list[int] = []  # per device: the most it held, temporaries too
    limits: list[int] = []
    for d in devs:
        try:
            s = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — stats are best-effort gauges
            s = {}
        if s.get("bytes_in_use") is not None:
            in_use.append(int(s["bytes_in_use"]))
            held.append(int(s.get("peak_bytes_in_use") or s["bytes_in_use"])
                        + int(s.get("peak_bytes_reserved") or 0))
        if s.get("peak_bytes_in_use") is not None:
            peaks.append(int(s["peak_bytes_in_use"]))
        if s.get("peak_bytes_reserved") is not None:
            reserved.append(int(s["peak_bytes_reserved"]))
        if s.get("bytes_limit") is not None:
            limits.append(int(s["bytes_limit"]))
    if in_use:
        rec: dict[str, Any] = {"source": "memory_stats",
                               "devices": len(devs),
                               "bytes_in_use_max": max(in_use)}
        if peaks:
            rec["peak_bytes_in_use_max"] = max(peaks)
        if reserved:
            rec["peak_bytes_reserved_max"] = max(reserved)
        if limits:
            rec["bytes_limit_min"] = min(limits)
            rec["headroom_bytes"] = min(limits) - max(held)
        return rec
    try:
        live = sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays())
    except Exception:  # noqa: BLE001
        live = 0
    return {"source": "live-buffers", "devices": len(devs),
            "live_bytes": int(live)}


# -- reader (jax-free fold for dlstatus --anatomy) ----------------------------


#: the named parts of ``host_s`` a lap carries, as ``dlstatus --anatomy``
#: prints them on one line
LOOP_SPLIT_KEYS = ("input_put_s", "emit_s", "callbacks_s", "checkpoint_s",
                   "eval_s", "unaccounted_s")


def _steps_fold(laps: list[dict]) -> dict[str, Any]:
    out = {"laps": len(laps),
           "steps": sum(int(e.get("steps", 0) or 0) for e in laps)}
    for key, src in (("wall_s", "anatomy_wall_s"), ("device_s", "device_s"),
                     ("device_dispatch_s", "device_dispatch_s"),
                     ("device_drain_s", "device_drain_s"),
                     ("host_s", "host_s"), ("compile_s", "compile_in_lap_s"),
                     ("input_wait_s", "input_wait_s"),
                     # the named parts of host_s (absent from older streams)
                     *((k, k) for k in LOOP_SPLIT_KEYS)):
        out[key] = round(sum(float(e.get(src, 0.0) or 0.0) for e in laps), 6)
    wall = out["wall_s"]
    covered = (out["device_s"] + out["host_s"] + out["compile_s"]
               + out["input_wait_s"])
    out["coverage"] = round(covered / wall, 4) if wall > 0 else None
    out["fractions"] = {
        k: (round(out[f"{k}_s"] / wall, 4) if wall > 0 else None)
        for k in ("device", "host", "compile", "input_wait")}
    return out


def _mfu_fold(laps: list[dict]) -> dict[str, Any]:
    peak = source = chips = None
    for e in reversed(laps):
        if e.get("peak_flops_per_chip"):
            peak = float(e["peak_flops_per_chip"])
            source = e.get("peak_source")
            chips = int(e.get("num_chips", 1) or 1)
            break
    flops_laps = [e for e in laps
                  if e.get("flops_per_step") and e.get("steps")]
    total_flops = sum(float(e["flops_per_step"]) * int(e["steps"])
                      for e in flops_laps)
    total_wall = sum(float(e.get("anatomy_wall_s", 0.0) or 0.0)
                     for e in flops_laps)
    mfu = None
    if peak and chips and total_flops > 0 and total_wall > 0:
        mfu = round(total_flops / total_wall / chips / peak, 6)
    last = next((e.get("mfu") for e in reversed(laps)
                 if e.get("mfu") is not None), None)
    newest_flops = next((float(e["flops_per_step"]) for e in reversed(laps)
                         if e.get("flops_per_step")), None)
    return {"mfu": mfu, "mfu_last_lap": last,
            "flops_per_step": newest_flops,
            "peak_flops_per_chip": peak, "peak_source": source,
            "num_chips": chips}


def _memory_fold(mems: list[dict]) -> dict[str, Any] | None:
    if not mems:
        return None
    newest_by_proc: dict[Any, dict] = {}
    for e in mems:
        newest_by_proc[e.get("process")] = e
    rows = list(newest_by_proc.values())
    stats = [e for e in rows if e.get("source") == "memory_stats"]
    if stats:
        in_use = max(int(e.get("bytes_in_use_max", 0) or 0) for e in stats)
        peaks = [int(e["peak_bytes_in_use_max"]) for e in stats
                 if e.get("peak_bytes_in_use_max") is not None]
        limits = [int(e["bytes_limit_min"]) for e in stats
                  if e.get("bytes_limit_min") is not None]
        out: dict[str, Any] = {"source": "memory_stats",
                               "bytes_in_use_max": in_use}
        if peaks:
            out["peak_bytes_in_use_max"] = max(peaks)
        reserved = [int(e["peak_bytes_reserved_max"]) for e in stats
                    if e.get("peak_bytes_reserved_max") is not None]
        if reserved:
            out["peak_bytes_reserved_max"] = max(reserved)
        if limits:
            out["bytes_limit_min"] = min(limits)
            # each process worked its own headroom out per device, reserved
            # bytes included; a stream from before that key has peaks only
            headrooms = [int(e["headroom_bytes"]) for e in stats
                         if e.get("headroom_bytes") is not None]
            out["headroom_bytes"] = (
                min(headrooms) if headrooms
                else min(limits) - max(peaks or [in_use]))
        return out
    live = max(int(e.get("live_bytes", 0) or 0) for e in rows)
    return {"source": "live-buffers", "live_bytes": live}


def anatomy_report(events: Iterable[dict]) -> dict[str, Any] | None:
    """Fold a stream into the ``dlstatus --anatomy`` report (jax-free).

    None when the run carries no anatomy evidence (no ``compile`` /
    ``memory`` events and no anatomy-stamped ``step_metrics``)."""
    events = list(events)
    compiles = [e for e in events if e.get("kind") == "compile"]
    laps = [e for e in events if e.get("kind") == "step_metrics"
            and e.get("anatomy_wall_s") is not None]
    mems = [e for e in events if e.get("kind") == "memory"]
    if not (compiles or laps or mems):
        return None

    flagged = [e for e in compiles if e.get("recompile")]
    sig_seen: dict[tuple, int] = {}
    for e in compiles:
        k = (e.get("fn"), e.get("sig_hash"))
        sig_seen[k] = sig_seen.get(k, 0) + 1
    duplicates = sum(1 for n in sig_seen.values() if n > 1)
    by_fn: dict[str, dict] = {}
    for e in compiles:
        fn = str(e.get("fn"))
        row = by_fn.setdefault(fn, {
            "compiles": 0, "signatures": set(), "flagged_recompiles": 0,
            "compile_s": 0.0, "flops": None, "bytes_accessed": None,
            "plan": None, "plan_sig": None})
        row["compiles"] += 1
        row["signatures"].add(e.get("sig_hash"))
        row["flagged_recompiles"] += bool(e.get("recompile"))
        row["compile_s"] += float(e.get("compile_s", 0.0) or 0.0)
        if e.get("flops"):
            row["flops"] = float(e["flops"])
        if e.get("bytes_accessed"):
            row["bytes_accessed"] = float(e["bytes_accessed"])
        if e.get("plan"):
            row["plan"] = e["plan"]
            row["plan_sig"] = e.get("plan_sig")
    for row in by_fn.values():
        row["signatures"] = len(row["signatures"])
        row["compile_s"] = round(row["compile_s"], 6)
    ledger = {
        "compiles": len(compiles),
        "distinct_signatures": len(sig_seen),
        "flagged_recompiles": len(flagged),
        "duplicate_signatures": duplicates,
        "total_compile_s": round(
            sum(float(e.get("compile_s", 0.0) or 0.0) for e in compiles), 6),
        "by_fn": by_fn,
        # newest-N only: a recompile STORM — the very case this report
        # diagnoses — produces one event per step for hours, and a
        # --watch tick must not serialize megabytes of them (the by_fn
        # rollup and the counters above carry the totals)
        "events": [
            {k: e.get(k) for k in
             ("ts", "process", "fn", "sig", "sig_hash", "compile_s",
              "flops", "bytes_accessed", "plan", "plan_sig", "recompile",
              "aot")}
            for e in compiles[-MAX_LEDGER_EVENTS_REPORTED:]],
        "events_omitted": max(0, len(compiles) - MAX_LEDGER_EVENTS_REPORTED),
    }

    per_process: dict[str, dict] = {}
    for e in laps:
        per_process.setdefault(str(e.get("process")), []).append(e)
    steps = _steps_fold(laps) if laps else None
    mfu = _mfu_fold(laps) if laps else None

    if flagged:
        worst = flagged[-1]
        recompile_verdict = (
            f"RECOMPILES — {len(flagged)} flagged compile(s) (e.g. "
            f"{worst.get('fn')} {worst.get('sig')}): the compile set is "
            f"not pinned; expect multi-second stalls mid-run")
    elif compiles:
        recompile_verdict = "OK — every signature compiled exactly once"
        if duplicates:
            recompile_verdict = (
                f"OK within each process; {duplicates} signature(s) "
                f"re-paid across attempts/processes (restarts re-pay jit "
                f"— see compile_s in goodput)")
    else:
        recompile_verdict = "no compiles recorded"

    bound_verdict = None
    if steps and steps["wall_s"] > 0:
        fr = steps["fractions"]
        ranked = sorted(
            ((fr.get(k) or 0.0), k)
            for k in ("device", "host", "input_wait", "compile"))
        top_frac, top = ranked[-1]
        label = {"device": "device-bound", "host": "host-bound",
                 "input_wait": "input-bound", "compile": "compile-bound"}[top]
        bound_verdict = (f"{label} — {100.0 * top_frac:.0f}% of lap "
                         f"wall-clock in {top.replace('_', '-')}")

    return {
        "compile_ledger": ledger,
        "steps": steps,
        "mfu": mfu,
        "memory": _memory_fold(mems),
        "per_process": {p: _steps_fold(ls)
                        for p, ls in sorted(per_process.items())},
        "verdicts": {"recompile": recompile_verdict, "bound": bound_verdict},
    }
