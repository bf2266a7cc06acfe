"""Run telemetry — a durable, typed JSONL event stream per run.

The reference's Spark UI leaves a per-stage account of where a job's time
went that survives the job; the rebuild's equivalents were fragmented —
``Meter`` laps lived in process memory, recovery events went to stderr, and
the supervisor's attempt history evaporated with the process. This module is
the single durable artifact: every process appends typed, timestamped
records to ``<workdir>/telemetry/events-<process>.jsonl`` and everything
downstream (the goodput accountant here, the ``dlstatus`` inspector in
:mod:`.status`) is a pure fold over those files — it works on a crashed
run's partial stream exactly as on a finished one.

Event kinds (one JSON object per line, ``ts``/``kind``/``process`` always
present):

- ``step_metrics`` — one metrics lap: ``step``, ``steps`` (in the lap),
  ``lap_s``, ``metrics`` (the device metrics), plus the input-starvation
  probe's snapshot (``input_wait_s``, ``input_put_s``, ``input_stack_s``,
  ``input_blocked_s``, ``prefetch_depth_min``, ...) and the loop's named
  sections (``emit_s``, ``callbacks_s``, ``unaccounted_s``, ...; the one
  list of section names is :data:`.spans.COUNTERS`).
- ``phase`` — ``name`` + ``edge`` ("begin"/"end"; end carries ``dur_s``).
  Phase names the goodput accountant treats as overhead: ``compile``,
  ``restore``, ``checkpoint``/``checkpoint-wait``/``checkpoint-verify``,
  ``eval``. Other names (``run``, ``manifest``, ``profile-trace``) are
  informational.
- ``recovery`` — a recovery action fired: ``event`` ("skip", "rollback",
  "restart", "restore-fallback", "geometry_change", "graceful_shutdown",
  "reshard", ...) plus free-form evidence fields. ``geometry_change`` is
  the supervisor's elastic shrink (``dead_host``, ``evidence_attempts``,
  ``from_processes``/``to_processes``, surviving ``hosts``,
  ``batch_policy``; ``step`` is where the survivors resume and ``resume``
  says how — "checkpoint" walk-back or "live-handoff" continuation);
  ``graceful_shutdown`` is a drained preemption exit (``dead_host``,
  ``ordinal``, ``step`` = the drain step — no backoff slot burned);
  ``reshard`` is one state move across layouts. Every reshard carries
  ``transport`` ("checkpoint" = restore-time re-projection, "collectives"
  = live all-to-all between steps, "handoff" = ingest of a drained
  host's persisted live state) and ``walk_back`` (True only for the
  checkpoint path — the run rewound to a saved step). Live paths add the
  engine's measured evidence: ``bytes_moved``, ``rounds``,
  ``peak_inflight_bytes``, ``mem_budget_mb``, ``wall_s``,
  ``leaves_moved``, ``verified``; the checkpoint path keeps its
  topology record (``from_mesh``/``to_mesh``, ``from_devices``/
  ``to_devices``, ``from_processes``/``to_processes``).
- ``attempt`` — supervisor gang lifecycle: ``edge`` ("begin"/"end"/
  "backoff"), ``ordinal``, ``num_processes`` (+ ``hosts``, the surviving
  original host ordinals, on begin), and on end ``returncodes``/
  ``classification``/``duration_s`` (+ ``dead_host`` when the failure
  unambiguously names one).
- ``heartbeat`` — liveness stamp (``step``), the telemetry twin of the
  supervisor's ``DLS_HEARTBEAT_FILE`` mtime. The writer auto-enriches it
  with the innermost open ``phase`` so a stalled host is localizable from
  its last event alone (:mod:`.fleet`).
- ``collective`` — an opt-in comms probe sample (``op``, ``wait_s``) from
  :mod:`..parallel.collectives`; feeds the fleet table's comms-wait column.
- ``request`` — one served inference request (:mod:`..serve`): ``engine``,
  ``outcome`` ("ok"/"shed"/"error"), and for ok ``queue_wait_s``,
  ``infer_s``, ``latency_s``, ``batch_size`` (continuous decode adds
  ``prefix_hit``/``prefix_tokens``; router tenant sheds add ``tenant``).
  ``dlstatus`` folds these into the p50/p99 serving rollup; they never
  enter goodput accounting (serving wall-clock is not training overhead).
- ``serve`` — a serving-state gauge (:mod:`..serve.generate`): KV page
  occupancy, prefix-cache hit rate, active slots, queue depth. The
  newest one per process is a replica's "now" in ``dlstatus
  --fleet-serve`` (:func:`.fleet.serving_fleet`).
- ``shuffle`` — one distributed-exchange gauge (:mod:`..data.exchange`;
  the device agg path emits the same shape): ``edge="spill"`` marks one
  reducer spill (``reducer``/``bucket``/``rows``/``bytes``),
  ``edge="done"`` the whole-shuffle summary (``op``, ``workers``,
  ``buckets``, ``pairs_in``, ``rows_out``, ``bytes_moved``, ``spills``,
  ``overflow``, ``map_s``, ``merge_s``, ``bucket_rows``, plus the
  per-format split: ``transport`` (``tuple``/``columnar``/``mixed``/
  ``device``), ``columnar_pairs``/``columnar_bytes``/
  ``tuple_pairs``/``tuple_bytes`` summing to the totals, and
  ``columnar_buckets``/``tuple_buckets`` — how each non-empty bucket
  finalized). The shuffle's map/merge wall-clock additionally lands
  as ``shuffle-map``/``shuffle-merge`` ``phase`` spans (informational —
  not goodput overhead: a shuffle IS the productive work of an ETL step),
  which lower into the span model like any phase. ``dlstatus`` renders
  the newest summaries as the shuffle block (bytes moved, spill count,
  per-format rows, per-bucket skew, slowest-bucket verdict).
- ``compile`` — one executable built by the compile ledger
  (:mod:`.anatomy`): ``fn`` (the instrumented callable), ``sig`` /
  ``sig_hash`` (shape/dtype signature), ``compile_s`` (= ``lower_s``,
  tracing and lowering, + ``backend_s``, XLA or the cache load), ``flops`` /
  ``bytes_accessed`` (XLA cost analysis), ``argument_bytes`` /
  ``output_bytes`` / ``temp_bytes`` (memory analysis), and ``recompile``
  — True when the signature compiled more than once or the distinct-
  signature count exceeded the wrapper's pinned expectation (1 for a
  train step, the bucket ladder for the serve forwards). Every compile
  additionally spans a ``compile`` *phase* so goodput accounts the
  stall. ``dlstatus --anatomy`` renders the ledger and its recompile
  verdict.
- ``startup`` — ONCE a process, when the first lap of its first ``fit``
  closes (:class:`.anatomy.StartupLedger`): the start in seconds, from the
  package's import to that boundary. The ``dls.start/*`` sections' own
  time (``import_s``, ``session_s``, ``backend_s``, ``sample_s``,
  ``init_state_s``, ``fit_unaccounted_s``), the first lap's parts from
  that lap's own records (``first_lower_s``, ``first_backend_s``,
  ``first_batch_s``, ``first_dispatch_s``, ``first_drain_s``) and
  ``caller_s`` (the caller's own code) sum to ``to_first_lap_s``;
  ``steps`` of that lap and ``attempt`` ride along.
- ``memory`` — a device-memory watermark sample (:mod:`.anatomy`), one
  per metrics lap: ``bytes_in_use_max`` / ``peak_bytes_in_use_max`` /
  ``peak_bytes_reserved_max`` / ``bytes_limit_min`` / ``headroom_bytes``
  (the limit less in-use peak plus reserved peak, per device) from jax
  device ``memory_stats()`` where the backend exposes them
  (``source="memory_stats"``), or the live-buffer byte total
  (``source="live-buffers"``, CPU fallback). The Chrome exporter draws
  these as a counter track.
- ``span`` — one closed span of a request-level distributed trace
  (:mod:`.trace`): ``trace_id``/``span_id``/``parent_id``/``name``/
  ``t0``/``t1`` + free-form ``attrs``. Spans are buffered per request and
  appended with :meth:`EventWriter.emit_many` at completion (ONE flush per
  request, so the serve hot loop stays cheap); a crash mid-request leaves
  a partial trace the reader flags ``incomplete``, never throws on.
  ``dlstatus --traces`` folds them into the latency anatomy, ``dlstatus
  --export-trace`` exports them (plus train ``phase`` spans lowered into
  the same model) as Chrome ``trace_event`` JSON. MPMD pipeline stages
  (:mod:`..train.pipeline_trainer`) emit the same kind: per-step
  ``pipe-step``/``pipe-fwd``/``pipe-bwd``/``pipe-*-wait`` spans (attrs
  ``stage``/``step``/``mb``) plus one cross-process trace per microbatch
  whose context rides the transport frames — folded by
  :func:`.fleet.pipeline_anatomy` into the measured bubble fraction.
- ``alert`` — one health-rule state *transition* from the continuous
  health engine (:mod:`.health`): ``edge`` ("raise"/"clear"), ``rule``
  (which rule fired), ``key`` (the dedup identity, e.g. ``slo:tenant0``
  or ``hang:host2`` — one live alert per key, re-evaluations of an
  already-raised state emit nothing), ``severity`` ("WARN"/"CRIT"; a
  clear carries ``cleared_from``), ``summary`` (one operator-facing
  line), ``evidence`` (the rule's measured inputs at the edge), and
  ``held`` (evaluations the new state was held before the edge emitted
  — the flap-damping receipt). Alert edges + ``recovery`` events are
  the incident timeline ``dlstatus --incidents`` renders; the Chrome
  exporter draws them as instant events on an ``alerts`` row.
- ``sched`` — one cluster-scheduler lifecycle edge (:mod:`..scheduler`):
  ``edge`` ("submit"/"place"/"launch"/"preempt"/"shrink"/"requeue"/
  "complete"/"fail"/"cancel"), ``job`` (the ledger job id), ``tenant``/
  ``priority``, and per-edge evidence (``assignment`` host map on place,
  ``mode``/``victim_of``/``ordinal`` on preempt, ``reason`` on requeue,
  ``rc`` on complete/fail). The scheduler writes its own stream under
  ``<root>/sched`` and mirrors the edges that concern a job (place,
  preempt, requeue) into that job's workdir stream — so ``dlstatus
  <workdir> --incidents`` folds them into the job's timeline and the
  Chrome exporter draws them beside alert edges on the ``alerts`` row.

Worker-side events additionally carry ``host`` (the process index from the
``DLS_*`` env contract via :func:`~..utils.env.process_identity`, plus
``hosts`` when the gang has more than one) so the cross-host aggregator in
:mod:`.fleet` can attribute a multi-host run's streams without parsing file
names. Non-host processes (the supervisor) write with
``host=None`` and stay out of the fleet table.

Writers are append-only and line-buffered; a SIGKILL can at worst tear the
final line, which readers skip. No jax import here — the reader side must
stay cheap enough for a CLI pointed at a run directory.
"""

from __future__ import annotations

import contextlib
import glob
import json
import logging
import math
import os
import threading
import time
from typing import Any, Iterable

from distributeddeeplearningspark_tpu.telemetry import spans

logger = logging.getLogger("distributeddeeplearningspark_tpu.telemetry")

#: Subdirectory of the workdir holding the per-process event files.
TELEMETRY_DIRNAME = "telemetry"

#: Env var carrying the run's workdir to every process (the supervisor
#: exports it; a bare `Trainer` falls back to its checkpointer directory).
WORKDIR_ENV = "DLS_TELEMETRY_DIR"

#: Env var capping one process's event file size in MB: past it the writer
#: rotates to ``events-<process>.<n>.jsonl`` segments (the reader merges
#: them transparently). Unset/invalid = unbounded (the training default —
#: runs are finite; long-lived serving fleets should cap).
MAX_MB_ENV = "DLS_TELEMETRY_MAX_MB"

#: Env var naming the tenant a run/fleet belongs to. When set (``dlsubmit
#: --tenant`` exports it; the supervisor and serve fleet pass their env to
#: children), every writer stamps ``tenant`` on its records — the attribution
#: key ``dlstatus --cluster`` and the multi-tenant scheduler fold on. An
#: explicit per-record ``tenant`` field (router tenant sheds, per-client
#: serving tenants) always wins over the env-level stamp.
TENANT_ENV = "DLS_TENANT"

#: Env var naming the run's scheduling priority (an integer; higher wins).
#: ``dlsubmit --priority`` exports it and the scheduler stamps it on every
#: job it launches; like the tenant stamp, every writer then carries
#: ``priority`` on its records so cluster views can attribute preemption
#: decisions without joining back to the ledger. An explicit per-record
#: ``priority`` always wins over the env-level stamp.
PRIORITY_ENV = "DLS_PRIORITY"


def _priority_from_env() -> int | None:
    raw = os.environ.get(PRIORITY_ENV)
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        logger.warning("ignoring malformed %s=%r", PRIORITY_ENV, raw)
        return None


def _max_bytes_from_env() -> int | None:
    raw = os.environ.get(MAX_MB_ENV)
    if not raw:
        return None
    try:
        mb = float(raw)
    except ValueError:
        logger.warning("ignoring malformed %s=%r", MAX_MB_ENV, raw)
        return None
    return int(mb * 1024 * 1024) if mb > 0 else None

#: phase name -> goodput component it is accounted under. Blocking spans
#: only: async background work (orbax writes, manifest CRC threads) must
#: NOT be listed here — it overlaps training and steals no step time.
PHASE_CATEGORY = {
    "compile": "compile_s",
    "restore": "restore_s",
    "checkpoint": "checkpoint_s",
    "checkpoint-wait": "checkpoint_s",
    "checkpoint-verify": "checkpoint_s",
    "eval": "eval_s",
}

_INTERVAL_COMPONENTS = ("compile_s", "restore_s", "checkpoint_s", "eval_s",
                        "restart_overhead_s", "idle_s")

#: Every goodput component, in display order — the ONE list dlstatus renders
#: and the acceptance tests sum ("components sum to wall-clock"). Extending
#: PHASE_CATEGORY with a new overhead category means extending this too.
GOODPUT_COMPONENTS = ("productive_s", "compile_s", "restore_s",
                      "checkpoint_s", "eval_s", "input_starved_s",
                      "restart_overhead_s", "idle_s")


def _default_process() -> str:
    """``p<rank>`` from the supervisor's env contract (``DLS_PROCESS_ID``);
    a plain single-process run is p0."""
    return f"p{os.environ.get('DLS_PROCESS_ID', '0')}"


def telemetry_dir(workdir: str | os.PathLike) -> str:
    """The events directory for ``workdir`` (which may BE the events dir —
    ``dlstatus <workdir>`` and ``dlstatus <workdir>/telemetry`` both work)."""
    workdir = os.fspath(workdir)
    sub = os.path.join(workdir, TELEMETRY_DIRNAME)
    if os.path.isdir(sub):
        return sub
    if os.path.basename(os.path.normpath(workdir)) == TELEMETRY_DIRNAME:
        return workdir
    if glob.glob(os.path.join(workdir, "events-*.jsonl")):
        return workdir
    return sub


class EventWriter:
    """Appends typed events to ``<workdir>/telemetry/events-<process>.jsonl``.

    Best-effort by design: a full disk or read-only filesystem downgrades
    telemetry to a one-time warning, never a training failure. ``clock`` is
    injectable (epoch seconds) so accounting tests run on a fake clock.
    """

    _HOST_FROM_ENV = object()  # sentinel: resolve host identity from DLS_*

    def __init__(self, workdir: str | os.PathLike, *, process: str | None = None,
                 clock=time.time, host: int | None | object = _HOST_FROM_ENV,
                 hosts: int | None = None, max_mb: float | None = None,
                 tenant: str | None = None, priority: int | None = None):
        self.workdir = os.path.abspath(os.fspath(workdir))
        self.process = process or _default_process()
        self.tenant = tenant if tenant is not None else (
            os.environ.get(TENANT_ENV) or None)
        self.priority = (priority if priority is not None
                         else _priority_from_env())
        # size-capped segment rotation (long-lived serving fleets must not
        # grow one unbounded file per process): segment 0 is the classic
        # ``events-<process>.jsonl``, later ones ``events-<process>.<n>.jsonl``
        # — all matched by the reader's events-*.jsonl glob, merged by ts.
        self._max_bytes = (int(max_mb * 1024 * 1024)
                           if max_mb else _max_bytes_from_env())
        self._seg = 0
        self._bytes = 0
        self.path = self._seg_path(0)
        # host identity stamped on every event (fleet aggregation key).
        # Default: the DLS_* env contract. host=None opts a non-host process
        # (the supervisor) out of the fleet table; an explicit
        # host should come with the gang size (``hosts``), which otherwise
        # falls back to the env contract's count.
        from distributeddeeplearningspark_tpu.utils.env import (
            process_identity,
        )

        env_host, env_hosts = process_identity()
        self.host = env_host if host is EventWriter._HOST_FROM_ENV else host
        self.hosts = hosts if hosts is not None else env_hosts
        if self.host is not None:
            self.hosts = max(self.hosts, self.host + 1)
        self._clock = clock
        self._lock = threading.Lock()
        self._f = None
        self._closed = False
        self._warned = False
        # innermost-open-phase tracking for heartbeat enrichment: a list,
        # not a set — nested identical names (restore inside restore) must
        # pop correctly
        self._open_phases: list[str] = []
        # open-span notes (serving request liveness): insertion-ordered, so
        # next(iter(...)) is the OLDEST in-flight request — the one a hang
        # verdict should name (see note_span)
        self._open_spans: dict[Any, tuple[str, float]] = {}

    def _seg_path(self, seg: int) -> str:
        name = (f"events-{self.process}.jsonl" if seg == 0
                else f"events-{self.process}.{seg}.jsonl")
        return os.path.join(self.workdir, TELEMETRY_DIRNAME, name)

    def _record(self, kind: str, fields: dict[str, Any]) -> dict[str, Any]:
        rec = {"ts": self._clock(), "kind": kind, "process": self.process,
               **fields}
        if self.host is not None:
            rec.setdefault("host", self.host)
            if self.hosts > 1:
                rec.setdefault("hosts", self.hosts)
        if self.tenant is not None:
            # setdefault: a record-level tenant (a router shed naming the
            # tenant it throttled) is evidence; the env stamp is attribution
            rec.setdefault("tenant", self.tenant)
        if self.priority is not None:
            # same discipline as the tenant stamp: a record-level priority
            # (a sched edge describing another job) wins over attribution
            rec.setdefault("priority", self.priority)
        return rec

    def _resume_segment(self) -> None:
        """Continue appending to the newest existing segment (a restarted
        process must extend its predecessor's rotation sequence, not
        overwrite segment 0 growth accounting)."""
        seg = 0
        for p in glob.glob(os.path.join(
                self.workdir, TELEMETRY_DIRNAME,
                f"events-{self.process}.*.jsonl")):
            tag = os.path.basename(p)[len(f"events-{self.process}."):-len(".jsonl")]
            if tag.isdigit():
                seg = max(seg, int(tag))
        self._seg = seg
        self.path = self._seg_path(seg)
        try:
            self._bytes = os.path.getsize(self.path)
        except OSError:
            self._bytes = 0

    def _write_lines(self, lines: list[str]) -> None:
        """Append + flush under the already-held lock (ONE flush per call
        — the single write path emit and emit_many share). Rotates to the
        next segment first when the append would push the current one past
        the size cap (a single oversized batch still lands whole — events
        are never split across segments)."""
        data = "\n".join(lines) + "\n"
        try:
            if self._f is None:
                os.makedirs(os.path.dirname(self.path), exist_ok=True)
                self._resume_segment()
                self._f = open(self.path, "a")
            if (self._max_bytes is not None and self._bytes > 0
                    and self._bytes + len(data) > self._max_bytes):
                self._f.close()
                # None BEFORE the reopen: if it raises, a later emit must
                # retry the open path, not write to a closed handle (a
                # ValueError no handler catches — telemetry failures
                # degrade to a warning, never kill a serving thread)
                self._f = None
                self._seg += 1
                self._bytes = 0
                self.path = self._seg_path(self._seg)
                self._f = open(self.path, "a")
            self._f.write(data)
            self._f.flush()
            self._bytes += len(data)
        except OSError as e:
            if not self._warned:
                logger.warning("telemetry disabled (%s): %s", self.path, e)
                self._warned = True

    def emit(self, kind: str, **fields: Any) -> None:
        rec = self._record(kind, fields)
        with self._lock:
            if self._closed:
                # a stale reference held past configure()'s rebind (or any
                # close()) must NOT silently reopen the file and fork the
                # stream in two — late emits drop instead
                return
            if kind == "phase":
                name = fields.get("name")
                if name:
                    if fields.get("edge") == "begin":
                        self._open_phases.append(name)
                    elif fields.get("edge") == "end" and name in self._open_phases:
                        # remove the LAST occurrence (innermost of nested spans)
                        for i in range(len(self._open_phases) - 1, -1, -1):
                            if self._open_phases[i] == name:
                                del self._open_phases[i]
                                break
            elif kind == "heartbeat" and "phase" not in rec:
                # a heartbeat names where the process IS, not just that it
                # lives — the field hang localization reads when a host's
                # last event is a heartbeat. Open phases win (training);
                # otherwise the OLDEST open request span (serving) plays
                # the same role, so a wedged request localizes exactly
                # like a wedged restore.
                if self._open_phases:
                    rec["phase"] = self._open_phases[-1]
                elif self._open_spans:
                    name, t0 = next(iter(self._open_spans.values()))
                    rec["phase"] = name
                    rec["phase_t0"] = t0
            self._write_lines([json.dumps(rec, default=str)])

    def emit_many(self, kind: str, records: "list[dict[str, Any]]") -> None:
        """Append N same-kind events under ONE lock/flush.

        The serving engine emits one ``request`` event per request in a
        coalesced batch; flushing per event made telemetry ~45% of the
        serving hot loop's host time. One flush per *batch* keeps the
        durability granularity the engine actually has (a crash loses at
        most the batch that was being reported) at 1/N the cost.

        ``phase``/``heartbeat`` are rejected: those kinds carry the
        open-phase tracking/enrichment that only :meth:`emit` maintains,
        and silently skipping it would starve hang localization."""
        if kind in ("phase", "heartbeat"):
            raise ValueError(
                f"emit_many({kind!r}): phase/heartbeat events need emit()'s "
                f"open-phase tracking — batch-append would skip it")
        if not records:
            return
        with self._lock:
            if self._closed:
                return
            self._write_lines([json.dumps(self._record(kind, fields),
                                          default=str)
                               for fields in records])

    def note_span(self, key: Any, name: str) -> None:
        """Mark an in-flight request span open (serving liveness).

        Nothing is written: the note only enriches subsequent heartbeats —
        when no training phase is open, a heartbeat carries the oldest
        noted span's ``name`` as its ``phase`` plus ``phase_t0`` (when the
        request began), so hang localization can say "replica 1 stuck in
        request for 312s" from the stream's last record alone, exactly as
        it says "stuck in restore". ``key`` is any hashable request
        identity; :meth:`clear_span` removes it."""
        with self._lock:
            self._open_spans.pop(key, None)
            self._open_spans[key] = (name, self._clock())

    def clear_span(self, key: Any) -> None:
        with self._lock:
            self._open_spans.pop(key, None)

    @contextlib.contextmanager
    def phase(self, name: str, **fields: Any):
        """Span a blocking phase: begin/end records, end carries ``dur_s``.
        The begin record makes crashed runs honest — an unterminated begin
        is accounted up to the stream's last event. The phase is also a
        ``dls.phase/<name>`` span in the profiler's trace (:mod:`.spans`),
        on the clock of the device's ops. A phase costs two file writes,
        so never one per step."""
        t0 = self._clock()
        self.emit("phase", name=name, edge="begin", **fields)
        try:
            with spans.span(spans.PHASE_PREFIX + name):
                yield
        finally:
            self.emit("phase", name=name, edge="end",
                      dur_s=self._clock() - t0, **fields)

    # typed convenience emitters ------------------------------------------

    def step_metrics(self, step: int, *, steps: int, lap_s: float,
                     metrics: dict[str, float] | None = None,
                     **gauges: Any) -> None:
        self.emit("step_metrics", step=int(step), steps=int(steps),
                  lap_s=float(lap_s), metrics=dict(metrics or {}), **gauges)

    def recovery(self, step: int | None, event: str, **fields: Any) -> None:
        """``step=None`` when the emitter doesn't know the training step
        (e.g. the supervisor, which only sees process lifecycles) — a wrong
        guess would mislead the dlstatus timeline."""
        if step is None:
            self.emit("recovery", event=event, **fields)
        else:
            self.emit("recovery", step=int(step), event=event, **fields)

    def attempt(self, edge: str, ordinal: int, **fields: Any) -> None:
        self.emit("attempt", edge=edge, ordinal=int(ordinal), **fields)

    def heartbeat(self, **fields: Any) -> None:
        self.emit("heartbeat", **fields)

    def close(self) -> None:
        with self._lock:
            self._closed = True
            if self._f is not None:
                try:
                    self._f.close()
                except OSError:
                    pass
                self._f = None


# -- module singleton (for layers that can't thread a writer through) --------

_writer: EventWriter | None = None


def configure(workdir: str | os.PathLike, *, process: str | None = None,
              clock=time.time) -> EventWriter:
    """Bind the process-wide writer to ``workdir`` (idempotent per workdir).

    The Trainer calls this with the resolved run workdir; from then on
    layers without a writer reference (checkpoint.py, profiling.py) emit
    through :func:`emit`/:func:`phase`."""
    global _writer
    wd = os.path.abspath(os.fspath(workdir))
    if (_writer is not None and _writer.workdir == wd
            and (process is None or _writer.process == process)):
        return _writer
    if _writer is not None:
        _writer.close()
    _writer = EventWriter(wd, process=process, clock=clock)
    return _writer


def get() -> EventWriter | None:
    return _writer


def reset() -> None:
    """Drop the process-wide writer (tests; also ends a run's binding)."""
    global _writer
    if _writer is not None:
        _writer.close()
        _writer = None


def emit(kind: str, **fields: Any) -> None:
    """Emit through the process-wide writer; no-op when unconfigured."""
    if _writer is not None:
        _writer.emit(kind, **fields)


def emit_many(kind: str, records: "list[dict[str, Any]]") -> None:
    """Batched :func:`emit` through the process-wide writer (one flush)."""
    if _writer is not None:
        _writer.emit_many(kind, records)


def phase(name: str, **fields: Any):
    """Span context through the process-wide writer; unconfigured, the
    phase is still a ``dls.phase/<name>`` span in the profiler's trace."""
    if _writer is not None:
        return _writer.phase(name, **fields)
    return spans.span(spans.PHASE_PREFIX + name)


# -- reader ------------------------------------------------------------------


def event_files(workdir: str | os.PathLike) -> list[str]:
    return sorted(glob.glob(os.path.join(telemetry_dir(workdir),
                                         "events-*.jsonl")))


def _parse_event_line(line: str) -> dict | None:
    """One JSONL line -> event dict, or None for torn/garbage lines.

    A record must be a JSON object carrying ``ts`` and ``kind`` — anything
    else (a half-written tail, an editor's stray newline, a non-event JSON
    value) is not an event."""
    line = line.strip()
    if not line:
        return None
    try:
        rec = json.loads(line)
    except ValueError:
        return None
    if isinstance(rec, dict) and "ts" in rec and "kind" in rec:
        return rec
    return None


def read_events(workdir: str | os.PathLike) -> list[dict]:
    """Merge every process's event file into one ts-ordered stream.

    Torn lines (a writer SIGKILLed mid-append) and non-JSON garbage are
    skipped — a crashed run's partial stream must parse. The sort is stable,
    so records with equal timestamps keep their per-file order (the
    multi-process merge contract the tests pin)."""
    events: list[dict] = []
    for path in event_files(workdir):
        try:
            with open(path) as f:
                for line in f:
                    rec = _parse_event_line(line)
                    if rec is not None:
                        events.append(rec)
        except OSError:
            continue
    events.sort(key=lambda e: float(e["ts"]))
    return events


class EventCursor:
    """Incremental :func:`read_events`: per-file byte offsets so each poll
    parses only what was appended since the last one.

    ``dlstatus --watch`` and the health engine re-evaluate every few
    seconds; re-parsing a long run's whole JSONL set each tick is O(total
    events) per tick and grows without bound. The cursor keeps one byte
    offset per segment file:

    - **New files/segments** (a rotation, a late-joining process) enter the
      glob on the next poll and are read from byte 0.
    - **Torn tails** — a writer mid-append when we poll — are held back:
      only complete (newline-terminated) lines are consumed, the offset
      stays at the line start, and the finished line parses next poll.
      A torn line is therefore *deferred*, never dropped (the one-shot
      reader, arriving after the crash, skips it instead).
    - **Truncated/replaced files** (offset beyond EOF) reset to 0.

    ``events`` is the accumulated ts-sorted merge (what :func:`read_events`
    would return, minus any still-torn tails); :meth:`poll` returns just the
    newly appended records. ``skipped_lines`` counts complete-but-garbage
    lines — the parseable-but-degraded signal the health engine reports
    when a crashed run's partial segment is all a workdir has."""

    def __init__(self, workdir: str | os.PathLike):
        self.workdir = os.fspath(workdir)
        self._offsets: dict[str, int] = {}
        self.events: list[dict] = []
        self.skipped_lines = 0
        #: total bytes consumed across every poll — the receipt that watch
        #: cost is bounded by the append rate (ci.sh history asserts it).
        self.bytes_read = 0

    @property
    def files(self) -> list[str]:
        """Every segment file seen so far (polled at least once)."""
        return sorted(self._offsets)

    def lag_bytes(self) -> int:
        """Bytes on disk the cursor has not consumed yet: appended-but-
        unpolled data plus still-torn tails (files the glob hasn't seen
        count in full). The health engine records this as its own
        falling-behind gauge."""
        lag = 0
        for path in event_files(self.workdir):
            try:
                size = os.path.getsize(path)
            except OSError:
                continue
            lag += max(0, size - self._offsets.get(path, 0))
        return lag

    def poll(self) -> list[dict]:
        """Read appended lines from every segment; return the new events
        (also merged, ts-stably, into :attr:`events`)."""
        new: list[dict] = []
        for path in event_files(self.workdir):
            off = self._offsets.setdefault(path, 0)
            try:
                size = os.path.getsize(path)
                if size < off:
                    off = self._offsets[path] = 0  # truncated/replaced
                if size == off:
                    continue
                with open(path, "rb") as f:
                    f.seek(off)
                    data = f.read()
            except OSError:
                continue
            end = data.rfind(b"\n")
            if end < 0:
                continue  # only a torn fragment so far — retry next poll
            self._offsets[path] = off + end + 1
            self.bytes_read += end + 1
            for raw in data[:end + 1].splitlines():
                rec = _parse_event_line(raw.decode("utf-8", errors="replace"))
                if rec is not None:
                    new.append(rec)
                elif raw.strip():
                    self.skipped_lines += 1
        if new:
            self.events.extend(new)
            self.events.sort(key=lambda e: float(e["ts"]))
        return new


# -- goodput accounting ------------------------------------------------------


def _union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total covered length of possibly-overlapping [t0, t1] intervals."""
    total = 0.0
    end = -math.inf
    for t0, t1 in sorted(intervals):
        if t1 <= end:
            continue
        total += t1 - max(t0, end)
        end = t1
    return total


def _subtract_intervals(
    iv: tuple[float, float], subs: list[tuple[float, float]],
) -> list[tuple[float, float]]:
    """``iv`` minus every interval in ``subs`` (may split it)."""
    out = [iv]
    for s0, s1 in subs:
        nxt: list[tuple[float, float]] = []
        for t0, t1 in out:
            if s1 <= t0 or t1 <= s0:
                nxt.append((t0, t1))
                continue
            if t0 < s0:
                nxt.append((t0, s0))
            if s1 < t1:
                nxt.append((s1, t1))
        out = nxt
    return out


def goodput(events: Iterable[dict]) -> dict[str, float]:
    """Fold an event stream into the run's time budget.

    Returns ``{wall_s, productive_s, compile_s, restore_s, checkpoint_s,
    eval_s, input_starved_s, restart_overhead_s, goodput_frac}``.

    Accounting model: wall-clock is the stream's [first ts, last ts] span.
    Overhead phases are intervals, merged by union — within a category so a
    double-instrumented span counts once, and across ALL categories for the
    productive residual, so a span nested in another is never subtracted
    twice. ``input_starved_s`` is a counter (the per-lap probe snapshots
    summed per process, then the MAX across processes — lockstep SPMD means
    the slowest host's wait is the gang's wait). ``restart_overhead_s``
    is the dead time between one attempt's end and the next one's begin
    (supervisor backoff + teardown). ``idle_s`` is the gap between one
    ``run`` span's end and the next one's begin — a stop-today/resume-
    tomorrow workdir accrues a day of idle, which must be neither
    "productive" nor a restart (gaps already covered by a supervisor
    restart interval are not double-counted). ``productive_s`` is the
    residual: wall − union(all overhead intervals) − input_starved. A
    crashed stream simply ends early — an unterminated phase begin is
    accounted up to the last event seen.
    """
    out = {"wall_s": 0.0, "productive_s": 0.0, "input_starved_s": 0.0,
           "goodput_frac": 0.0}
    for c in _INTERVAL_COMPONENTS:
        out[c] = 0.0
    # alert events are meta-observation (the health engine watching the
    # run), not run activity: a long-lived engine appending edges to a
    # finished workdir must not stretch its wall-clock span
    events = [e for e in events if "ts" in e and e.get("kind") != "alert"]
    if not events:
        return out
    events = sorted(events, key=lambda e: float(e["ts"]))
    t_lo, t_hi = float(events[0]["ts"]), float(events[-1]["ts"])
    wall = t_hi - t_lo
    out["wall_s"] = wall

    intervals: dict[str, list[tuple[float, float]]] = {
        c: [] for c in _INTERVAL_COMPONENTS}
    open_phases: dict[tuple, list[float]] = {}
    last_ts_by_process: dict[str | None, float] = {}
    attempt_ends: list[float] = []
    input_by_process: dict[str | None, float] = {}
    last_attempt_end: float | None = None
    last_end_ordinal = -2  # sentinel: nothing follows it
    last_run_end: float | None = None
    idle_candidates: list[tuple[float, float]] = []
    for e in events:
        kind, ts = e.get("kind"), float(e["ts"])
        proc = e.get("process")
        prev_proc_ts = last_ts_by_process.get(proc)
        last_ts_by_process[proc] = ts
        if kind == "phase":
            name = e.get("name", "")
            cat = PHASE_CATEGORY.get(name)
            key = (proc, name)
            if e.get("edge") == "begin":
                if name == "run":
                    starts = open_phases.get(key)
                    if starts:
                        # a NEW run span while this process's previous one
                        # never closed: that session crashed — it effectively
                        # ended at the process's last prior event, and the
                        # gap from there to this resume is idle, not
                        # productive residual
                        starts.clear()
                        if prev_proc_ts is not None and ts > prev_proc_ts:
                            idle_candidates.append((prev_proc_ts, ts))
                    elif last_run_end is not None and ts > last_run_end:
                        # gap since the previous run span closed cleanly =
                        # a stopped workdir sitting idle between sessions
                        idle_candidates.append((last_run_end, ts))
                    last_run_end = None
                open_phases.setdefault(key, []).append(ts)
            elif e.get("edge") == "end":
                starts = open_phases.get(key)
                t0 = starts.pop() if starts else ts - float(e.get("dur_s", 0.0))
                if cat:
                    intervals[cat].append((min(t0, ts), ts))
                if name == "run":
                    last_run_end = ts
        elif kind == "step_metrics":
            input_by_process[proc] = (input_by_process.get(proc, 0.0)
                                      + float(e.get("input_wait_s", 0.0) or 0.0))
        elif kind == "attempt":
            if e.get("edge") == "end":
                last_attempt_end = ts
                last_end_ordinal = int(e.get("ordinal", -1))
                attempt_ends.append(ts)
            elif e.get("edge") == "begin" and last_attempt_end is not None:
                # restart overhead only pairs WITHIN one supervisor session
                # (ordinals increment per relaunch); an ordinal that does
                # not follow the last end is a fresh supervisor invocation
                # on the same workdir — that gap is idle time between
                # sessions, not the price of a restart
                if (int(e.get("ordinal", -1)) == last_end_ordinal + 1
                        and ts > last_attempt_end):
                    intervals["restart_overhead_s"].append(
                        (last_attempt_end, ts))
                last_attempt_end = None
    # crash mid-phase: the begin is all we have. Do NOT extend it to the
    # whole stream's end — a relaunched attempt appends hours of events to
    # the same file set, and an orphaned span stretched across them would
    # swallow the relaunch's productive time. The honest bound is the first
    # supervisor attempt-end after the begin (when the death was reaped),
    # falling back to the opening process's own last event (when it went
    # silent) for unsupervised runs.
    for (proc, name), starts in open_phases.items():
        cat = PHASE_CATEGORY.get(name or "")
        if cat:
            proc_last = last_ts_by_process.get(proc, t_hi)
            for t0 in starts:
                reaped = [t for t in attempt_ends if t >= t0]
                t1 = min(reaped) if reaped else proc_last
                intervals[cat].append((t0, max(t0, t1)))

    # idle-between-runs, minus the sub-spans a supervisor restart interval
    # already accounts for (a relaunch IS a run-end→run-begin gap too).
    # SUBTRACTED, not dropped whole: a hang's dwell (worker silent long
    # before the watchdog reaped it) and the relaunch's startup tail extend
    # beyond the restart interval and must not fall back into "productive"
    restarts = intervals["restart_overhead_s"]
    intervals["idle_s"] = [
        piece for cand in idle_candidates
        for piece in _subtract_intervals(cand, restarts)]

    all_iv: list[tuple[float, float]] = []
    for cat, iv in intervals.items():
        out[cat] = _union_seconds(iv)
        all_iv.extend(iv)
    # gang-step SPMD runs in lockstep: the slowest host's input wait gates
    # every step, so the gang-level starvation is the MAX over processes —
    # summing would over-count N-fold exactly like un-unioned intervals
    input_starved = max(input_by_process.values(), default=0.0)
    out["input_starved_s"] = input_starved
    overhead = _union_seconds(all_iv) + input_starved
    out["productive_s"] = max(0.0, wall - overhead)
    out["goodput_frac"] = out["productive_s"] / wall if wall > 0 else 0.0
    return out
