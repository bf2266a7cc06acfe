"""The program's own spans on the training hot path: one helper, one list.

:func:`span` marks a section of the loop or of the feed twice at once. It
opens a ``jax.profiler.TraceAnnotation``, so the section lies in the
profiler's trace on the clock of the device's ops whenever a profiler runs
(``fit(profile=...)``, ``utils/profiling.trace``, a caller's own
``start_trace``) and costs a fraction of a microsecond when none does. And,
given a ``sink``, it adds the section's ``perf_counter`` time to the per-lap
accumulator that rides ``step_metrics``: :class:`~.anatomy.StepAnatomy` for
the loop thread, :class:`~..data.prefetch.StarvationProbe` for the feed,
and once a process :class:`~.anatomy.StartupLedger` for the sections of the
start. No sink (telemetry off; the start on record) means a bare
``TraceAnnotation``. Nothing here writes to the JSONL stream.

No jax import at module level: ``telemetry/__init__`` imports this for
:func:`~..telemetry.phase`, and the reader side must stay jax-free. A
process that never imported jax has no profiler to write to and gets a null
context.
"""

from __future__ import annotations

import contextlib
import ctypes
import sys
import threading

#: every ``dls.start/*`` section: the start of the process, up to the close
#: of its first lap
START_PREFIX = "dls.start/"

#: span name -> the counter its time adds to: per lap in ``step_metrics``
#: (loop thread first, then the feed's threads), once a process in the
#: ``startup`` record for the ``dls.start/*`` names (docs/OBSERVABILITY.md
#: "Device anatomy" says where each is opened).
COUNTERS = {
    "dls.feed/wait": "input_wait_s",
    "dls.feed/put": "input_put_s",
    "dls.step/dispatch": "device_dispatch_s",
    "dls.step/lower": "compile_in_lap_s",
    "dls.step/compile": "compile_in_lap_s",
    "dls.fit/sync": "device_drain_s",
    "dls.fit/emit": "emit_s",
    "dls.fit/callbacks": "callbacks_s",
    "dls.fit/checkpoint": "checkpoint_s",
    "dls.fit/eval": "eval_s",
    "dls.feed/assemble": "input_assembly_s",
    "dls.feed/stack": "input_stack_s",
    "dls.feed/ring_full": "input_blocked_s",
    "dls.feed/map": "input_map_s",
    "dls.feed/decode": "input_decode_s",
    # of these three the NUMBER of sections is added, not their time: a
    # batch's arrays came from a slot the feed kept, or from new memory
    # (``data/feed._Slots``); every local row of a batch was written by the
    # pool of the ``map_parallel`` stream it came from (``_Assembly``)
    "dls.feed/slot_reused": "input_slot_reused",
    "dls.feed/slot_new": "input_slot_new",
    "dls.feed/filled_by_map": "input_filled_by_map",
    # the start (``anatomy.StartupLedger``); ``dls.start/fit`` holds every
    # section below it, so its own time is the start that no section covers
    "dls.start/import": "import_s",
    "dls.start/session": "session_s",
    "dls.start/backend": "backend_s",
    "dls.start/fit": "fit_unaccounted_s",
    "dls.start/sample": "sample_s",
    "dls.start/init_state": "init_state_s",
}
#: ``EventWriter.phase(name)`` also opens ``dls.phase/<name>`` (trace only)
PHASE_PREFIX = "dls.phase/"
#: every span name the program writes; ``train`` is the step marker
#: (``utils/profiling.step_annotation``)
SPAN_NAMES = (*COUNTERS, PHASE_PREFIX, "train")

_thread = threading.local()
_TraceAnnotation = None  # jax.profiler.TraceAnnotation, once jax is loaded


def span(name: str, sink=None):
    """Context manager for one section called ``name``.

    ``sink`` is an accumulator with ``clock()`` and ``add(name, dt,
    inner_s)``; ``inner_s`` is the time of sections with the same sink that
    ran nested inside this one on the same thread (the anatomy subtracts it,
    so its sections tile a lap; the probe ignores it). A section that raises
    is not added. Never hold one open across a ``yield``: nesting is
    tracked per thread.
    """
    ann = _annotation(name)
    return ann if sink is None else _Timed(ann, name, sink)


def _annotation(name: str):
    global _TraceAnnotation
    if _TraceAnnotation is None:
        if "jax" not in sys.modules:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name)


class _Timed:
    __slots__ = ("_ann", "_name", "_sink", "_t0", "_inner_s")

    def __init__(self, ann, name: str, sink):
        self._ann, self._name, self._sink = ann, name, sink

    def __enter__(self):
        self._ann.__enter__()
        self._inner_s = 0.0
        _open_spans().append(self)
        self._t0 = self._sink.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = self._sink.clock() - self._t0
        stack = _open_spans()
        stack.pop()
        if stack and stack[-1]._sink is self._sink:
            stack[-1]._inner_s += dt
        if exc_type is None:
            self._sink.add(self._name, dt, self._inner_s)
        return self._ann.__exit__(exc_type, exc, tb)


def _open_spans() -> list:
    try:
        return _thread.open
    except AttributeError:
        _thread.open = []
        return _thread.open


def bind_sink(sink) -> None:
    """Make ``sink`` the calling thread's feed accumulator: what the feed's
    sections that have no ``probe`` argument (``host_batches``' row copies
    and slots, ``map_parallel``'s calls, ``decode_jpeg``) add to. ``None``
    unbinds."""
    _thread.sink = sink


def bound_sink():
    return getattr(_thread, "sink", None)


def name_thread(name: str) -> None:
    """Name the calling thread for the operating system (15 bytes at most).

    The profiler calls a host thread's line by that name and a Python
    thread has none of its own, so every unnamed thread's line is called
    after the process; ``top -H`` and ``py-spy`` show the same name. Never
    call it on the main thread: that renames the process. A no-op where
    there is no ``prctl``."""
    if not sys.platform.startswith("linux"):
        return
    try:
        ctypes.CDLL(None).prctl(15, name.encode()[:15], 0, 0, 0)  # PR_SET_NAME
    except (OSError, AttributeError):
        pass
