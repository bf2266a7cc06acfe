"""Device-side prefetch: keep HBM fed while the current step runs.

BASELINE.json's north star names this explicitly: "Spark RDD/DataFrame
partitions stream into HBM via a device-side prefetch iterator". JAX dispatch
is asynchronous, so the recipe is a small look-ahead ring: transfer the next
``buffer_size`` batches to device *before* the consumer asks for them. The
``device_put`` for batch N+1 overlaps the device executing step N; a separate
host thread does the (possibly expensive) host-side assembly (decode /
augment / stack) so Python never blocks the dispatch path.

**Starvation probe.** An input-bound step and a compute-bound step look
identical in wall-clock; the difference is whether the *consumer* had to
block waiting for the next host batch. :class:`StarvationProbe` measures
exactly that (plus prefetch queue depth and host assembly time), the Trainer
snapshots it per metrics lap into the telemetry stream, and the goodput
accountant reports the total as ``input_starved_s`` — see
docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Callable, Iterator

import jax
from jax.sharding import Mesh

from distributeddeeplearningspark_tpu.data.feed import put_global
from distributeddeeplearningspark_tpu.telemetry import spans

_SENTINEL = object()


def _timed(it, name: str, sink) -> Iterator:
    """Each blocking ``next()`` of ``it`` as one ``name`` section (the pull
    that finds the iterator exhausted is not one)."""
    it = iter(it)  # accept plain iterables, same as a for-loop would
    while True:
        try:
            with spans.span(name, sink):
                x = next(it)
        except StopIteration:
            return
        yield x


class StarvationProbe:
    """Thread-safe per-lap counters for "how long did training wait on
    input, and what was the feed doing?" — the feed's sink of
    :func:`~..telemetry.spans.span`.

    - ``input_wait_s`` (``dls.feed/wait``) — consumer-side block: the
      training loop asked for the next batch and the prefetch ring had
      nothing ready. This is the starvation signal proper (sums into
      ``input_starved_s``); ``input_waits`` / ``input_wait_max_s`` ride along.
    - ``input_put_s`` (``dls.feed/put``) — the loop thread inside
      ``put(hb, mesh)``: ``device_put`` of the batch it already has.
    - ``record_depth`` — prefetch queue depth sampled at each consumer get;
      a ring that is persistently empty (min 0, mean ≈ 0) is input-bound,
      one that hovers full is compute-bound.
    - ``input_assembly_s`` (``dls.feed/assemble``) — producer-side cost of
      building one host batch (decode/augment/stack), measured in the
      background thread; tells you WHY the ring ran dry. Of it,
      ``input_stack_s`` (``dls.feed/stack``) is the copying of the
      examples into their rows of the batch by the producer itself
      (``feed._Assembly``), and once a batch the taking of its slot.
    - ``input_slot_reused`` / ``input_slot_new`` (``dls.feed/slot_reused``,
      ``dls.feed/slot_new``) — numbers, not seconds: the batches whose
      arrays ``host_batches`` took from a slot it kept, and from new memory
      because every kept slot was still referred to (or there was none yet).
    - ``input_filled_by_map`` (``dls.feed/filled_by_map``) — a number: the
      batches whose local rows were all written by the threads of the
      ``map_parallel`` pool that made them (``feed._Assembly``), so that
      the producer did nothing once an example; 0 where no stream offers
      ``fill``.
    - ``input_worker_items`` / ``input_worker_reads``
      (``dls.feed/worker_example``, ``dls.feed/worker_read``) — numbers:
      the examples this feed's thread took from worker processes
      (``data/workers.WorkerPool``), and the files those processes read for
      them, credited with each example (0 on the threads' path).
    - ``input_blocked_s`` (``dls.feed/ring_full``) — the producer holding a
      finished batch with no room in the ring: the feed's headroom.
    - ``input_map_s`` (``dls.feed/map``) — thread-seconds inside
      ``map_parallel``'s function, summed over the pool, or the worker
      processes' seconds inside theirs, credited with each example; absent
      until a parallel map has run under this probe.
    - ``input_decode_s`` (``dls.feed/decode``) — seconds inside
      ``vision.decode_jpeg`` on this feed's threads or worker processes
      (summed over them where a pool decodes, and then a part of
      ``input_map_s``); absent until a JPEG has been decoded under this
      probe.

    ``clock`` is injectable so tests measure deterministic fake seconds.
    ``snapshot(reset=True)`` returns-and-clears, giving per-lap gauges.
    """

    #: the counters that are numbers of sections, not their seconds
    _COUNTED = ("input_slot_reused", "input_slot_new", "input_filled_by_map",
                "input_worker_items", "input_worker_reads")
    #: the counters every snapshot carries (``input_map_s`` and
    #: ``input_decode_s`` apart)
    _ALWAYS = ("input_wait_s", "input_put_s", "input_assembly_s",
               "input_stack_s", "input_blocked_s", *_COUNTED)

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self._lock = threading.Lock()
        self._sums: dict[str, float] = {}
        self._zero()

    def _zero(self) -> None:
        # a probe that has seen a parallel map, or a decode, keeps its key
        self._sums = dict.fromkeys((*self._ALWAYS, *self._sums), 0.0)
        self._waits = 0
        self._wait_max = 0.0
        self._depth_sum = 0
        self._depth_n = 0
        self._depth_min: int | None = None

    def add(self, name: str, dt: float, inner_s: float = 0.0) -> None:
        """One closed section (:func:`~..telemetry.spans.span`'s sink side);
        the feed's counters are inclusive, so ``inner_s`` is not taken off."""
        key = spans.COUNTERS[name]
        with self._lock:
            self._sums[key] = self._sums.get(key, 0.0) + (
                1 if key in self._COUNTED else dt)
            if key == "input_wait_s":
                self._waits += 1
                self._wait_max = max(self._wait_max, dt)

    def credit(self, name: str, amount: float) -> None:
        """Add ``amount`` to ``name``'s counter: what a worker process did
        for an example this feed's thread took from it, which none of this
        process's sections saw (seconds, or a number of files)."""
        key = spans.COUNTERS[name]
        with self._lock:
            self._sums[key] = self._sums.get(key, 0.0) + amount

    def record_depth(self, depth: int) -> None:
        with self._lock:
            self._depth_sum += depth
            self._depth_n += 1
            self._depth_min = (depth if self._depth_min is None
                               else min(self._depth_min, depth))

    def timed(self, it, name: str = "dls.feed/wait") -> Iterator:
        """Wrap an iterable so each blocking ``next()`` is one ``name``
        section of this probe (default: the consumer's wait)."""
        return _timed(it, name, self)

    def snapshot(self, *, reset: bool = True) -> dict[str, float]:
        """Gauges since the last snapshot, keyed for the telemetry record.

        When a :mod:`~distributeddeeplearningspark_tpu.data.workers` pool is
        live, the per-worker utilization/queue-depth rollup rides along
        (``input_workers``, ``worker_util_mean/min``, ``worker_items``,
        ``worker_overflow``, ``worker_ahead_mean``, ``worker_ring_used_mb``)
        so ``dlstatus`` can tell pool-bound (util ≈ 1 while the consumer
        still waits) from consumer-bound (util low, waits low) input.
        Worker utilizations are pool-lifetime fractions (pools restart per
        epoch); the wait/assembly keys stay per-lap as before.
        """
        with self._lock:
            out = {**self._sums, "input_waits": self._waits,
                   "input_wait_max_s": self._wait_max}
            if self._depth_n:
                out["prefetch_depth_mean"] = self._depth_sum / self._depth_n
                out["prefetch_depth_min"] = self._depth_min
            if reset:
                self._zero()
        try:
            from distributeddeeplearningspark_tpu.data import workers

            out.update(workers.pool_gauges())
        except Exception:  # noqa: BLE001 — gauges must never fail a lap
            pass
        return out


def prefetch_to_device(
    host_iter: Iterator[dict[str, Any]],
    mesh: Mesh,
    *,
    buffer_size: int = 2,
    put: Callable[[dict[str, Any], Mesh], Any] = put_global,
    background: bool = True,
    probe: StarvationProbe | None = None,
) -> Iterator[Any]:
    """Wrap a host-batch iterator into a double-buffered device iterator.

    ``buffer_size=2`` (double buffering) is enough to hide transfer latency
    when host assembly keeps up; raise it for bursty sources. ``probe``
    times the consumer-blocked fetch of each host batch and samples the
    ring's queue depth (see :class:`StarvationProbe`).
    """
    if background:
        host_iter = _background(host_iter, maxsize=buffer_size + 1,
                                probe=probe)
    # times the blocking pull of the NEXT host batch: with background=True
    # that's the q.get() wait (assembly ran behind), without it the
    # synchronous assembly itself — either way, time training stood still
    host_iter = _timed(host_iter, "dls.feed/wait", probe)

    buf: collections.deque = collections.deque()
    for hb in host_iter:
        with spans.span("dls.feed/put", probe):
            placed = put(hb, mesh)
        buf.append(placed)
        if len(buf) >= buffer_size:
            yield buf.popleft()
    while buf:
        yield buf.popleft()


def _background(it: Iterator, *, maxsize: int,
                probe: StarvationProbe | None = None) -> Iterator:
    """Run an iterator in a daemon thread through a bounded queue."""
    q: queue.Queue = queue.Queue(maxsize=maxsize)
    err: list[BaseException] = []

    def worker() -> None:
        spans.name_thread("dls-prefetch")
        # host_batches' row copies and map_parallel's calls run under this
        # thread's pulls and have no probe argument: they find it here
        spans.bind_sink(probe)
        try:
            for x in _timed(it, "dls.feed/assemble", probe):
                with spans.span("dls.feed/ring_full", probe):
                    q.put(x)
        except BaseException as e:  # propagate into consumer
            err.append(e)
        finally:
            q.put(_SENTINEL)

    t = threading.Thread(target=worker, daemon=True, name="dls-prefetch")
    t.start()
    while True:
        if probe is not None:
            probe.record_depth(q.qsize())
        x = q.get()
        if x is _SENTINEL:
            if err:
                raise err[0]
            return
        yield x
