"""PartitionedDataset — the RDD surface, rebuilt as lazy host-side partitions.

The reference's data plane (SURVEY.md §1 L5, §3.1) is Spark RDDs: immutable,
lazy, partitioned collections transformed by ``map``/``mapPartitions`` and
consumed by actions (``collect``, ``reduce``, ``treeAggregate``). The training
loop itself is ``rdd.mapPartitions(train_fn)``.

Here the same lazy/partitioned user model is kept, but partitions are plain
Python thunks producing iterables on the *host*; the device never sees an
"RDD" — terminal consumption happens through
:mod:`distributeddeeplearningspark_tpu.data.feed`, which assembles global
batches from partitions and lays them onto the mesh with batch sharding
(one partition ≙ one data shard, matching Spark's partition↔task pairing).

Wide operations have TWO execution paths since PR 8:

- **Serial (default)**: per-partition combine, then a driver-side dict —
  the honest narrow-engine stance (SURVEY.md §7 "What NOT to build"),
  bounded by the ``max_groups`` cardinality ceiling (``DLS_AGG_MAX_GROUPS``,
  default 1M) which refuses user-id-like keys loudly instead of growing an
  unbounded dict.
- **Distributed exchange**: when workers are available (``num_workers=`` or
  ``DLS_DATA_WORKERS``), ``reduce_by_key``/``group_by_key``/``distinct``/
  ``sort_by`` route through :mod:`~.data.exchange` — a cross-worker
  hash-partitioned shuffle with spill-to-disk reduce, no ceiling at all.
  Output is canonical (bucket by :func:`~.data.exchange.key_bytes`, that
  order within buckets) on BOTH paths, so results are byte-identical at
  any worker count for exact commutative combines.

Both pyspark camelCase and pythonic snake_case spellings are provided.
"""

from __future__ import annotations

import collections
import functools
import itertools
import random
import threading
from typing import Any, Callable, Iterable, Iterator, Sequence

import numpy as np

from distributeddeeplearningspark_tpu.telemetry import spans

#: numbers the ``dls-map-<i>`` pool threads over the process (every
#: partition has a pool of its own, and the profiler keys a thread's line
#: by its name)
_map_thread_ids = itertools.count()


def _start_map_thread(sink) -> None:
    spans.name_thread(f"dls-map-{next(_map_thread_ids)}")
    # what the mapped function opens itself (``decode_jpeg``) finds it here
    spans.bind_sink(sink)

PartitionFn = Callable[[], Iterable[Any]]


class _MappedStream:
    """``f`` over one partition's iterator by a pool of ``workers`` threads,
    in the iterator's order: what :meth:`PartitionedDataset.map_parallel`
    makes of a partition.

    ``next`` keeps a sliding window of ``2 x workers`` calls in flight and
    hands their results over one by one. :meth:`fill` asks for rows of a
    batch instead. The pool starts at the first of either, on the thread
    that asks (whose feed sink its threads bind), and ends when the stream
    does: exhausted or failed (what it was handed before is still done), or
    let go of.
    """

    def __init__(self, it: Iterable[Any], f: Callable[[Any], Any],
                 workers: int):
        self._pool = None
        self._closed = False  # let go of: nothing more is made or written
        self._ended = False   # the upstream iterator has run out
        self._it = iter(it)
        self._f = f
        self._workers = workers
        self._window: collections.deque = collections.deque()
        self._sink = None

    def __iter__(self) -> "_MappedStream":
        return self

    def _started(self):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            # the probe of the feed whose thread pulls this partition (None
            # outside a feed with telemetry): thread-seconds in ``f`` add to
            # its ``input_map_s``, and the pool's threads have it bound
            self._sink = spans.bound_sink()
            self._pool = ThreadPoolExecutor(
                self._workers, initializer=_start_map_thread,
                initargs=(self._sink,))
        return self._pool

    def _call(self, item: Any) -> Any:
        if self._closed:
            return None
        with spans.span("dls.feed/map", self._sink):
            return self._f(item)

    def __next__(self) -> Any:
        if self._closed or (self._ended and not self._window):
            raise StopIteration
        try:
            pool = self._started()
            while not self._ended and len(self._window) < 2 * self._workers:
                try:
                    item = next(self._it)
                except StopIteration:
                    self._ended = True
                    break
                self._window.append(pool.submit(self._call, item))
            if self._window:
                handed = self._window.popleft()
                self._let_the_pool_go()
                return handed.result()
            self._let_the_pool_go()
        except BaseException:
            self._fail()
            raise
        raise StopIteration

    def _fail(self) -> None:
        """The upstream iterator or ``f`` raised: the stream has ended, as a
        generator would have. Rows asked for before are still written (a
        batch begun earlier is whole, and is handed on before the failure
        is raised)."""
        self._ended = True
        self._window.clear()
        if self._pool is not None:
            self._let_the_pool_go()

    def _let_the_pool_go(self) -> None:
        """Once the upstream iterator has ended and nothing more will be
        handed to the pool, its threads end when they have done what they
        were handed."""
        if self._ended and not self._window:
            self._pool.shutdown(wait=False)

    def fill(self, arrays: dict[str, np.ndarray], at: int, n: int) -> "_Fill":
        """The next ``n`` examples into rows ``at``... of ``arrays``, each
        written by the pool thread that made it.

        The calling thread takes the ``n`` items off the upstream iterator
        at once (what ``next`` had already asked for comes first, so the
        order holds) and hands them to the pool in runs of consecutive
        rows, a few runs a thread. A run writes each example under the test
        of ``data/feed._copy_rows`` (the arrays' keys, the row's shape, the
        same dtype), inside the call's ``dls.feed/map`` section, and from
        the first that does not fit keeps its examples loose. Returns at
        once; :meth:`_Fill.wait` is what the caller waits on, once."""
        fill = _Fill(arrays)
        if self._closed or (self._ended and not self._window):
            return fill._asked(0)
        try:
            pool = self._started()
            early = [self._window.popleft()
                     for _ in range(min(n, len(self._window)))]
            items = [] if self._ended else list(
                itertools.islice(self._it, n - len(early)))
            fill.taken = len(early) + len(items)
            run = max(1, -(-n // (4 * self._workers)))
            runs = [(made, todo[i:i + run]) for made, todo in
                    ((True, early), (False, items))
                    for i in range(0, len(todo), run)]
            fill._asked(len(runs))
            row = at
            for k, (made, todo) in enumerate(runs):
                pool.submit(self._run, fill, k, row, todo, made)
                row += len(todo)
            self._ended |= fill.taken < n
            self._let_the_pool_go()
        except BaseException:
            fill.cancel()
            self._fail()
            raise
        return fill

    def _run(self, fill: "_Fill", k: int, row: int, todo: list,
             made: bool) -> None:
        """One run of a :meth:`fill`, on a pool thread: ``todo`` are items
        for ``f``, or (``made``) the futures of calls ``next`` had asked
        for, which lie ahead of this run in the pool's queue."""
        from distributeddeeplearningspark_tpu.data.feed import _copy_rows

        start, loose, arrays = row, None, fill.arrays
        try:
            for x in todo:
                if self._closed or fill.failed_before(k):
                    raise _Stopped
                example = x.result() if made else None
                with spans.span("dls.feed/map", self._sink):
                    if not made:
                        example = self._f(x)
                    if loose is None and _copy_rows(arrays, row, [example]):
                        row += 1
                    elif loose is None:
                        loose = []
                if loose is not None:
                    loose.append(example)
            result: Any = (range(start, row), loose)
        except BaseException as e:  # handed to the thread that waits
            result = e
        # or the thread that waits could wake, look for a free slot and find
        # this frame still referring to one (``data/feed._Slots``)
        del arrays
        fill._done(k, result)

    def __del__(self) -> None:
        # nobody can ask any more: what the pool has not begun does nothing
        self._closed = True
        if self._pool is not None:
            self._pool.shutdown(wait=False)


class _Stopped(Exception):
    """A run that gave up: a run of earlier rows had raised (that comes
    first in row order), or the fill was cancelled or its stream let go of
    (then nobody asks)."""


class _Fill:
    """What :meth:`_MappedStream.fill` hands back at once.

    ``taken`` says how many examples the stream had for the rows asked for
    (known when ``fill`` returns); :meth:`wait` blocks until every run has
    finished, so that no thread writes into ``arrays`` after it, and lets go
    of the arrays: the stream's own references must not make a feed's slot
    look busy (``data/feed._Slots`` reads reference counts)."""

    def __init__(self, arrays: dict[str, np.ndarray]):
        self.arrays: dict[str, np.ndarray] | None = arrays
        self.taken = 0
        self._results: list = []
        self._left = 0
        self._failed = None  # the first run, in row order, that raised
        self._lock = threading.Lock()
        self._finished = threading.Event()

    def _asked(self, runs: int) -> "_Fill":
        self._results = [None] * runs
        self._left = runs
        if not runs:
            self.arrays = None
            self._finished.set()
        return self

    def _done(self, k: int, result: Any) -> None:
        with self._lock:
            self._results[k] = result
            if (isinstance(result, BaseException)
                    and not isinstance(result, _Stopped)
                    and (self._failed is None or k < self._failed)):
                self._failed = k
            self._left -= 1
            if self._left:
                return
        self.arrays = None
        self._finished.set()

    def failed_before(self, k: int) -> bool:
        """A run of earlier rows raised (or the fill was cancelled): the
        batch is lost, and run ``k`` need not go on."""
        return self._failed is not None and self._failed < k

    def cancel(self) -> None:
        """Runs that have not finished stop at their next example."""
        with self._lock:
            self._failed = -1

    def wait(self) -> list:
        """Blocks until the last run has finished. Returns the rows asked
        for, in order, as pieces: a ``range`` of rows that were written, or
        a list of examples that did not fit the arrays (then ``np.stack``
        decides, in the caller). Raises what ``f`` raised, the first in row
        order, as ``next`` would have."""
        self._finished.wait()
        pieces: list = []
        for result in self._results:
            if isinstance(result, _Stopped):
                raise RuntimeError(
                    "these rows were given up: the fill was cancelled")
            if isinstance(result, BaseException):
                raise result
            rows, loose = result
            if rows:
                if pieces and isinstance(pieces[-1], range):
                    pieces[-1] = range(pieces[-1].start, rows.stop)
                else:
                    pieces.append(rows)
            if loose:
                pieces.append(loose)
        return pieces


class PartitionedDataset:
    """A lazy, partitioned dataset (RDD-shaped).

    ``infinite=True`` marks a dataset whose partitions never exhaust
    (``repeat()``); transformations propagate it. The multi-host feed uses it
    to skip walking non-local partitions (end-of-data can never need global
    agreement), which is what makes pod-scale input IO per-host-local.
    """

    def __init__(self, partition_fns: Sequence[PartitionFn], *,
                 infinite: bool = False):
        self._parts: tuple[PartitionFn, ...] = tuple(partition_fns)
        self._infinite = infinite

    @property
    def is_infinite(self) -> bool:
        return self._infinite

    # -- construction -------------------------------------------------------

    @staticmethod
    def parallelize(data: Sequence | Iterable, num_slices: int) -> "PartitionedDataset":
        """Split ``data`` into ``num_slices`` partitions (Spark's slicing rule:
        contiguous, sizes differing by at most one)."""
        if num_slices < 1:
            raise ValueError("num_slices must be >= 1")
        if isinstance(data, np.ndarray):
            chunks = np.array_split(data, num_slices)
            return PartitionedDataset([functools.partial(lambda c: c, c) for c in chunks])
        items = list(data)
        n = len(items)
        bounds = [(i * n // num_slices, (i + 1) * n // num_slices) for i in range(num_slices)]
        return PartitionedDataset(
            [functools.partial(lambda lo, hi: items[lo:hi], lo, hi) for lo, hi in bounds]
        )

    @staticmethod
    def from_generators(gens: Sequence[PartitionFn]) -> "PartitionedDataset":
        return PartitionedDataset(gens)

    # -- transformations (lazy) ---------------------------------------------

    def map(self, f: Callable[[Any], Any]) -> "PartitionedDataset":
        return self.map_partitions(lambda it: map(f, it))

    def filter(self, pred: Callable[[Any], bool]) -> "PartitionedDataset":
        return self.map_partitions(lambda it: filter(pred, it))

    def map_parallel(self, f: Callable[[Any], Any], *,
                     num_threads: int | None = None) -> "PartitionedDataset":
        """``map`` with a bounded thread pool per partition — order-preserving.

        The Spark analog of multiple task slots per executor: one Python
        process per host means a plain ``map`` decodes/augments on ONE core
        while the chip consumes thousands of examples/sec. ``f`` should be
        GIL-releasing work (PIL/numpy/the native C++ kernels all are) for
        real speedup. A sliding window of ``2×threads`` in-flight futures
        keeps memory bounded and works on infinite (``.repeat()``) streams —
        ``ThreadPoolExecutor.map`` would consume the whole iterator up
        front.

        ``num_threads`` 0/1 = plain serial map. The default divides the
        host's cores by the partition count — the feed opens every
        partition's iterator concurrently, so per-partition full-machine
        pools would oversubscribe by ``num_partitions×``. Compose
        ``.repeat()`` BEFORE this (like ``shuffle``) so one pool lives
        across epochs instead of draining and respawning per pass.

        **Which thread writes a row.** A partition's stream is an iterator
        with one more method, :meth:`_MappedStream.fill`: asked for the next
        ``n`` examples as rows of a batch's arrays, the pool's threads
        write what they made into the rows themselves, and the asking
        thread does nothing once an example (``data/feed.host_batches``
        asks where a shard is one such stream). Whoever iterates
        (``collect``, ``take``, ``write_array_records``, a loop) gets each
        result handed over by ``next``, as ever; both may be mixed on one
        stream and keep its order.
        """
        import os

        if num_threads in (0, 1):
            return self.map(f)
        workers = num_threads or min(
            32, max(1, (os.cpu_count() or 4) // max(self.num_partitions, 1)))

        return self.map_partitions(
            lambda it: _MappedStream(it, f, workers))

    def flat_map(self, f: Callable[[Any], Iterable[Any]]) -> "PartitionedDataset":
        return self.map_partitions(lambda it: itertools.chain.from_iterable(map(f, it)))

    def map_partitions(
        self, f: Callable[[Iterable[Any]], Iterable[Any]]
    ) -> "PartitionedDataset":
        """The reference's central primitive: the per-partition trainer is a
        ``mapPartitions`` closure (SURVEY.md §2 'Per-partition trainer')."""
        def wrap(part: PartitionFn) -> PartitionFn:
            return lambda: f(part())

        return PartitionedDataset([wrap(p) for p in self._parts],
                                  infinite=self._infinite)

    def map_partitions_with_index(
        self, f: Callable[[int, Iterable[Any]], Iterable[Any]]
    ) -> "PartitionedDataset":
        def wrap(i: int, part: PartitionFn) -> PartitionFn:
            return lambda: f(i, part())

        return PartitionedDataset([wrap(i, p) for i, p in enumerate(self._parts)],
                                  infinite=self._infinite)

    def batch(self, batch_size: int, *, drop_remainder: bool = True) -> "PartitionedDataset":
        """Group elements into lists of ``batch_size`` within each partition."""

        def batcher(it: Iterable[Any]) -> Iterator[list]:
            buf: list = []
            for x in it:
                buf.append(x)
                if len(buf) == batch_size:
                    yield buf
                    buf = []
            if buf and not drop_remainder:
                yield buf

        return self.map_partitions(batcher)

    def _require_finite(self, op: str) -> None:
        if self._infinite:
            raise ValueError(
                f"{op}() on an infinite (.repeat()) dataset would hang or "
                f"drop data — apply {op}() BEFORE .repeat()")

    def shuffle(self, seed: int = 0) -> "PartitionedDataset":
        """Per-partition shuffle (narrow; no cross-partition exchange —
        combine with interleaved partition assignment for global mixing).
        Shuffle BEFORE ``.repeat()`` (materializes each partition once)."""
        self._require_finite("shuffle")

        def shuf(i: int, it: Iterable[Any]) -> Iterable[Any]:
            items = list(it)
            random.Random(seed + i).shuffle(items)
            return items

        return self.map_partitions_with_index(shuf)

    def repeat(self, count: int | None = None) -> "PartitionedDataset":
        """Repeat each partition ``count`` times (None = forever)."""

        def rep(part: PartitionFn) -> PartitionFn:
            def gen() -> Iterator[Any]:
                if count is None:
                    while True:
                        yield from part()
                else:
                    for _ in range(count):
                        yield from part()

            return gen

        return PartitionedDataset([rep(p) for p in self._parts],
                                  infinite=count is None or self._infinite)

    def coalesce(self, num_partitions: int) -> "PartitionedDataset":
        """Reduce partition count by concatenating adjacent partitions."""
        self._require_finite("coalesce")
        if num_partitions >= self.num_partitions:
            return self
        groups = np.array_split(np.arange(self.num_partitions), num_partitions)
        parts = self._parts

        def make(idx: np.ndarray) -> PartitionFn:
            return lambda: itertools.chain.from_iterable(parts[i]() for i in idx)

        return PartitionedDataset([make(g) for g in groups],
                                  infinite=self._infinite)

    def union(self, other: "PartitionedDataset") -> "PartitionedDataset":
        """Spark ``union``: concatenate partition lists (no dedup, no
        shuffle — exactly Spark's semantics; partition count is the sum)."""
        if self._infinite or other._infinite:
            raise ValueError("union() with an infinite (.repeat()) dataset "
                             "would never yield the other side's rows")
        return PartitionedDataset(self._parts + other._parts)

    def sample(self, fraction: float, seed: int = 0) -> "PartitionedDataset":
        """Spark ``sample(withReplacement=False)``: keep each element with
        probability ``fraction``, independently per element (deterministic
        per seed+partition; narrow, no materialization)."""
        if not 0.0 <= fraction <= 1.0:
            raise ValueError(f"fraction must be in [0, 1], got {fraction}")

        def samp(i: int, it: Iterable[Any]) -> Iterator[Any]:
            rng = random.Random((seed << 16) ^ i)
            return (x for x in it if rng.random() < fraction)

        return self.map_partitions_with_index(samp)

    def distinct(self, *, num_workers: int | None = None,
                 transport: str | None = None) -> "PartitionedDataset":
        """Spark ``distinct`` (hashable elements).

        With workers (``num_workers=`` / ``DLS_DATA_WORKERS``): the
        distributed exchange dedups per bucket with spill-to-disk — no
        cardinality ceiling; output is hash-partitioned over the input's
        partition count in canonical ``key_bytes`` order. Plain
        ``int``/``float`` element batches ride the columnar transport
        (flat key-hash + key planes, vectorized dedup) unless
        ``transport="tuple"`` forces the pickled path — output identical
        either way.

        Serial: per-partition dedup plus a driver-side cross-partition set
        on first iteration; output keeps first-occurrence order and
        collapses to partition 0, like ``distinct().coalesce(1)``. The set
        is bounded by the ``max_groups`` ceiling — past it the scan refuses
        loudly (a user-id-like stream would otherwise grow an unbounded
        driver set, the same bug class ``max_groups`` guards in agg)."""
        self._require_finite("distinct")
        from distributeddeeplearningspark_tpu.data import exchange

        nw = exchange.resolve_shuffle_workers(num_workers)
        if nw:
            return exchange.distinct(self, nw, transport=transport)
        parts = self._parts
        limit = exchange.max_groups_limit()

        def gen() -> Iterator[Any]:
            seen: set = set()
            for p in parts:
                for x in p():
                    if x not in seen:
                        if len(seen) >= limit:
                            raise ValueError(exchange.serial_refusal(
                                "distinct()", limit, "distinct elements"))
                        seen.add(x)
                        yield x

        return PartitionedDataset([gen])

    def cache(self) -> "PartitionedDataset":
        """Spark ``cache()``: materialize each partition on first iteration
        and serve subsequent iterations from memory — for small/medium
        driver-side data (vocab builds, eval sets iterated per epoch). The
        ARRAY-scale analog is the record path (`data/records.py`
        write-once materialization); use that for image/token corpora."""
        self._require_finite("cache")

        def cached(part: PartitionFn) -> PartitionFn:
            store: list = []
            done = [False]

            def gen() -> Iterator[Any]:
                if done[0]:
                    return iter(store)

                def fill() -> Iterator[Any]:
                    # build into a LOCAL list and commit atomically on
                    # completion: consumers may stop mid-way (take(n)) or
                    # interleave two live iterators — a shared store would
                    # be corrupted by the second filler (r4 review repro)
                    tmp: list = []
                    for x in part():
                        tmp.append(x)
                        yield x
                    store[:] = tmp
                    done[0] = True

                return fill()

            return gen

        return PartitionedDataset([cached(p) for p in self._parts])

    def _hash_partitioned_by_key(
        self, op: str, num_partitions: int | None,
        build: Callable[[], dict],
    ) -> "PartitionedDataset":
        """Serial-path scaffolding for the byKey ops: validate, ``build()``
        the full key→value dict ONCE (memoized, cache() semantics — else
        each output partition would re-walk the input), bucket it ONCE by
        the exchange's canonical :func:`~.data.exchange.key_bytes` hash
        (deterministic across processes AND runs — ``hash()`` moves with
        ``PYTHONHASHSEED``) sorted by that key within each bucket, and
        serve bucket ``i`` as partition ``i``. This is byte-for-byte the
        layout the distributed exchange emits, so a run is reproducible at
        any worker count."""
        self._require_finite(op)
        if num_partitions is not None and num_partitions < 1:
            raise ValueError(f"num_partitions must be >= 1, got {num_partitions}")
        n_out = num_partitions or len(self._parts)
        memo: dict = {}

        def buckets() -> list:
            if "b" not in memo:
                from distributeddeeplearningspark_tpu.data import exchange

                b: list = [[] for _ in range(n_out)]
                for k, v in build().items():
                    kb = exchange.key_bytes(k)
                    b[exchange.bucket_of(kb, n_out)].append((kb, k, v))
                memo["b"] = [[(k, v) for _kb, k, v in sorted(
                    bi, key=lambda t: t[0])] for bi in b]
            return memo["b"]

        def make(idx: int) -> PartitionFn:
            return lambda: iter(buckets()[idx])

        return PartitionedDataset([make(i) for i in range(n_out)])

    def reduce_by_key(self, f: Callable[[Any, Any], Any],
                      num_partitions: int | None = None, *,
                      num_workers: int | None = None,
                      combine: str | None = None,
                      transport: str | None = None) -> "PartitionedDataset":
        """Spark ``reduceByKey`` over (key, value) pairs. ``f`` must be
        commutative + associative (Spark's own contract).

        With workers (``num_workers=`` / ``DLS_DATA_WORKERS``): routed
        through the distributed exchange (:mod:`~.data.exchange`) — mappers
        combine per partition slice, bucketed partials stream to per-bucket
        reducers that spill to disk under ``DLS_SHUFFLE_MEM_MB``. No
        cardinality ceiling.

        ``combine`` declares ``f``'s numeric semantics (``"sum"`` /
        ``"min"`` / ``"max"``) so conforming batches — plain ``int`` /
        ``float`` scalar keys AND values — can ride the **columnar
        transport**: flat key-hash/key/value planes, vectorized
        segment-combine, an order of magnitude past the pickled-tuple
        ceiling. The declaration is a contract exactly like commutativity
        is: an ``f`` that disagrees with it diverges between paths, and
        that is the caller's bug. Undeclared (or ``transport="tuple"``)
        keeps the pickled path; non-conforming batches fall back to it
        per batch either way, byte-identically.

        Serial: values combine per-partition first (Spark's map-side
        combine), then the per-partition partials merge in a driver-side
        dict, refusing past the ``max_groups`` ceiling
        (``DLS_AGG_MAX_GROUPS``) with the exchange as the first
        remediation. Output is hash-partitioned over ``num_partitions``
        (default: the input's count) in canonical key order — identical on
        both paths.
        """
        self._require_finite("reduce_by_key")
        from distributeddeeplearningspark_tpu.data import exchange

        if num_partitions is not None and num_partitions < 1:
            raise ValueError(f"num_partitions must be >= 1, got {num_partitions}")
        if combine is not None and combine not in exchange.NUMERIC_COMBINES:
            raise ValueError(
                f"combine={combine!r} not in {exchange.NUMERIC_COMBINES}")
        nw = exchange.resolve_shuffle_workers(num_workers)
        if nw:
            return exchange.reduce_by_key(
                self, f, num_partitions or len(self._parts), nw,
                combine=combine, transport=transport)
        parts = self._parts
        limit = exchange.max_groups_limit()

        def merged() -> dict:
            acc: dict = {}
            for p in parts:
                # map-side combine per partition, then fold into the global
                local: dict = {}
                for k, v in p():
                    local[k] = f(local[k], v) if k in local else v
                for k, v in local.items():
                    if k not in acc and len(acc) >= limit:
                        raise ValueError(exchange.serial_refusal(
                            "reduce_by_key()", limit))
                    acc[k] = f(acc[k], v) if k in acc else v
            return acc

        return self._hash_partitioned_by_key(
            "reduce_by_key", num_partitions, merged)

    def group_by_key(self, num_partitions: int | None = None, *,
                     num_workers: int | None = None) -> "PartitionedDataset":
        """Spark ``groupByKey``: (key, [values...]) with values in
        partition-major encounter order (on BOTH paths: the exchange tags
        each value with its source position and sorts lists back at emit).
        The Spark guidance applies: prefer ``reduce_by_key`` when the
        downstream op is a fold, since grouping materializes every value
        list. Serial build is a direct dict-of-lists (appends), NOT
        reduce_by_key(list concat) — that fold copies the accumulated
        prefix per element, O(m²) on a hot key — and refuses past the
        ``max_groups`` distinct-key ceiling.
        """
        self._require_finite("group_by_key")
        from distributeddeeplearningspark_tpu.data import exchange

        if num_partitions is not None and num_partitions < 1:
            raise ValueError(f"num_partitions must be >= 1, got {num_partitions}")
        nw = exchange.resolve_shuffle_workers(num_workers)
        if nw:
            return exchange.group_by_key(
                self, num_partitions or len(self._parts), nw)
        parts = self._parts
        limit = exchange.max_groups_limit()

        def grouped() -> dict:
            acc: dict = {}
            for p in parts:
                for k, v in p():
                    if k not in acc and len(acc) >= limit:
                        raise ValueError(exchange.serial_refusal(
                            "group_by_key()", limit))
                    acc.setdefault(k, []).append(v)
            return acc

        return self._hash_partitioned_by_key(
            "group_by_key", num_partitions, grouped)

    def sort_by(self, key: Callable[[Any], Any], *, ascending: bool = True,
                num_partitions: int | None = None,
                num_workers: int | None = None) -> "PartitionedDataset":
        """Spark ``sortBy``: totally ordered output, range-partitioned so
        partition i's elements all precede partition i+1's (the property
        Spark's sort guarantees; descending reverses it).

        With workers: a range-partitioned external sort through the
        exchange — boundaries from a deterministic sample pass, per-bucket
        spill-to-disk sorted runs + k-way merge, so the sort never
        materializes driver-side. The concatenated stream is identical to
        the serial sort (equal keys keep encounter order); partition
        BOUNDARIES fall on sample quantiles rather than exact equal splits.

        Serial: driver-side sort, sized for driver-scale data like metric
        tables and vocab builds — refuses past the ``max_groups`` ceiling
        (here a total-element bound: a sort materializes everything).
        """
        self._require_finite("sort_by")
        from distributeddeeplearningspark_tpu.data import exchange

        if num_partitions is not None and num_partitions < 1:
            raise ValueError(f"num_partitions must be >= 1, got {num_partitions}")
        n_out = num_partitions or len(self._parts)
        nw = exchange.resolve_shuffle_workers(num_workers)
        if nw:
            return exchange.sort_by(self, key, ascending=ascending,
                                    n_out=n_out, num_workers=nw)
        parts = self._parts
        limit = exchange.max_groups_limit()
        memo: dict = {}  # sort once (cache() semantics), see reduce_by_key

        def sorted_all() -> list:
            if "data" not in memo:
                data: list = []
                for p in parts:
                    for x in p():
                        if len(data) >= limit:
                            raise ValueError(exchange.serial_refusal(
                                "sort_by()", limit, "materialized elements"))
                        data.append(x)
                data.sort(key=key, reverse=not ascending)
                memo["data"] = data
            return memo["data"]

        def make(idx: int) -> PartitionFn:
            def gen() -> Iterator[Any]:
                data = sorted_all()
                per = -(-len(data) // n_out) or 1
                return iter(data[idx * per:(idx + 1) * per])
            return gen

        return PartitionedDataset([make(i) for i in range(n_out)])

    def zip_with_index(self) -> "PartitionedDataset":
        """(elem, global_index) pairs; forces a driver count of prior partitions."""
        self._require_finite("zip_with_index")
        sizes = [sum(1 for _ in p()) for p in self._parts]
        offsets = list(itertools.accumulate([0] + sizes[:-1]))

        def zipper(i: int, it: Iterable[Any]) -> Iterator[tuple]:
            return ((x, offsets[i] + j) for j, x in enumerate(it))

        return self.map_partitions_with_index(zipper)

    # -- actions (eager, driver-side) ---------------------------------------

    @property
    def num_partitions(self) -> int:
        return len(self._parts)

    def iter_partition(self, i: int) -> Iterator[Any]:
        return iter(self._parts[i]())

    def collect(self) -> list:
        self._require_finite("collect")
        return [x for p in self._parts for x in p()]

    def count(self) -> int:
        self._require_finite("count")
        return sum(sum(1 for _ in p()) for p in self._parts)

    def take(self, n: int) -> list:
        out: list = []
        for p in self._parts:
            for x in p():
                out.append(x)
                if len(out) == n:
                    return out
        return out

    def first(self) -> Any:
        taken = self.take(1)
        if not taken:
            raise ValueError("empty dataset")
        return taken[0]

    def reduce(self, f: Callable[[Any, Any], Any]) -> Any:
        return functools.reduce(f, self.collect())

    def tree_aggregate(
        self,
        zero: Any,
        seq_op: Callable[[Any, Any], Any],
        comb_op: Callable[[Any, Any], Any],
    ) -> Any:
        """Spark ``treeAggregate``: per-partition fold, then driver combine.

        This is the reference PR1 gradient-aggregation path (SURVEY.md §3.1);
        kept for the CPU parity mode and tests, not for the SPMD hot loop.
        """
        import copy

        per_part = []
        for p in self._parts:
            acc = copy.deepcopy(zero)
            for x in p():
                acc = seq_op(acc, x)
            per_part.append(acc)
        return functools.reduce(comb_op, per_part)

    def foreach_partition(self, f: Callable[[Iterable[Any]], None]) -> None:
        for p in self._parts:
            f(p())

    # -- pyspark camelCase aliases ------------------------------------------

    mapPartitions = map_partitions
    mapPartitionsWithIndex = map_partitions_with_index
    flatMap = flat_map
    treeAggregate = tree_aggregate
    zipWithIndex = zip_with_index
    foreachPartition = foreach_partition
    reduceByKey = reduce_by_key
    groupByKey = group_by_key
    sortBy = sort_by

    def getNumPartitions(self) -> int:
        """pyspark spells this as a method; kept callable for ported code."""
        return self.num_partitions

    def __repr__(self) -> str:
        return f"PartitionedDataset(num_partitions={self.num_partitions})"
