"""Partition → mesh feed: global batches laid out with batch sharding.

The reference streams RDD partition iterators into each executor's GPU
(SURVEY.md §1 L5). Here, partitions are host-side iterators of example dicts
(``{"image": ..., "label": ...}``, numpy); this module assembles them into
*global* batches and places them on the mesh with the leading axis sharded
over (data, fsdp) — the GSPMD equivalent of "each executor trains on its
partition".

Two assembly modes:

- **aligned** (default when ``num_partitions`` divides evenly into the data
  shards): partition *i* feeds data shard ``i % num_shards``, preserving
  Spark's partition↔task pairing — shard-local data stays shard-local.
- **chained**: partitions are concatenated into one stream and dealt out in
  order. Used when partition count and mesh shape don't line up.

Multi-process placement uses ``jax.make_array_from_process_local_data`` so
each host only materializes its addressable shard of the global batch.
"""

from __future__ import annotations

import itertools
import sys
from typing import Any, Iterable, Iterator

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributeddeeplearningspark_tpu.parallel.mesh import BATCH_AXES, num_data_shards
from distributeddeeplearningspark_tpu.rdd import PartitionedDataset
from distributeddeeplearningspark_tpu.telemetry import spans


def process_shard_range(num_shards: int) -> tuple[int, int] | None:
    """This process's data-shard slice [lo, hi), or None when single-process.

    Mesh device order is process-major (jax.devices() sorts by id, ids are
    assigned per process), so each process's addressable batch rows are one
    contiguous run of shards.
    """
    pc = jax.process_count()
    if pc == 1:
        return None
    if num_shards % pc:
        raise ValueError(
            f"data shards ({num_shards}) must divide evenly across {pc} processes"
        )
    spp = num_shards // pc
    return (jax.process_index() * spp, (jax.process_index() + 1) * spp)


def stack_examples(examples: list[dict[str, Any]]) -> dict[str, np.ndarray]:
    keys = examples[0].keys()
    try:
        return {k: np.stack([np.asarray(e[k]) for e in examples])
                for k in keys}
    except KeyError as e:
        # an ETL stream that mis-joins features (e.g. a DLRM pipeline
        # unioning positive/negative example sources with different
        # fields) fails here with a bare KeyError that names neither the
        # batch nor the fix — diagnose the schema drift instead
        schemas = {tuple(sorted(ex.keys())) for ex in examples}
        raise ValueError(
            f"batch examples disagree on their keys (missing {e}); "
            f"schemas in this batch: {sorted(schemas)} — every example "
            f"dict in a stream must carry the same fields") from e


def _stack(examples: list[dict[str, Any]]) -> dict[str, np.ndarray]:
    """``stack_examples`` as the feed's remainder paths call it: one
    ``dls.feed/stack`` section of the probe whose thread pulls these
    batches, if any."""
    with spans.span("dls.feed/stack", spans.bound_sink()):
        return stack_examples(examples)


def _most_referred(arrays: dict[str, np.ndarray]) -> int:
    return max(map(sys.getrefcount, arrays.values()), default=0)


#: what :func:`_most_referred` reads of arrays that only their dict refers to
_UNSHARED = _most_referred({"x": np.empty(0)})
#: batches of arrays a stream keeps to fill again: the default prefetch
#: ring's three, the one its consumer holds, the one being filled and, where
#: a mapping pool fills the rows, the one begun ahead of it (the pool gets the
#: next batch's rows before this batch is waited for, so it never drains)
_KEPT_SLOTS = 6
#: examples are copied into their batch this many bytes at a time: one image
#: as it arrives, a whole batch of token windows with one ``np.stack`` a leaf
_COPY_BYTES = 1 << 20


class _Slots:
    """The memory one ``host_batches`` stream fills its batches into.

    A slot is the arrays of one batch. The stream keeps up to
    ``_KEPT_SLOTS`` of them and writes a slot again only when nothing but
    the slot itself refers to its arrays, which it reads off their
    reference counts: a batch somebody kept, a view of one, a
    ``device_put`` still reading one (jax holds the numpy argument until
    the transfer is done) and a CPU device array aliasing one all count.
    Where every kept slot is referred to, the batch gets new memory, as
    every batch did before there were slots.
    """

    def __init__(self) -> None:
        #: rows, then ``(key, shape, dtype)`` of each leaf of an example
        self._signature: tuple | None = None
        self._kept: list[dict[str, np.ndarray]] = []
        #: examples of the last batch's size that make ``_COPY_BYTES``
        self.examples_a_copy = 1

    def knows(self, rows: int) -> bool:
        """A batch of ``rows`` rows has been shaped before: :meth:`take`
        needs no first example."""
        return self._signature is not None and self._signature[0] == rows

    def forget(self) -> None:
        """The next batch is shaped by its own first example again."""
        self._signature = None

    def take(self, first: dict[str, Any] | None, rows: int,
             sink) -> dict[str, np.ndarray]:
        """Arrays of ``rows`` rows shaped like ``first``, a batch's first
        example, or like the last batch's where ``first`` is None (their
        contents are whatever was there). The section is
        ``dls.feed/slot_reused`` or ``dls.feed/slot_new``; the feed's
        probe counts them."""
        if first is not None:
            leaves = {k: np.asarray(v) for k, v in first.items()}
            signature = (rows, *((k, a.shape, a.dtype)
                                 for k, a in leaves.items()))
            if signature != self._signature:  # the stream changed its shapes
                self._signature, self._kept = signature, []
                self.examples_a_copy = max(1, _COPY_BYTES // max(
                    1, sum(a.nbytes for a in leaves.values())))
        free = next((s for s in self._kept if _most_referred(s) == _UNSHARED),
                    None)
        with spans.span("dls.feed/slot_reused" if free is not None
                        else "dls.feed/slot_new", sink):
            if free is None:
                free = {k: np.empty((rows, *shape), dtype)
                        for k, shape, dtype in self._signature[1:]}
                if len(self._kept) < _KEPT_SLOTS:
                    self._kept.append(free)
            # the caller's own dict: what it does to it leaves the slot whole
            return dict(free)


def _copy_rows(arrays: dict[str, np.ndarray], at: int,
               examples: list[dict[str, Any]]) -> bool:
    """``examples`` into rows ``at``... of ``arrays``; False where
    ``np.stack`` would not have given these arrays (a key missing, another
    shape, a dtype to promote). numpy copies a large row without the
    interpreter lock."""
    try:
        for k, a in arrays.items():
            rows = [np.asarray(e[k]) for e in examples]
            if (len(rows) == 1 and rows[0].shape == a.shape[1:]
                    and rows[0].dtype == a.dtype):
                # what np.stack would do, without its wrapper: beside a
                # busy pool that costs the producer 3% of its images
                a[at] = rows[0]
            else:
                np.stack(rows, out=a[at:at + len(rows)], casting="no")
    except (KeyError, TypeError, ValueError):
        return False
    return True


class _Assembly:
    """One batch out of ``segments``, each ``(stream, rows, local)``: begun
    when made, whole when :meth:`finish` returns.

    ``rows`` examples are taken from each stream in turn. A local segment
    whose stream offers ``fill`` (a :meth:`~..rdd.PartitionedDataset.
    map_parallel` partition) is ASKED for its rows: the stream's pool
    writes each example it makes into its row of the batch's arrays, this
    thread does nothing once an example, and :meth:`finish` waits for the
    pool once (``asked``; the batch's arrays are shaped like the last
    batch's, and a stream's first batch takes one example through ``next``
    to shape them by). From any other stream the local examples are pulled
    and copied into their rows as they arrive, ``_COPY_BYTES`` at a time,
    and let go at once, so a worker pool's ring never carries a batch of
    views; the segments of other hosts are only walked (every host advances
    every shard, see :func:`host_batches`). What decides is what the stream
    can do, looked at once a segment.
    """

    def __init__(self, segments: list[tuple[Iterator, int, bool]],
                 slots: "_Slots"):
        self._slots = slots
        self._arrays: dict[str, np.ndarray] | None = None
        #: a segment's examples, in order, in pieces: a range of rows they
        #: were copied to, a list of loose examples, or what a ``fill``
        #: handed back, which becomes pieces when waited for
        self._parts: list[tuple[list, bool]] = []
        self.short = False   # a stream ran out inside this batch
        self.asked = False   # some rows are a pool's to write
        self._by_map = True  # ... and every local row is
        try:
            self._begin(segments, spans.bound_sink())
        except BaseException:
            self.abandon()
            raise

    def _begin(self, segments, sink) -> None:
        local_rows = sum(rows for _, rows, local in segments if local)
        filled = 0
        loose = False  # some examples did not fit the arrays: np.stack decides
        for stream, rows, local in segments:
            fill = getattr(stream, "fill", None) if local and not loose else None
            pieces: list = []
            self._parts.append((pieces, local))
            if fill is not None:
                got = 0
                if not self._slots.knows(local_rows) and self._arrays is None:
                    self._by_map = False
                    first = list(itertools.islice(stream, 1))
                    if first:
                        with spans.span("dls.feed/stack", sink):
                            self._arrays = self._slots.take(
                                first[0], local_rows, sink)
                            _copy_rows(self._arrays, filled, first)
                        pieces.append(range(filled, filled + 1))
                        got = 1
                elif self._arrays is None:
                    with spans.span("dls.feed/stack", sink):
                        self._arrays = self._slots.take(None, local_rows, sink)
                if self._arrays is not None:
                    asked = fill(self._arrays, filled + got, rows - got)
                    pieces.append(asked)
                    self.asked = True
                    got += asked.taken
                filled += got
            elif not local or loose:
                pieces.append(list(itertools.islice(stream, rows)))
                got = len(pieces[0])
            else:
                self._by_map = False
                pulled = itertools.islice(stream, rows)
                got = 0
                while group := list(itertools.islice(
                        pulled, self._slots.examples_a_copy)):
                    with spans.span("dls.feed/stack", sink):
                        if self._arrays is None:
                            self._arrays = self._slots.take(
                                group[0], local_rows, sink)
                        loose = not _copy_rows(self._arrays, filled + got,
                                               group)
                    if loose:
                        break
                    got += len(group)
                pieces.append(range(filled, filled + got))
                filled += got
                if loose:
                    pieces.append([*group, *pulled])
                    got += len(pieces[1])
            self.short |= got < rows

    def _waited(self) -> list[tuple[list, bool]]:
        """Every segment's pieces once no pool thread writes any more: the
        first exception in row order is raised after the last has been
        waited for."""
        parts, self._parts = self._parts, []
        waited: list[tuple[list, bool]] = []
        failed = None
        for pieces, local in parts:
            out: list = []
            for piece in pieces:
                if isinstance(piece, (list, range)):
                    out.append(piece)
                    continue
                try:
                    out.extend(piece.wait())
                except BaseException as e:  # noqa: BLE001 — raised below
                    failed = failed or e
            waited.append((out, local))
        if failed is not None:
            self._arrays = None
            raise failed
        return waited

    def abandon(self) -> None:
        """Give the batch up; returns when no thread writes into it."""
        for pieces, _ in self._parts:
            for piece in pieces:
                if not isinstance(piece, (list, range)):
                    piece.cancel()
        try:
            self._waited()
        except BaseException:  # noqa: BLE001 — the batch is given up
            pass
        self._arrays = None

    def finish(self) -> tuple[dict[str, np.ndarray] | None, list | None]:
        """``(batch, None)``, or ``(None, rest)`` with every example taken,
        in order, when a stream ran short: the remainder paths stack
        those (the rows that were filled, as views of them)."""
        parts = self._waited()
        arrays, self._arrays = self._arrays, None
        loose = any(isinstance(piece, list) and piece
                    for pieces, local in parts if local for piece in pieces)
        if not self.short and not loose:
            if self.asked and self._by_map:
                with spans.span("dls.feed/filled_by_map", spans.bound_sink()):
                    pass
            return arrays, None
        examples = [[e for piece in pieces for e in _rows_of(arrays, piece)]
                    for pieces, _ in parts]
        if self.short:
            return None, [e for part in examples for e in part]
        if self.asked:  # what a row is has changed: look at the next again
            self._slots.forget()
        return _stack([e for part, (_, local) in zip(examples, parts)
                       if local for e in part]), None


def _rows_of(arrays: dict[str, np.ndarray] | None, part: list | range) -> list:
    """A segment's examples: themselves, or as views of the rows they were
    copied to."""
    if isinstance(part, list):
        return part
    return [{k: a[i] for k, a in arrays.items()} for i in part]


def _round_robin(iters: list[Iterator]) -> Iterator:
    """Deal elements from iterators in turn; drained ones drop out so uneven
    partitions lose no data (matches Spark consuming every partition fully)."""
    active = list(iters)
    while active:
        still = []
        for it in active:
            try:
                yield next(it)
                still.append(it)
            except StopIteration:
                pass
        active = still


def _pad_to_shards(
    rest: list[dict[str, Any]], num_shards: int
) -> dict[str, np.ndarray]:
    """Stack a sub-shard remainder padded to a ``num_shards`` multiple.

    Pad rows are copies of row 0 carrying ``eval_mask == 0.0`` (real rows
    carry 1.0); every contract loss downweights masked rows to exactly
    nothing (train/losses.py), so the padded batch's weighted metrics equal
    the unpadded remainder's — GSPMD gets its equal shard sizes without a
    single dropped row (VERDICT r3 missing-#5).
    """
    n = len(rest)
    target = -(-n // num_shards) * num_shards
    batch = _stack(rest + [rest[0]] * (target - n))
    if "eval_mask" in batch:
        raise ValueError(
            "'eval_mask' is reserved for remainder padding — rename the "
            "dataset key or pass pad_remainder=False")
    batch["eval_mask"] = (np.arange(target) < n).astype(np.float32)
    return batch


def host_batches(
    dataset: PartitionedDataset,
    batch_size: int,
    *,
    num_shards: int = 1,
    drop_remainder: bool = True,
    shard_range: tuple[int, int] | None = None,
    pad_remainder: bool = False,
    num_workers: int | None = None,
) -> Iterator[dict[str, np.ndarray]]:
    """Yield stacked host batches from an RDD of example dicts.

    Each batch is what :func:`stack_examples` gives for its examples, filled
    a row at a time (:class:`_Assembly`). **Which thread writes a row.**
    Where a shard of this host is ONE partition of a ``map_parallel``
    dataset (``imagenet_train``; its stream offers ``fill``), the pool's
    threads write each example they make into its row, the thread that
    pulls these batches does nothing once an example, and the next batch's
    rows are handed to the pool before this batch is waited for, so two
    batches are being filled at once. Every other stream stays on the pull
    path, where the pulling thread copies the examples into their rows as
    they arrive: plain iterators (token windows), a
    :class:`~.workers.WorkerMappedDataset`'s ring views, several partitions
    chained into one stream, and more than one partition dealt in turn to
    a shard. The bytes are the same either way. **Whose memory a batch
    is:** the caller's, for as long as it refers to it. The stream fills
    its batches into a few kept sets of arrays (:class:`_Slots`) and writes
    one again only once nothing refers to its arrays any more: not the
    batch's dict, not a view of a leaf, not a transfer or a device array
    that jax made from one. A caller that keeps every batch gets new memory
    for each, as before. A batch is handed on only when every thread that
    writes into it has finished, and when the stream ends, however it ends,
    no thread writes into any of them any more.

    ``num_workers`` overrides the worker-process count of a pool-backed
    dataset (:class:`~.workers.WorkerMappedDataset`, e.g. from
    ``imagenet_train(num_workers=...)``): the per-example map fans out over
    that many processes with shared-memory delivery, and each ring view is
    copied straight into its row of the batch. ``None`` keeps the
    dataset's own setting (ultimately ``DLS_DATA_WORKERS``); 0 forces the
    in-process path. The batch stream is byte-identical either way —
    ordered delivery is part of the pool contract — so this knob is pure
    throughput. On a dataset without a pool spec it is ignored (there is
    no map to fan out).

    ``shard_range=(lo, hi)`` restricts output to data shards [lo, hi) — the
    multi-process mode: each host STACKS only the rows its own devices will
    hold (``batch_size`` stays the GLOBAL batch size), as each Spark executor
    trains only its own partitions. Every host still *advances* all shard
    streams in lockstep so that end-of-data is decided identically everywhere
    — uneven shards must never let one host yield a batch its peers don't,
    or the stragglers hang in the next collective. The partition→shard mapping
    is global (partition *i* → shard ``i % num_shards``).

    ``pad_remainder`` (eval exactness): a final batch that cannot fill every
    shard equally is padded with ``eval_mask == 0`` rows instead of dropping
    the sub-shard tail (see :func:`_pad_to_shards`) — including in
    multi-process mode, where the tail was previously dropped whole.
    """
    if num_workers is not None and hasattr(dataset, "with_num_workers"):
        dataset = dataset.with_num_workers(num_workers)
    n_parts = dataset.num_partitions
    lo, hi = shard_range if shard_range is not None else (0, num_shards)

    def checked(batch: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
        """Non-padded yields under pad_remainder: the reserved key must be
        rejected on EVERY batch, not only when a remainder happens to occur
        — otherwise a dataset carrying its own 'eval_mask' column is
        silently reinterpreted as pad weights on exactly-divisible sizes
        and errors data-size-dependently on others."""
        if pad_remainder and "eval_mask" in batch:
            raise ValueError(
                "'eval_mask' is reserved for remainder padding — rename "
                "the dataset key or pass pad_remainder=False")
        return batch
    if shard_range is not None and batch_size % num_shards:
        raise ValueError(
            f"multi-process feed needs batch_size ({batch_size}) divisible by "
            f"num_shards ({num_shards})"
        )
    # partitions line up with shards: partition i → shard (i % num_shards),
    # and a lockstep draw keeps the pairing. Else the chained fallback: every
    # host walks the same global stream in order and keeps only its shards'
    # rows — correct but not bandwidth-minimal; align partitions to shards
    # to avoid it.
    aligned = (n_parts % num_shards == 0 and batch_size % num_shards == 0
               and n_parts > 1)
    per_shard = batch_size // num_shards
    if aligned:
        # Infinite dataset (.repeat(), the training config): end-of-data can
        # never need cross-host agreement, so this host opens and walks ONLY
        # its own shards' partitions — per-host-local input IO at pod scale
        # (VERDICT r1 weak-5: the lockstep walk is for finite datasets only).
        local_only = (getattr(dataset, "is_infinite", False)
                      and shard_range is not None)
        segments = []
        for s in range(num_shards):
            if local_only and not (lo <= s < hi):
                continue
            g = [dataset.iter_partition(i)
                 for i in range(s, n_parts, num_shards)]
            segments.append((_round_robin(g) if len(g) > 1 else g[0],
                             per_shard, lo <= s < hi))
    else:
        # one partition is handed on as its own iterator: chaining would
        # hide what it can do (``fill``)
        stream = (dataset.iter_partition(0) if n_parts == 1
                  else itertools.chain.from_iterable(
                      dataset.iter_partition(i) for i in range(n_parts)))
        if shard_range is None:
            segments = [(stream, batch_size, True)]
        else:
            segments = [(stream, lo * per_shard, False),
                        (stream, (hi - lo) * per_shard, True),
                        (stream, (num_shards - hi) * per_shard, False)]
    slots = _Slots()
    begun: list[_Assembly] = []  # the batch being filled, the one ahead
    failed: BaseException | None = None
    try:
        while failed is None:
            if not begun:
                begun.append(_Assembly(segments, slots))
            if begun[0].asked and not begun[0].short:
                # a pool is writing this batch's rows: it gets the next
                # batch's before this one is waited for, or it would drain
                # at every batch's end. What that raises waits its turn.
                try:
                    begun.append(_Assembly(segments, slots))
                except Exception as e:  # noqa: BLE001 — raised below
                    failed = e
            batch, rest = begun.pop(0).finish()
            if batch is None:
                break
            yield checked(batch)
            # or this frame would still refer to the slot it has just
            # handed on
            del batch
        if failed is not None:
            raise failed
    finally:
        # no pool thread writes once this stream has ended, however it did
        for each in begun:
            each.abandon()
        del begun[:]
    if not rest or drop_remainder:
        return
    if pad_remainder:
        batch = _pad_to_shards(rest, num_shards)
        if shard_range is not None:
            per = batch["eval_mask"].shape[0] // num_shards
            batch = {k: v[lo * per:hi * per] for k, v in batch.items()}
        yield batch
    elif shard_range is None:
        # legacy mode; shards in lockstep keep only what divides evenly
        # across them (GSPMD needs equal shard sizes)
        keep = len(rest) - (len(rest) % num_shards if aligned else 0)
        if keep:
            yield _stack(rest[:keep])


def put_global(
    batch: dict[str, np.ndarray], mesh: Mesh, *, seq_sharded: bool = False
) -> dict[str, jax.Array]:
    """Place a host batch onto the mesh with batch sharding.

    Single-process: a plain sharded ``device_put`` (XLA slices per device).
    Multi-process: each process passes its *local* rows and JAX assembles the
    global array — the moral replacement for "each executor reads its own
    partition" with zero driver round-trip.

    ``seq_sharded`` (context parallelism): rank≥2 leaves additionally split
    dim 1 over the ``seq`` mesh axis; rank-1 leaves stay batch-only.
    """
    from distributeddeeplearningspark_tpu.parallel.mesh import batch_sharding

    def sharding_for(v) -> NamedSharding:
        return batch_sharding(mesh, np.ndim(v), seq_sharded=seq_sharded)

    if jax.process_count() > 1:
        return {
            k: jax.make_array_from_process_local_data(sharding_for(v), v)
            for k, v in batch.items()
        }
    return {k: jax.device_put(v, sharding_for(v)) for k, v in batch.items()}


def device_batches(
    dataset: PartitionedDataset,
    mesh: Mesh,
    batch_size: int,
    *,
    drop_remainder: bool = True,
    probe=None,
    num_workers: int | None = None,
) -> Iterator[dict[str, jax.Array]]:
    """host_batches → sharded device arrays (no prefetch; see prefetch.py).

    ``probe`` (a :class:`~.prefetch.StarvationProbe`) times each host-batch
    assembly — on this unbuffered path every assembly blocks the consumer,
    so the same wait the prefetch ring would hide is measured directly.
    ``num_workers`` passes through to :func:`host_batches` (worker-pool
    override for pool-backed datasets).
    """
    nshards = num_data_shards(mesh)
    hb: Iterator[dict[str, np.ndarray]] = host_batches(
        dataset, batch_size, num_shards=nshards, drop_remainder=drop_remainder,
        shard_range=process_shard_range(nshards), num_workers=num_workers,
    )
    if probe is not None:
        hb = probe.timed(hb)
    for b in hb:
        yield put_global(b, mesh)
