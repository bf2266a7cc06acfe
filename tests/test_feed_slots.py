"""``host_batches`` fills a batch as its examples arrive, into slots it keeps.

Held here: the batches are the bytes ``stack_examples`` gave before there
were slots, on every assembly path; a slot is written again only when
nothing refers to its arrays, whoever the referrer is; and the counters the
mechanism brought.
"""

import itertools
import zlib

import jax
import numpy as np
import pytest

from distributeddeeplearningspark_tpu.data import feed, prefetch
from distributeddeeplearningspark_tpu.data import workers as W
from distributeddeeplearningspark_tpu.data.feed import (
    host_batches, stack_examples)
from distributeddeeplearningspark_tpu.parallel.mesh import single_device_mesh
from distributeddeeplearningspark_tpu.rdd import PartitionedDataset
from distributeddeeplearningspark_tpu.telemetry import spans


def _image(i):
    rng = np.random.default_rng(i)
    return {"image": rng.standard_normal((16, 16, 3)).astype(np.float32),
            "label": np.int32(i)}


def _tokens(i):
    rng = np.random.default_rng(i)
    return {"input_ids": rng.integers(0, 30522, 12).astype(np.int32),
            "attention_mask": (np.arange(12) < 3 + i % 9).astype(np.int32),
            "mlm_positions": rng.integers(0, 12, 4).astype(np.int32),
            "weight": np.float32(i) / 7}


KINDS = {"image": _image, "tokens": _tokens}


def _as_it_was(dataset, batch_size, *, num_shards=1, drop_remainder=True,
               shard_range=None, pad_remainder=False):
    """The assembly as it stood before there were slots: pull a batch's
    examples into a list, then ``stack_examples`` the local ones."""
    n_parts = dataset.num_partitions
    lo, hi = shard_range if shard_range is not None else (0, num_shards)
    aligned = (n_parts % num_shards == 0 and batch_size % num_shards == 0
               and n_parts > 1)
    per_shard = batch_size // num_shards
    if aligned:
        streams = [feed._round_robin([dataset.iter_partition(i) for i in
                                      range(s, n_parts, num_shards)])
                   for s in range(num_shards)]
    else:
        stream = itertools.chain.from_iterable(
            dataset.iter_partition(i) for i in range(n_parts))
    while True:
        if aligned:
            chunks = [list(itertools.islice(s, per_shard)) for s in streams]
            everything = [e for c in chunks for e in c]
            local = [e for c in chunks[lo:hi] for e in c]
        else:
            everything = list(itertools.islice(stream, batch_size))
            local = everything[lo * per_shard:hi * per_shard]
        if len(everything) == batch_size:
            yield stack_examples(local)
            continue
        if everything and not drop_remainder:
            if pad_remainder:
                n = len(everything)
                target = -(-n // num_shards) * num_shards
                batch = stack_examples(
                    everything + [everything[0]] * (target - n))
                batch["eval_mask"] = (np.arange(target) < n).astype(np.float32)
                per = target // num_shards
                yield {k: v[lo * per:hi * per] for k, v in batch.items()}
            elif shard_range is None:
                keep = len(everything) - (len(everything) % num_shards
                                          if aligned else 0)
                if keep:
                    yield stack_examples(everything[:keep])
        return


def _same_batches(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert list(g) == list(w)
        for k in w:
            assert g[k].dtype == w[k].dtype and g[k].shape == w[k].shape
            assert g[k].tobytes() == w[k].tobytes(), k


@pytest.fixture(params=["an_example_a_copy", "a_batch_a_copy"])
def copies(request, monkeypatch):
    """Both ways the examples go into a batch: each alone as it arrives (an
    image is over ``_COPY_BYTES``), and many with one ``np.stack`` a leaf
    (token windows are far under it)."""
    if request.param == "an_example_a_copy":
        monkeypatch.setattr(feed, "_COPY_BYTES", 1)
    return request.param


LAYOUTS = {  # partitions, shards, the shard ranges a host may have
    "chained_one_shard": (1, 1, [None]),
    "chained": (3, 2, [None, (0, 1), (1, 2)]),
    "aligned": (4, 2, [None, (0, 1), (1, 2)]),
}
REMAINDERS = {
    "drop": dict(drop_remainder=True),
    "keep": dict(drop_remainder=False),
    "pad": dict(drop_remainder=False, pad_remainder=True),
}


@pytest.mark.parametrize("remainder", REMAINDERS)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("layout,shard_range", [
    (name, r) for name, (_, _, ranges) in LAYOUTS.items() for r in ranges])
def test_every_batch_is_what_stack_examples_gave(layout, shard_range, kind,
                                                 remainder, copies):
    parts, shards, _ = LAYOUTS[layout]
    # 45 examples in batches of 8: five full batches and a tail that fills
    # neither a batch nor, on the aligned layout, every shard alike
    ds = PartitionedDataset.parallelize(
        [KINDS[kind](i) for i in range(45)], parts)
    kw = dict(num_shards=shards, shard_range=shard_range,
              **REMAINDERS[remainder])
    # dropped as they come, so the slots are in use: each batch is compared
    # before the next is pulled, and once more by digest at the end
    digests = []
    want = list(_as_it_was(ds, 8, **kw))
    n = 0
    for got in host_batches(ds, 8, **kw):
        _same_batches([got], [want[n]])
        digests.append({k: zlib.crc32(v.tobytes()) for k, v in got.items()})
        n += 1
        del got
    assert n == len(want) >= 5
    assert digests == [{k: zlib.crc32(v.tobytes()) for k, v in w.items()}
                       for w in want]
    # and kept, every batch in memory of its own
    _same_batches(list(host_batches(ds, 8, **kw)), want)


def _double(ex):
    return {k: v * 2 for k, v in ex.items()}


@pytest.mark.skipif(not W.fork_available(),
                    reason="worker pool needs the fork start method")
@pytest.mark.parametrize("layout", ["chained_one_shard", "aligned"])
def test_a_worker_pools_ring_views_fill_the_same_batches(layout, copies):
    parts, shards, _ = LAYOUTS[layout]
    base = PartitionedDataset.parallelize([_image(i) for i in range(45)],
                                          parts)
    ds = W.WorkerMappedDataset(base, _double, 2)
    # (the pool hands an example's leaves on in an order of its own)
    want = [{k: v.copy() for k, v in b.items()}
            for b in _as_it_was(ds, 8, num_shards=shards)]
    assert len(want) == 5 and want[1]["label"].sum() > 0
    got = []
    for batch in host_batches(ds, 8, num_shards=shards):
        got.append({k: v.copy() for k, v in batch.items()})
        del batch
    _same_batches(got, want)


def test_examples_np_stack_would_promote_or_refuse_still_are(copies):
    """A batch whose examples do not all fit the first one's arrays is
    handed to ``stack_examples`` whole: same promotion, same errors."""
    mixed = [{"x": np.float32(i) if i % 8 != 5 else np.float64(i)}
             for i in range(24)]
    ds = PartitionedDataset.parallelize(mixed, 1)
    got = list(host_batches(ds, 8))
    _same_batches(got, list(_as_it_was(ds, 8)))
    assert got[0]["x"].dtype == np.float64
    ragged = [{"x": np.zeros(3 if i != 6 else 4, np.float32)}
              for i in range(8)]
    with pytest.raises(ValueError, match="same shape"):
        next(host_batches(PartitionedDataset.parallelize(ragged, 1), 8))
    drifting = [{"x": np.float32(i), **({"y": np.float32(i)} if i != 3 else {})}
                for i in range(8)]
    with pytest.raises(ValueError, match="disagree on their keys"):
        next(host_batches(PartitionedDataset.parallelize(drifting, 1), 8))
    # a stream whose batches change shape between them gets arrays to match
    lengths = [{"x": np.full(2 + i // 8, i, np.int32)} for i in range(24)]
    ds = PartitionedDataset.parallelize(lengths, 1)
    _same_batches([dict(b) for b in host_batches(ds, 8)],
                  list(_as_it_was(ds, 8)))


# -- rows a ``map_parallel`` pool writes itself --------------------------------


def _plus_one(ex):
    return {k: v + 1 for k, v in ex.items()}


MAPPED_LAYOUTS = {  # partitions, shards, shard ranges; does the feed ask?
    "chained_one_shard": (1, 1, [None], True),
    "chained_one_partition_two_shards": (1, 2, [None, (0, 1), (1, 2)], True),
    "aligned_a_partition_a_shard": (2, 2, [None, (0, 1), (1, 2)], True),
    "chained": (3, 2, [None, (1, 2)], False),
    "aligned_round_robin": (4, 2, [None, (0, 1)], False),
}


def _mapped(examples, parts, f=_plus_one, threads=3):
    return PartitionedDataset.parallelize(examples, parts).map_parallel(
        f, num_threads=threads)


@pytest.mark.parametrize("remainder", REMAINDERS)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("layout,shard_range", [
    (name, r) for name, (_, _, ranges, _) in MAPPED_LAYOUTS.items()
    for r in ranges])
def test_every_batch_of_a_mapped_stream_is_what_stack_examples_gave(
        layout, shard_range, kind, remainder):
    """The pool writes the rows where a shard is ONE ``map_parallel``
    partition, and is pulled from where partitions are chained or dealt in
    turn: the same bytes either way, the finite stream's tail included."""
    parts, shards, _, asks = MAPPED_LAYOUTS[layout]
    ds = _mapped([KINDS[kind](i) for i in range(45)], parts)
    kw = dict(num_shards=shards, shard_range=shard_range,
              **REMAINDERS[remainder])
    want = list(_as_it_was(ds, 8, **kw))
    stream, probe = _counted(ds, 8, **kw)
    n = 0
    for got in stream:
        _same_batches([got], [want[n]])
        n += 1
        del got
    assert n == len(want) >= 5
    # the five whole batches but the stream's first, whose first example
    # shaped the slot (the tail's rows are filled too, and stacked as views)
    assert probe.snapshot()["input_filled_by_map"] == (4 if asks else 0)
    # and kept, every batch in memory of its own
    _same_batches(list(host_batches(ds, 8, **kw)), want)


def test_an_infinite_mapped_stream_fills_batch_after_batch():
    ds = PartitionedDataset.parallelize(
        [_image(i) for i in range(20)], 1).repeat().map_parallel(
            _plus_one, num_threads=4)
    want = _as_it_was(ds, 8)
    stream, probe = _counted(ds, 8)
    for _ in range(30):       # 20 does not divide by 8: every phase of it
        batch = next(stream)
        _same_batches([batch], [next(want)])
        del batch
    stream.close()
    snap = probe.snapshot()
    assert snap["input_filled_by_map"] >= 29
    # two are being filled at once, so two slots serve a caller that keeps
    # nothing; every later batch went into one of them
    assert snap["input_slot_new"] == 2 and snap["input_slot_reused"] >= 28


@pytest.mark.parametrize("odd", ["dtype", "shape", "a_key_more", "a_key_less",
                                 "changing_shapes"])
def test_a_mapped_example_that_does_not_fit_its_row_is_np_stacks_to_decide(odd):
    def f(ex):
        i = int(ex["label"])
        out = dict(ex)
        if odd == "dtype" and i % 8 == 5:
            out["image"] = ex["image"].astype(np.float64)
        if odd == "shape" and i == 13:
            out["image"] = ex["image"][:8]
        if odd == "a_key_more" and i == 11:
            out["more"] = np.int32(1)
        if odd == "a_key_less" and i == 11:
            del out["label"]
        if odd == "changing_shapes":
            out["image"] = ex["image"][:4 + i // 8]
        return out

    ds = _mapped([_image(i) for i in range(40)], 1, f)
    if odd in ("shape", "a_key_less"):
        with pytest.raises(ValueError) as asked:
            list(host_batches(ds, 8))
        with pytest.raises(ValueError) as was:
            list(_as_it_was(ds, 8))
        assert str(asked.value) == str(was.value)
        return
    got = []
    for batch in host_batches(ds, 8):
        got.append({k: v.copy() for k, v in batch.items()})
        del batch
    _same_batches(got, list(_as_it_was(ds, 8)))
    if odd == "dtype":
        assert got[0]["image"].dtype == np.float64


def test_a_failure_in_the_mapped_function_surfaces_and_nothing_writes_after(
        monkeypatch):
    """Row 21 (third batch) raises. The batches before it arrive, the error
    is the function's own, and once it is out no pool thread writes into
    any slot the stream ever took: the batch begun ahead is given up and
    waited for first."""
    import time

    class Boom(RuntimeError):
        pass

    def f(ex):
        time.sleep(0.002)
        if int(ex["label"]) == 21:
            raise Boom("row 21")
        return _plus_one(ex)

    taken = []
    real_take = feed._Slots.take

    def take(self, first, rows, sink):
        arrays = real_take(self, first, rows, sink)
        taken.append(dict(arrays))
        return arrays

    monkeypatch.setattr(feed._Slots, "take", take)
    ds = PartitionedDataset.parallelize(
        [_image(i) for i in range(64)], 1).repeat().map_parallel(
            f, num_threads=4)
    want = _as_it_was(PartitionedDataset.parallelize(
        [_plus_one(_image(i)) for i in range(16)], 1), 8)
    stream = host_batches(ds, 8)
    for _ in range(2):
        _same_batches([next(stream)], [next(want)])
    with pytest.raises(Boom, match="row 21"):
        next(stream)
    assert len(taken) >= 3     # the failed batch's, and the one begun ahead
    after = [{k: zlib.crc32(v.tobytes()) for k, v in a.items()} for a in taken]
    time.sleep(0.1)
    assert after == [{k: zlib.crc32(v.tobytes()) for k, v in a.items()}
                     for a in taken]
    assert next(stream, None) is None


def test_a_failure_of_the_upstream_iterator_waits_its_turn():
    """The walk for the batch begun ahead hits the upstream's error while
    the batch before it is still being filled: that batch arrives whole,
    then the error, the upstream's own, as on the pull path."""
    def upstream():
        for i in range(64):
            if i == 20:
                raise OSError("the disk went away")
            yield _image(i)

    import time

    def slowly(ex):   # the second batch is still being filled by then
        time.sleep(0.01)
        return _plus_one(ex)

    ds = PartitionedDataset.from_generators([upstream]).map_parallel(
        slowly, num_threads=3)
    want = list(_as_it_was(PartitionedDataset.parallelize(
        [_plus_one(_image(i)) for i in range(16)], 1), 8))
    stream = host_batches(ds, 8)
    _same_batches([next(stream), next(stream)], want)
    with pytest.raises(OSError, match="the disk went away"):
        next(stream)
    assert next(stream, None) is None


def test_a_stream_let_go_of_mid_batch_stops_writing():
    """``close()`` of the batch stream (what ``fit`` does when its steps are
    done) waits for the batch begun ahead."""
    import time

    def f(ex):
        time.sleep(0.002)
        return _plus_one(ex)

    ds = PartitionedDataset.parallelize(
        [_image(i) for i in range(64)], 1).repeat().map_parallel(
            f, num_threads=4)
    stream = host_batches(ds, 16)
    first, second = next(stream), next(stream)
    stream.close()
    digest = zlib.crc32(second["image"].tobytes())
    time.sleep(0.1)
    assert zlib.crc32(second["image"].tobytes()) == digest
    _same_batches([first, second], list(itertools.islice(_as_it_was(ds, 16), 2)))


# -- whose memory a batch is --------------------------------------------------


def _counted(dataset, batch_size, **kw):
    """``host_batches`` pulled under a bound probe, as the prefetch thread
    pulls it."""
    probe = prefetch.StarvationProbe()

    def pulls():
        spans.bind_sink(probe)
        try:
            yield from host_batches(dataset, batch_size, **kw)
        finally:
            spans.bind_sink(None)

    return pulls(), probe


@pytest.mark.parametrize("layout", ["chained_one_shard", "aligned"])
def test_a_caller_that_keeps_every_batch_finds_each_unchanged(layout, copies):
    parts, shards, _ = LAYOUTS[layout]
    ds = PartitionedDataset.parallelize([_image(i) for i in range(160)], parts)
    stream, probe = _counted(ds, 8, num_shards=shards)
    kept = list(stream)
    _same_batches(kept, list(_as_it_was(ds, 8, num_shards=shards)))
    snap = probe.snapshot()
    assert (snap["input_slot_new"], snap["input_slot_reused"]) == (20, 0)
    # no two of them share memory
    assert len({b["image"].ctypes.data for b in kept}) == 20


@pytest.mark.parametrize("layout", ["chained_one_shard", "aligned"])
def test_a_caller_that_drops_its_batches_sees_the_slots_reused(layout, copies):
    parts, shards, _ = LAYOUTS[layout]
    ds = PartitionedDataset.parallelize([_image(i) for i in range(160)], parts)
    stream, probe = _counted(ds, 8, num_shards=shards)
    addresses = set()
    for batch in stream:
        addresses.add(batch["image"].ctypes.data)
        del batch
    snap = probe.snapshot()
    # the loop above refers to one batch at a time: one slot serves it
    assert (snap["input_slot_new"], snap["input_slot_reused"]) == (1, 19)
    assert len(addresses) == 1


@pytest.mark.parametrize("holder", ["batch", "leaf", "view", "memoryview"])
def test_one_batch_held_while_many_more_are_pulled_keeps_its_bytes(holder, copies):
    ds = PartitionedDataset.parallelize([_image(i) for i in range(8 * 40)], 1)
    want = list(_as_it_was(ds, 8))
    stream, probe = _counted(ds, 8)
    first = next(stream)
    _same_batches([first], want[:1])
    held = {"batch": first, "leaf": first["image"],
            "view": first["image"][3, 2:5],
            "memoryview": memoryview(first["label"])}[holder]
    del first
    n = 1
    for batch in stream:   # 39 more: well over three times the slots
        _same_batches([batch], [want[n]])
        n += 1
        del batch
    assert n == 40 > 3 * feed._KEPT_SLOTS
    still = held["image"] if holder == "batch" else np.asarray(held)
    was = {"batch": want[0]["image"], "leaf": want[0]["image"],
           "view": want[0]["image"][3, 2:5],
           "memoryview": want[0]["label"]}[holder]
    assert still.tobytes() == was.tobytes()
    snap = probe.snapshot()
    # the held batch's slot was never written again; one other served the rest
    assert (snap["input_slot_new"], snap["input_slot_reused"]) == (2, 38)


def test_more_batches_alive_than_slots_get_new_memory_not_an_error():
    ds = PartitionedDataset.parallelize([_tokens(i) for i in range(8 * 30)], 1)
    want = list(_as_it_was(ds, 8))
    stream, probe = _counted(ds, 8)
    window = []   # a consumer that keeps the last eight batches
    for n, batch in enumerate(stream):
        window.append((n, batch))
        del batch
        window = window[-8:]
        _same_batches([b for _, b in window], [want[i] for i, _ in window])
    snap = probe.snapshot()
    assert snap["input_slot_new"] + snap["input_slot_reused"] == 30
    # five slots among nine batches alive: some filled again, some not
    assert snap["input_slot_reused"] > 0 and snap["input_slot_new"] > 8


# -- through the prefetch ring, onto the (CPU) device -------------------------


@pytest.mark.parametrize("rows_by", ["the_producer", "the_maps_pool"])
@pytest.mark.parametrize("kind", KINDS)
def test_device_batches_hold_what_their_host_batches_held_at_put(kind, copies,
                                                                 rows_by):
    """On the CPU backend a device array may alias the numpy array it was
    made from, for as long as it lives: the slot must stay unwritten. The
    consumer holds the last two device batches, as ``fit`` does."""
    batches = 4 * feed._KEPT_SLOTS
    ds = PartitionedDataset.parallelize(
        [KINDS[kind](i) for i in range(8 * batches)], 1)
    if rows_by == "the_maps_pool":
        ds = ds.map_parallel(_plus_one, num_threads=3)
    want = list(_as_it_was(ds, 8))
    mesh = single_device_mesh()
    at_put = []

    def put(batch, mesh):
        at_put.append({k: zlib.crc32(v.tobytes()) for k, v in batch.items()})
        return feed.put_global(batch, mesh)

    probe = prefetch.StarvationProbe()
    held = []
    n = 0
    for placed in prefetch.prefetch_to_device(host_batches(ds, 8), mesh,
                                              put=put, probe=probe):
        held = [*held[-1:], (n, placed)]
        del placed
        for i, device_batch in held:
            back = jax.device_get(device_batch)
            assert {k: zlib.crc32(v.tobytes())
                    for k, v in back.items()} == at_put[i]
            # (a pytree's dict comes back with its keys sorted)
            _same_batches([{k: back[k] for k in want[i]}], [want[i]])
        n += 1
    assert n == batches >= 3 * feed._KEPT_SLOTS
    snap = probe.snapshot()
    # (a finite mapped stream takes one more slot, for the batch begun
    # ahead that finds the stream ended)
    mapped = rows_by == "the_maps_pool"
    assert (snap["input_slot_new"] + snap["input_slot_reused"]
            == batches + mapped)
    assert snap["input_slot_reused"] > 0
    assert snap["input_filled_by_map"] == (batches - 1 if mapped else 0)


def test_two_threads_and_a_short_switch_interval_never_share_a_slot(copies):
    """The producer fills slots on its thread while this one keeps, reads
    and lets go of batches at random, switching every few bytecodes: a slot
    written while a batch still referred to it would change that batch's
    digest between its arrival and its release."""
    import random
    import sys
    import time

    ds = PartitionedDataset.parallelize(
        [_image(i) for i in range(64)], 1).repeat()
    rng = random.Random(29)
    probe = prefetch.StarvationProbe()
    stream = prefetch._background(host_batches(ds, 8), maxsize=3, probe=probe)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        deadline = time.monotonic() + 60
        kept = []
        first = {}
        for n, batch in enumerate(stream):
            digest = zlib.crc32(batch["image"].tobytes())
            assert first.setdefault(n % 8, digest) == digest  # 8 a pass
            kept.append((digest, batch))
            del batch
            while kept and (len(kept) > 7 or rng.random() < 0.5):
                digest, gone = kept.pop(rng.randrange(len(kept)))
                assert zlib.crc32(gone["image"].tobytes()) == digest
                del gone
            if n == 400:
                break
            assert time.monotonic() < deadline
    finally:
        sys.setswitchinterval(interval)
    snap = probe.snapshot()
    assert snap["input_slot_reused"] > 100 and snap["input_slot_new"] >= 2


# -- the counters --------------------------------------------------------------


def test_the_slot_counters_are_on_the_one_list_and_in_every_snapshot():
    assert spans.COUNTERS["dls.feed/slot_reused"] == "input_slot_reused"
    assert spans.COUNTERS["dls.feed/slot_new"] == "input_slot_new"
    assert {"dls.feed/slot_reused", "dls.feed/slot_new",
            "dls.feed/stack"} <= set(spans.SPAN_NAMES)
    fresh = prefetch.StarvationProbe().snapshot()
    assert (fresh["input_slot_reused"], fresh["input_slot_new"],
            fresh["input_stack_s"]) == (0, 0, 0.0)
    # they count sections, whatever the sections' time
    probe = prefetch.StarvationProbe()
    probe.add("dls.feed/slot_new", 0.25)
    probe.add("dls.feed/slot_reused", 0.5)
    probe.add("dls.feed/slot_reused", 0.5)
    snap = probe.snapshot()
    assert (snap["input_slot_new"], snap["input_slot_reused"]) == (1, 2)
    assert probe.snapshot()["input_slot_reused"] == 0


def test_the_fill_counter_is_on_the_one_list_and_in_every_snapshot():
    assert spans.COUNTERS["dls.feed/filled_by_map"] == "input_filled_by_map"
    assert "dls.feed/filled_by_map" in spans.SPAN_NAMES
    assert prefetch.StarvationProbe().snapshot()["input_filled_by_map"] == 0
    probe = prefetch.StarvationProbe()
    probe.add("dls.feed/filled_by_map", 0.25)
    probe.add("dls.feed/filled_by_map", 0.25)
    assert probe.snapshot()["input_filled_by_map"] == 2
    # a token stream offers no ``fill``: every lap reads 0, and its few
    # copies stay the producer's
    ds = PartitionedDataset.parallelize([_tokens(i) for i in range(64)], 1)
    stream, probe = _counted(ds, 8)
    for batch in stream:
        del batch
    snap = probe.snapshot()
    assert snap["input_filled_by_map"] == 0
    assert snap["input_slot_new"] + snap["input_slot_reused"] == 8


def test_the_producer_copies_one_example_of_a_mapped_stream(monkeypatch):
    """The check for ``fill`` is once a segment a batch, and after the
    stream's first example the producer's thread copies nothing."""
    import threading

    by_thread = []
    real_copy = feed._copy_rows

    def copy(arrays, at, examples):
        by_thread.append((threading.current_thread().name, len(examples)))
        return real_copy(arrays, at, examples)

    monkeypatch.setattr(feed, "_copy_rows", copy)
    ds = _mapped([_image(i) for i in range(64)], 1)
    stream, probe = _counted(ds, 8)
    for batch in stream:
        del batch
    mine = threading.current_thread().name
    assert [n for name, n in by_thread if name == mine] == [1]
    assert sum(n for name, n in by_thread if name != mine) == 63
    assert all(name.startswith("ThreadPoolExecutor") or name == mine
               for name, _ in by_thread)


def test_the_row_copies_are_the_stack_seconds_of_an_image_stream():
    ds = PartitionedDataset.parallelize([_image(i) for i in range(64)], 1)
    stream, probe = _counted(ds, 8)
    for batch in stream:
        del batch
    snap = probe.snapshot()
    assert snap["input_stack_s"] > 0
    assert snap["input_slot_new"] + snap["input_slot_reused"] == 8


def _benchmarks_reader(name):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


def test_the_benchmarks_reader_of_the_slot_counters():
    reader = _benchmarks_reader("feed_slot_reuse_share")
    laps = [{"steps": 2, "input_slot_reused": 2.0, "input_slot_new": 0.0},
            {"steps": 2, "input_slot_reused": 1.0, "input_slot_new": 1.0}]
    assert reader.read({"laps": laps}) == pytest.approx(75.0)
    # the parent's laps have no such keys; a window with no batch reads nothing
    assert reader.read({"laps": [{"steps": 2, "input_stack_s": 0.1}]}) is None
    assert reader.read({"laps": [{"steps": 0, "input_slot_reused": 0.0,
                                  "input_slot_new": 0.0}]}) is None
    assert reader.read({"laps": []}) is None


def test_the_benchmarks_reader_of_the_fill_counter():
    reader = _benchmarks_reader("feed_map_fill_share")
    lap = {"steps": 2, "input_slot_reused": 2.0, "input_slot_new": 0.0}
    assert reader.read({"laps": [
        {**lap, "input_filled_by_map": 2.0},
        {**lap, "input_slot_reused": 1.0, "input_slot_new": 1.0,
         "input_filled_by_map": 1.0}]}) == pytest.approx(75.0)
    # a token cell: batches, none of them filled by a pool
    assert reader.read({"laps": [{**lap, "input_filled_by_map": 0.0}]}) == 0.0
    # the parent's laps have no such key; a window with no batch reads nothing
    assert reader.read({"laps": [lap]}) is None
    assert reader.read({"laps": [{"steps": 0, "input_slot_reused": 0.0,
                                  "input_slot_new": 0.0,
                                  "input_filled_by_map": 0.0}]}) is None
    assert reader.read({"laps": []}) is None
