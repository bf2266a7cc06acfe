"""The JPEG decode map in worker processes by default (data/vision.py,
data/workers.py, data/sources.py).

- Where nobody names a count, ``imagenet_train`` / ``imagenet_eval`` over a
  source of unread files (``imagenet_folder(decode=False)``) map in worker
  processes, as many as ``default_map_workers`` gives; ``DLS_DATA_WORKERS``
  or an explicit ``num_workers`` (0 included) or ``num_threads`` still
  decide, and the text and exchange paths keep their default of 0.
- The batches are byte-identical to the in-process map's, across epochs, at
  two seeds and after a fast-forward resume.
- Each worker reads its own examples' files and no other's, each epoch: the
  feed's probe counts one file read an example (``input_worker_reads``).

A forked worker that hangs would hold the suite: the suite's time limit
(``tests/conftest.py``) fails the test instead.
"""

import itertools
import os
import time

import numpy as np
import pytest

from distributeddeeplearningspark_tpu.data import prefetch, vision
from distributeddeeplearningspark_tpu.data import workers as W
from distributeddeeplearningspark_tpu.data.feed import host_batches
from distributeddeeplearningspark_tpu.data.sources import (
    EncodedFile, files_read, imagenet_folder)
from distributeddeeplearningspark_tpu.rdd import PartitionedDataset
from distributeddeeplearningspark_tpu.telemetry import spans

pytestmark = pytest.mark.skipif(
    not W.fork_available(), reason="worker pool needs the fork start method")

#: images in the folder: two classes of ``PER_CLASS``; batches of ``BATCH``
PER_CLASS, BATCH = 6, 4


@pytest.fixture(autouse=True)
def no_workers_env(monkeypatch):
    monkeypatch.delenv(W.WORKERS_ENV, raising=False)


@pytest.fixture(scope="module")
def jpeg_root(tmp_path_factory):
    from PIL import Image

    root = tmp_path_factory.mktemp("jpegs")
    rng = np.random.default_rng(1)
    for cls in range(2):
        d = root / f"class_{cls}"
        d.mkdir()
        for i in range(PER_CLASS):
            arr = rng.integers(0, 255, (56, 72, 3), np.uint8)
            Image.fromarray(arr).save(str(d / f"img_{i}.jpg"), quality=90)
    return str(root)


def _train(root, **kw):
    return vision.imagenet_train(
        imagenet_folder(root, num_partitions=1, decode=False), size=32,
        repeat=True, **kw)


def _batches(ds, n, skip=0, probe=None):
    spans.bind_sink(probe)
    feed = host_batches(ds, BATCH)
    try:
        return list(itertools.islice(feed, skip, skip + n))
    finally:
        feed.close()
        spans.bind_sink(None)


def _same(xs, ys):
    assert len(xs) == len(ys)
    for x, y in zip(xs, ys):
        assert x.keys() == y.keys()
        for k in x:
            assert x[k].tobytes() == np.asarray(y[k]).tobytes(), k


# ---------------------------------------------------------------------------
# where the map runs
# ---------------------------------------------------------------------------

def test_the_default_engages_for_unread_jpeg_files(jpeg_root, monkeypatch):
    monkeypatch.setattr(W, "default_map_workers", lambda: 3)
    ds = _train(jpeg_root)
    assert isinstance(ds, W.WorkerMappedDataset) and ds.num_workers == 3
    ev = vision.imagenet_eval(
        imagenet_folder(jpeg_root, num_partitions=1, decode=False), size=32)
    assert isinstance(ev, W.WorkerMappedDataset) and ev.num_workers == 3
    # DLS_DATA_WORKERS=0 turns it off, as an explicit count or threads do
    monkeypatch.setenv(W.WORKERS_ENV, "0")
    assert not isinstance(_train(jpeg_root), W.WorkerMappedDataset)
    monkeypatch.delenv(W.WORKERS_ENV)
    for route in ({"num_workers": 0}, {"num_threads": 4}):
        assert not isinstance(_train(jpeg_root, **route),
                              W.WorkerMappedDataset), route
    # the environment's count wins over the default's
    monkeypatch.setenv(W.WORKERS_ENV, "2")
    ds = _train(jpeg_root)
    assert isinstance(ds, W.WorkerMappedDataset) and ds.num_workers is None


def test_decoded_examples_keep_the_threads(jpeg_root, monkeypatch):
    monkeypatch.setattr(W, "default_map_workers", lambda: 3)
    decoded = imagenet_folder(jpeg_root, num_partitions=1)
    assert not isinstance(vision.imagenet_train(decoded, size=32),
                          W.WorkerMappedDataset)
    arrays = PartitionedDataset.parallelize(
        [{"image": np.zeros((40, 40, 3), np.uint8), "label": np.int32(0)}], 1)
    assert not isinstance(vision.imagenet_train(arrays, size=32),
                          W.WorkerMappedDataset)


def test_no_fork_or_too_few_cores_keeps_the_threads(monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(13)))
    assert W.default_map_workers() == 11
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(30)))
    assert W.default_map_workers() == W._MAP_WORKERS_MAX
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(3)))
    assert W.default_map_workers() == 0
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(8)))
    monkeypatch.setattr(W, "fork_available", lambda: False)
    assert W.default_map_workers() == 0


def test_text_and_exchange_defaults_stay_zero(monkeypatch):
    """Only the decode map's default changed: the text feed's tokenize stage
    and the exchange still resolve an unset count to 0, whatever the
    decode map's default would be."""
    from distributeddeeplearningspark_tpu.data import exchange, text

    monkeypatch.setattr(W, "default_map_workers", lambda: 3)
    docs = PartitionedDataset.parallelize(["a b", "c d"], 1)
    tokens = text._tokens_dataset(docs, str.split, None, label="t")
    assert not isinstance(tokens, W.WorkerMappedDataset)
    assert tokens.collect() == [["a", "b"], ["c", "d"]]
    assert exchange.resolve_shuffle_workers(None) == 0
    assert W.resolve_num_workers(None) == 0


def test_the_folder_lists_files_unread(jpeg_root):
    before = files_read()
    examples = imagenet_folder(jpeg_root, num_partitions=2,
                               decode=False).collect()
    assert files_read() == before
    assert len(examples) == 2 * PER_CLASS
    f = examples[0]["jpeg"]
    assert isinstance(f, EncodedFile) and os.path.isfile(f)
    assert bytes(f)[:2] == b"\xff\xd8" and files_read() == before + 1


# ---------------------------------------------------------------------------
# the same bytes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7])
def test_default_workers_byte_identical_to_the_serial_map(jpeg_root, seed,
                                                          monkeypatch):
    """Three epochs of three batches each (12 images, batches of 4), and the
    last four batches again after a fast-forward past the first five."""
    monkeypatch.setattr(W, "default_map_workers", lambda: 3)
    serial = _batches(_train(jpeg_root, seed=seed, num_workers=0,
                             num_threads=0), 9)
    default = _train(jpeg_root, seed=seed)
    assert isinstance(default, W.WorkerMappedDataset)
    _same(serial, _batches(default, 9))
    _same(serial[5:], _batches(default, 4, skip=5))


def test_take_maps_in_this_process(jpeg_root, monkeypatch):
    """A handful of examples (a trainer's sample) forks no pool, and they
    are the pool's first ones byte for byte."""
    monkeypatch.setattr(W, "default_map_workers", lambda: 3)
    ds = _train(jpeg_root, seed=7)
    it = iter(ds.iter_partition(0))
    pooled = list(itertools.islice(it, 5))
    it.close()

    def no_pool(*a, **k):
        raise AssertionError("take forked a pool")

    monkeypatch.setattr(W, "WorkerPool", no_pool)
    taken = ds.take(5)
    assert len(taken) == 5
    for a, b in zip(taken, pooled):
        assert a.keys() == b.keys()
        for k in a:
            assert np.asarray(a[k]).tobytes() == np.asarray(b[k]).tobytes()


# ---------------------------------------------------------------------------
# what each worker reads
# ---------------------------------------------------------------------------

class _Credits:
    """A feed sink that keeps every credit apart, in order."""

    clock = staticmethod(time.perf_counter)

    def __init__(self):
        self.credits: list[tuple[str, float]] = []
        self.sections: list[str] = []

    def add(self, name, dt, inner_s=0.0):
        self.sections.append(name)

    def credit(self, name, amount):
        self.credits.append((name, amount))


def test_each_worker_reads_its_own_files_each_epoch(jpeg_root, monkeypatch):
    """Two epochs through three workers: every example brings along ONE file
    read (its own: the bytes equal the serial map's), so no worker read
    another's file, and none read any file in its walk."""
    monkeypatch.setattr(W, "default_map_workers", lambda: 3)
    sink = _Credits()
    n = 2 * (2 * PER_CLASS) // BATCH
    got = _batches(_train(jpeg_root), n, probe=sink)
    _same(_batches(_train(jpeg_root, num_workers=0, num_threads=0), n), got)
    reads = [a for name, a in sink.credits if name == "dls.feed/worker_read"]
    taken = sink.sections.count("dls.feed/worker_example")
    assert taken >= n * BATCH and len(reads) == taken
    assert set(reads) == {1}
    assert {name for name, _ in sink.credits} >= {"dls.feed/map",
                                                   "dls.feed/decode"}


def test_the_probe_counts_one_read_an_example(jpeg_root, monkeypatch):
    monkeypatch.setattr(W, "default_map_workers", lambda: 2)
    probe = prefetch.StarvationProbe()
    _batches(_train(jpeg_root), 6, probe=probe)
    snap = probe.snapshot()
    assert snap["input_worker_items"] >= 6 * BATCH
    assert snap["input_worker_reads"] == snap["input_worker_items"]
    assert snap["input_map_s"] > 0 and snap["input_decode_s"] > 0
    # the threads' path takes nothing from a worker
    _batches(_train(jpeg_root, num_workers=0), 2, probe=probe)
    snap = probe.snapshot()
    assert snap["input_worker_items"] == snap["input_worker_reads"] == 0


def test_a_walk_that_reads_shows_as_the_width(jpeg_root):
    """A source whose walk reads every file (bytes in the example, as the
    folder gave them before it listed files unread) puts each worker through
    the whole epoch: the counter reads the pool's width an example."""
    read_in_walk = imagenet_folder(
        jpeg_root, num_partitions=1, decode=False).map(
            lambda e: {**e, "jpeg": e["jpeg"].read()})
    ds = vision.imagenet_train(read_in_walk, size=32, repeat=True,
                               num_workers=3)
    probe = prefetch.StarvationProbe()
    _batches(ds, 6, probe=probe)
    snap = probe.snapshot()
    assert snap["input_worker_reads"] / snap["input_worker_items"] \
        == pytest.approx(3, rel=0.35)


def test_credit_adds_what_it_is_given():
    probe = prefetch.StarvationProbe()
    fresh = probe.snapshot()
    assert fresh["input_worker_items"] == fresh["input_worker_reads"] == 0
    probe.credit("dls.feed/worker_read", 3)
    probe.credit("dls.feed/map", 0.25)
    probe.add("dls.feed/worker_example", 9.0)  # a section counts one
    snap = probe.snapshot()
    assert snap["input_worker_reads"] == 3 and snap["input_map_s"] == 0.25
    assert snap["input_worker_items"] == 1


# ---------------------------------------------------------------------------
# the benchmark's readers of the two counters
# ---------------------------------------------------------------------------

def _reader(name):
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("laps, share, per_item", [
    # every example from a worker, one file each
    ([{"steps": 2, "input_worker_items": 512, "input_worker_reads": 512},
      {"steps": 2, "input_worker_items": 512, "input_worker_reads": 512}],
     100.0, 1.0),
    # each of 11 workers walking the epoch's bytes
    ([{"steps": 4, "input_worker_items": 1024, "input_worker_reads": 11264}],
     100.0, 11.0),
    # the threads' path: nothing from a worker
    ([{"steps": 2, "input_worker_items": 0, "input_worker_reads": 0}],
     0.0, None),
    # a program without the counters
    ([{"steps": 2}], None, None),
])
def test_the_readers_of_the_worker_counters(laps, share, per_item):
    ctx = {"laps": laps, "items_per_step": 256}
    got = _reader("feed_worker_share")(ctx)
    assert got == (pytest.approx(share) if share is not None else None)
    got = _reader("feed_reads_per_item")(ctx)
    assert got == (pytest.approx(per_item) if per_item is not None else None)
