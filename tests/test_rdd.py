"""PartitionedDataset (RDD-shaped) semantics tests."""

import numpy as np
import pytest

from distributeddeeplearningspark_tpu.rdd import PartitionedDataset


def test_parallelize_slicing():
    ds = PartitionedDataset.parallelize(list(range(10)), 3)
    assert ds.num_partitions == 3
    parts = [list(ds.iter_partition(i)) for i in range(3)]
    assert [len(p) for p in parts] == [3, 3, 4]
    assert ds.collect() == list(range(10))


def test_lazy_map_filter():
    evals = []

    def f(x):
        evals.append(x)
        return x * 2

    ds = PartitionedDataset.parallelize(range(4), 2).map(f)
    assert evals == []  # lazy
    assert ds.collect() == [0, 2, 4, 6]
    assert ds.filter(lambda x: x > 2).collect() == [4, 6]


def test_map_partitions_with_index():
    ds = PartitionedDataset.parallelize(range(6), 3)
    tagged = ds.map_partitions_with_index(lambda i, it: ((i, x) for x in it))
    assert tagged.collect() == [(0, 0), (0, 1), (1, 2), (1, 3), (2, 4), (2, 5)]


def test_batch_and_repeat():
    ds = PartitionedDataset.parallelize(range(10), 2).batch(2)
    assert ds.collect() == [[0, 1], [2, 3], [5, 6], [7, 8]]  # drop remainder per partition
    r = PartitionedDataset.parallelize(range(2), 1).repeat(3)
    assert r.collect() == [0, 1, 0, 1, 0, 1]


def test_shuffle_deterministic_and_partition_local():
    ds = PartitionedDataset.parallelize(range(8), 2)
    s1 = ds.shuffle(seed=1).collect()
    s2 = ds.shuffle(seed=1).collect()
    assert s1 == s2
    assert sorted(s1[:4]) == [0, 1, 2, 3]  # partition contents preserved
    assert sorted(s1[4:]) == [4, 5, 6, 7]


def test_tree_aggregate_matches_sum():
    ds = PartitionedDataset.parallelize(range(100), 4)
    total = ds.tree_aggregate(0, lambda acc, x: acc + x, lambda a, b: a + b)
    assert total == sum(range(100))


def test_actions():
    ds = PartitionedDataset.parallelize(range(7), 3)
    assert ds.count() == 7
    assert ds.take(3) == [0, 1, 2]
    assert ds.first() == 0
    assert ds.reduce(lambda a, b: a + b) == 21
    assert ds.coalesce(2).num_partitions == 2
    assert ds.coalesce(2).collect() == list(range(7))


def test_zip_with_index():
    ds = PartitionedDataset.parallelize(list("abcd"), 2)
    assert ds.zip_with_index().collect() == [("a", 0), ("b", 1), ("c", 2), ("d", 3)]


def test_numpy_parallelize():
    arr = np.arange(12).reshape(6, 2)
    ds = PartitionedDataset.parallelize(arr, 3)
    got = np.concatenate([np.asarray(list(ds.iter_partition(i))) for i in range(3)])
    np.testing.assert_array_equal(got.reshape(6, 2), arr)


def test_union_concatenates_partitions():
    a = PartitionedDataset.parallelize([1, 2], 2)
    b = PartitionedDataset.parallelize([3, 4, 5], 1)
    u = a.union(b)
    assert u.num_partitions == 3
    assert u.collect() == [1, 2, 3, 4, 5]
    import pytest

    with pytest.raises(ValueError, match="union"):
        a.union(b.repeat())


def test_sample_deterministic_and_bounded():
    ds = PartitionedDataset.parallelize(range(1000), 4)
    s1 = ds.sample(0.3, seed=7).collect()
    s2 = ds.sample(0.3, seed=7).collect()
    assert s1 == s2  # deterministic per seed
    assert 200 < len(s1) < 400  # ~300 expected
    assert set(s1) <= set(range(1000))
    assert ds.sample(0.0).count() == 0
    assert ds.sample(1.0).count() == 1000
    import pytest

    with pytest.raises(ValueError, match="fraction"):
        ds.sample(1.5)


def test_distinct_keeps_first_occurrence_order():
    ds = PartitionedDataset.parallelize([3, 1, 3, 2, 1, 2, 5], 3)
    assert ds.distinct().collect() == [3, 1, 2, 5]


def test_reduce_by_key_combines_across_partitions():
    ds = PartitionedDataset.parallelize(
        [("a", 1), ("b", 2), ("a", 3), ("c", 4), ("b", 5)], 3)
    out = ds.reduce_by_key(lambda x, y: x + y)
    assert out.num_partitions == 3
    assert dict(out.collect()) == {"a": 4, "b": 7, "c": 4}
    # every pair lands in the partition its CANONICAL key hash owns (PR 8:
    # exchange.key_bytes, stable across runs — hash() moves with
    # PYTHONHASHSEED), in key_bytes order within the partition
    from distributeddeeplearningspark_tpu.data import exchange

    for i in range(out.num_partitions):
        part = list(out.iter_partition(i))
        for k, _ in part:
            assert exchange.bucket_of(exchange.key_bytes(k), 3) == i
        kbs = [exchange.key_bytes(k) for k, _ in part]
        assert kbs == sorted(kbs)
    # num_partitions override + the infinite guard
    assert dict(ds.reduce_by_key(lambda x, y: x + y,
                                 num_partitions=1).collect()) == {
        "a": 4, "b": 7, "c": 4}
    import pytest

    with pytest.raises(ValueError, match="reduce_by_key"):
        ds.repeat().reduce_by_key(lambda x, y: x + y)


def test_group_by_key_orders_values_partition_major():
    ds = PartitionedDataset.parallelize(
        [("a", 1), ("b", 2), ("a", 3), ("a", 5)], 2)
    got = dict(ds.group_by_key().collect())
    assert got == {"a": [1, 3, 5], "b": [2]}


def test_by_key_camel_aliases_and_guards():
    ds = PartitionedDataset.parallelize([("a", 1), ("a", 2)], 2)
    assert dict(ds.reduceByKey(lambda x, y: x + y).collect()) == {"a": 3}
    assert dict(ds.groupByKey().collect()) == {"a": [1, 2]}
    assert ds.sortBy(lambda kv: kv[1]).collect() == [("a", 1), ("a", 2)]
    import pytest

    with pytest.raises(ValueError, match="num_partitions"):
        ds.reduce_by_key(lambda x, y: x, num_partitions=-2)
    with pytest.raises(ValueError, match="num_partitions"):
        ds.group_by_key(num_partitions=-2)
    with pytest.raises(ValueError, match="num_partitions"):
        ds.sort_by(lambda x: x, num_partitions=0)


def test_sort_by_is_range_partitioned_total_order():
    ds = PartitionedDataset.parallelize([5, 1, 4, 2, 3, 9, 0], 3)
    out = ds.sort_by(lambda x: x)
    assert out.collect() == [0, 1, 2, 3, 4, 5, 9]
    # range partitioning: max of partition i <= min of partition i+1
    parts = [list(out.iter_partition(i)) for i in range(out.num_partitions)]
    flat_bounds = [(min(p), max(p)) for p in parts if p]
    for (_, hi), (lo, _) in zip(flat_bounds, flat_bounds[1:]):
        assert hi <= lo
    assert ds.sort_by(lambda x: x, ascending=False).collect() == [
        9, 5, 4, 3, 2, 1, 0]


def test_cache_materializes_once_and_survives_partial_reads():
    calls = [0]

    def gen():
        calls[0] += 1
        yield from range(5)

    ds = PartitionedDataset.from_generators([gen]).cache()
    assert ds.take(2) == [0, 1]   # partial read: cache must NOT freeze this
    assert ds.collect() == [0, 1, 2, 3, 4]
    assert ds.collect() == [0, 1, 2, 3, 4]
    # one partial + one full pass over the source; the last collect was served
    # from memory
    assert calls[0] == 2
    # interleaved live iterators must not corrupt the committed store
    # (r4 review repro: a shared fill buffer yielded [0..4, 1..4] forever)
    it = ds.iter_partition(0)
    next(it)
    assert ds.collect() == [0, 1, 2, 3, 4]
    list(it)  # drain the stale iterator
    assert ds.collect() == [0, 1, 2, 3, 4]


def test_pyspark_aliases():
    ds = PartitionedDataset.parallelize(range(4), 2)
    assert ds.mapPartitions(lambda it: (x + 1 for x in it)).collect() == [1, 2, 3, 4]
    assert ds.flatMap(lambda x: [x, x]).count() == 8


class TestMapParallel:
    """map_parallel: thread-pool map (the Spark task-slot analog) must be a
    pure drop-in for map — same order, same values, bounded on infinite
    streams. (This sandbox has 1 CPU, so speedup is asserted architecturally
    on real hosts, not here.)"""

    def test_order_preserved_under_jittered_durations(self):
        import time

        def slow_square(x):
            time.sleep(0.001 * (7 - x % 7))  # later items finish earlier
            return x * x

        ds = PartitionedDataset.parallelize(list(range(40)), num_slices=2)
        got = ds.map_parallel(slow_square, num_threads=8).collect()
        assert got == [x * x for x in ds.collect()]

    def test_infinite_stream_stays_bounded(self):
        """The sliding window must not consume the infinite iterator up
        front (ThreadPoolExecutor.map would)."""
        ds = PartitionedDataset.parallelize(list(range(8)), num_slices=2)
        inf = ds.repeat().map_parallel(lambda x: x + 1, num_threads=4)
        it = inf.iter_partition(0)
        got = [next(it) for _ in range(50)]
        assert len(got) == 50 and got[:4] == [1, 2, 3, 4]  # partition 0 = first contiguous slice

    @staticmethod
    def _rows(n):
        return {"x": np.full((n, 3), -1, np.int64),
                "y": np.full(n, -1.0, np.float32)}

    @staticmethod
    def _row(i):
        return {"x": np.arange(3, dtype=np.int64) + 10 * i,
                "y": np.float32(i) / 4}

    def test_fill_and_next_mixed_on_one_stream_keep_the_order(self):
        """``next`` asks for 2 x threads calls ahead; a ``fill`` after it
        must deliver those first, then go on where they end, and a ``next``
        after a ``fill`` goes on where the fill's rows end."""
        ds = PartitionedDataset.parallelize(list(range(100)), 1)
        stream = ds.map_parallel(self._row, num_threads=3).iter_partition(0)
        assert hasattr(stream, "fill") and iter(stream) is stream
        got = [next(stream)["x"][0] // 10, next(stream)["x"][0] // 10]
        arrays = self._rows(40)
        first = stream.fill(arrays, 5, 30)     # 4 of the window, 26 new
        second = stream.fill(arrays, 35, 5)    # before the first is waited on
        assert (first.taken, second.taken) == (30, 5)
        assert first.wait() == [range(5, 35)] and second.wait() == [range(35, 40)]
        got += (arrays["x"][5:40, 0] // 10).tolist()
        assert (arrays["x"][:5] == -1).all()   # rows nobody asked for
        assert (arrays["y"][5:40] * 4).tolist() == list(range(2, 37))
        got += [next(stream)["x"][0] // 10 for _ in range(3)]
        tail = self._rows(80)
        last = stream.fill(tail, 0, 80)        # the stream ends inside it
        assert last.taken == 60 and last.wait() == [range(60)]
        got += (tail["x"][:60, 0] // 10).tolist()
        assert got == list(range(100))
        assert (tail["x"][60:] == -1).all()
        # ended: nothing more either way
        assert stream.fill(tail, 0, 4).taken == 0
        assert stream.fill(tail, 0, 4).wait() == []
        assert next(stream, None) is None
        # the handles let go of the arrays: a feed's slot must not look busy
        assert first.arrays is None and last.arrays is None

    def test_fill_hands_back_loose_what_does_not_fit_its_row(self):
        def odd(i):
            row = self._row(i)
            if i == 7:
                row["x"] = row["x"].astype(np.int32)     # another dtype
            if i == 21:
                row["x"] = np.arange(4, dtype=np.int64)  # another shape
            if i == 22:
                del row["y"]                             # a key missing
            return row

        ds = PartitionedDataset.parallelize(list(range(32)), 1)
        stream = ds.map_parallel(odd, num_threads=2).iter_partition(0)
        arrays = self._rows(32)
        pieces = stream.fill(arrays, 0, 32).wait()   # runs of 4 rows
        assert [p if isinstance(p, range) else len(p) for p in pieces] == [
            range(0, 7), 1, range(8, 21), 3, range(24, 32)]
        # a run keeps everything from its first misfit on loose, in order
        assert pieces[1][0]["x"].dtype == np.int32
        assert [int(e["x"][0]) for e in pieces[3]] == [0, 220, 230]
        assert "y" not in pieces[3][1]
        written = np.r_[0:7, 8:21, 24:32]
        assert (arrays["x"][written, 0] == 10 * written).all()

    def test_fill_raises_the_first_failure_in_row_order_after_every_run(self):
        import threading
        import time

        late = threading.Event()

        def f(i):
            if i == 5:
                late.wait(5)      # the earlier row fails LATER in time
                raise KeyError("row 5")
            if i == 20:
                try:
                    raise ValueError("row 20")
                finally:
                    late.set()
            time.sleep(0.001)
            return self._row(i)

        ds = PartitionedDataset.parallelize(list(range(48)), 1)
        stream = ds.map_parallel(f, num_threads=4).iter_partition(0)
        arrays = self._rows(48)
        asked = stream.fill(arrays, 0, 48)
        with pytest.raises(KeyError, match="row 5"):
            asked.wait()
        # every run had finished: nothing is written after the raise
        before = {k: v.copy() for k, v in arrays.items()}
        time.sleep(0.05)
        assert all((arrays[k] == before[k]).all() for k in arrays)
        assert asked.arrays is None

    def test_imagenet_train_parallel_equals_serial(self, tmp_path):
        """Content-seeded augmentation: thread scheduling cannot change the
        pipeline output, so parallel ≡ serial example-for-example."""
        import numpy as np
        from PIL import Image

        from distributeddeeplearningspark_tpu.data.sources import imagenet_folder
        from distributeddeeplearningspark_tpu.data.vision import imagenet_train

        rng = np.random.default_rng(0)
        for cls in range(2):
            d = tmp_path / f"c{cls}"
            d.mkdir()
            for i in range(6):
                arr = rng.integers(0, 255, (64, 64, 3), np.uint8)
                Image.fromarray(arr).save(str(d / f"i{i}.jpg"), quality=92)
        serial = imagenet_train(
            imagenet_folder(str(tmp_path), num_partitions=2),
            size=32, num_threads=1).collect()
        parallel = imagenet_train(
            imagenet_folder(str(tmp_path), num_partitions=2),
            size=32, num_threads=6).collect()
        assert len(serial) == len(parallel) == 12
        for a, b in zip(serial, parallel):
            np.testing.assert_array_equal(a["image"], b["image"])
            assert a["label"] == b["label"]
