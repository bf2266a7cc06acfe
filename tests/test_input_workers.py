"""Multi-process input pipeline (data/workers.py — ISSUE 5).

The three contracts under test:

- determinism: the batch stream is byte-identical for num_workers 0/1/4,
  across the vision (JPEG), record, batched-fused, and text paths, and
  across a checkpoint fast-forward resume;
- crash propagation: a worker that raises or dies surfaces a typed
  WorkerCrashed in the consumer within a bounded wait, with no orphaned
  processes or leaked shared-memory segments — including on plain
  interpreter exit without close();
- backpressure: the per-worker in-flight window (metadata queue + byte
  ring) stays bounded under a slow consumer, with zero overflow when the
  consumer releases views promptly.
"""

import multiprocessing as mp
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from distributeddeeplearningspark_tpu.data import workers as W
from distributeddeeplearningspark_tpu.data.feed import host_batches
from distributeddeeplearningspark_tpu.data.workers import (
    WorkerCrashed, WorkerMappedDataset, WorkerPool, _Arena, _split_budget,
    pool_gauges, resolve_num_workers)
from distributeddeeplearningspark_tpu.rdd import PartitionedDataset

pytestmark = pytest.mark.skipif(
    not W.fork_available(), reason="worker pool needs the fork start method")


def _assert_no_leaks():
    """No dls worker processes or dlsw shm segments survive."""
    deadline = time.time() + 5.0
    while time.time() < deadline:
        kids = [p for p in mp.active_children()
                if p.name.startswith("dls-worker")]
        if not kids:
            break
        time.sleep(0.05)
    assert not [p for p in mp.active_children()
                if p.name.startswith("dls-worker")]
    if os.path.isdir("/dev/shm"):
        mine = [f for f in os.listdir("/dev/shm")
                if f.startswith(f"dlsw-{os.getpid()}-")]
        assert not mine, mine


# ---------------------------------------------------------------------------
# unit: budget split, env resolution, byte ring
# ---------------------------------------------------------------------------

def test_resolve_num_workers_env(monkeypatch):
    assert resolve_num_workers(3) == 3
    assert resolve_num_workers(0) == 0
    monkeypatch.delenv(W.WORKERS_ENV, raising=False)
    assert resolve_num_workers(None) == 0
    monkeypatch.setenv(W.WORKERS_ENV, "4")
    assert resolve_num_workers(None) == 4
    # explicit beats env; garbage env is ignored with a warning
    assert resolve_num_workers(1) == 1
    monkeypatch.setenv(W.WORKERS_ENV, "lots")
    with pytest.warns(UserWarning):
        assert resolve_num_workers(None) == 0


def test_split_budget_totals_and_floor():
    # budget >= P: exact total, spread round-robin
    assert [_split_budget(8, 4, i) for i in range(4)] == [2, 2, 2, 2]
    assert [_split_budget(5, 4, i) for i in range(4)] == [2, 1, 1, 1]
    # 0 < budget < P rounds UP to one per partition (a serial partition
    # would gate the whole round-robin interleave)
    assert [_split_budget(2, 4, i) for i in range(4)] == [1, 1, 1, 1]
    assert [_split_budget(0, 4, i) for i in range(4)] == [0, 0, 0, 0]


class TestArena:
    def test_alloc_free_coalesce(self):
        a = _Arena(100)
        assert a.try_alloc(0, 40) == 0
        assert a.try_alloc(1, 40) == 40
        assert a.try_alloc(2, 30) is None  # only 20 left
        a.free(0)
        assert a.try_alloc(2, 30) == 0  # first-fit reuses the hole
        assert a.used == 100 - 10 - 20  # 30 + 40 live, [30,40)+[80,100) free

    def test_out_of_order_free_is_reusable(self):
        """The consumer's hold pattern: the OLDEST allocations (a batch's
        first views) stay live while everything after them churns — frees
        behind a live tail must still be reusable (the FIFO-ring design
        this replaced wedged full here and fell back to pickling)."""
        a = _Arena(100)
        assert a.try_alloc(0, 20) == 0  # held view (batch head)
        ids = 1
        for _ in range(50):  # churn far past capacity while id 0 is held
            got = a.try_alloc(ids, 40)
            assert got is not None and got >= 20
            a.free(ids)
            ids += 1
        a.free(0)
        assert a.used == 0

    def test_free_intervals_coalesce_both_sides(self):
        a = _Arena(90)
        assert a.try_alloc(0, 30) == 0
        assert a.try_alloc(1, 30) == 30
        assert a.try_alloc(2, 30) == 60
        a.free(0)
        a.free(2)
        a.free(1)  # merges with both neighbors
        assert a._free == [[0, 90]]
        assert a.try_alloc(3, 90) == 0

    def test_oversized_is_refused(self):
        a = _Arena(64)
        assert a.try_alloc(0, 65) is None
        assert a.try_alloc(1, 0) is None


# ---------------------------------------------------------------------------
# pool core: ordering, transport, gauges
# ---------------------------------------------------------------------------

def test_ordered_delivery_and_shm_transport():
    n = 41
    src = lambda: ({"plane": np.full((32, 32, 3), i % 251, np.uint8),
                    "label": np.int32(i)} for i in range(n))
    fn = lambda ex: {**ex, "plane": ex["plane"].astype(np.float32) / 255.0}
    pool = WorkerPool(src, fn, 3)
    got = list(pool.stream())
    want = [fn(e) for e in src()]
    assert len(got) == n
    for a, b in zip(got, want):
        assert int(a["label"]) == int(b["label"])  # inline (queue) path
        assert np.asarray(a["plane"]).tobytes() == b["plane"].tobytes()
    _assert_no_leaks()


def test_non_dict_results_unwrap():
    pool = WorkerPool(lambda: iter(range(10)),
                      lambda x: np.full(200, x, np.int32), 2)
    got = list(pool.stream())
    assert [int(g[0]) for g in got] == list(range(10))
    # both transports: 200×i32=800B rides shm, tiny arrays ride the queue
    pool2 = WorkerPool(lambda: iter(range(7)),
                       lambda x: np.int32(x * 2), 2)
    assert [int(v) for v in pool2.stream()] == [0, 2, 4, 6, 8, 10, 12]
    _assert_no_leaks()


def test_gauges_shape():
    pool = WorkerPool(lambda: iter(range(30)),
                      lambda x: {"v": np.full(400, x, np.float32)}, 2)
    s = pool.stream()
    for _ in range(10):
        next(s)
    g = pool.gauges()
    assert g["workers"] == 2 and len(g["per_worker"]) == 2
    agg = pool_gauges()
    assert agg["input_workers"] == 2
    assert set(agg) >= {"worker_util_mean", "worker_util_min",
                        "worker_items", "worker_overflow",
                        "worker_ahead_mean", "worker_ring_used_mb"}
    s.close()
    assert pool_gauges() == {}  # closed pools drop out of the rollup
    _assert_no_leaks()


# ---------------------------------------------------------------------------
# crash propagation
# ---------------------------------------------------------------------------

def test_worker_exception_propagates_typed():
    def boom(x):
        if x == 11:
            raise ValueError("poisoned example")
        return {"v": np.full(300, x, np.float32)}

    pool = WorkerPool(lambda: iter(range(40)), boom, 2)
    t0 = time.monotonic()
    with pytest.raises(WorkerCrashed) as ei:
        list(pool.stream())
    assert time.monotonic() - t0 < 30.0  # bounded wait
    assert "poisoned example" in str(ei.value)  # original traceback forwarded
    assert ei.value.worker in (0, 1)
    _assert_no_leaks()


def test_worker_sigkill_respawns_byte_identical():
    """A SIGKILL'd worker respawns (ISSUE 14): the replacement takes over
    the residue class fast-forwarded past what was already delivered, so
    the stream completes with EXACTLY the bytes of an unfaulted run, and
    the respawn leaves a `recovery` telemetry event."""
    from distributeddeeplearningspark_tpu import telemetry

    def work(x):
        time.sleep(0.002)
        return {"v": np.full(300, x, np.float32)}

    n = 400
    ref = [work(x)["v"].tobytes() for x in range(n)]
    events = []
    orig_emit = telemetry.emit
    telemetry.emit = lambda kind, **f: events.append({"kind": kind, **f})
    try:
        pool = WorkerPool(lambda: iter(range(n)), work, 2)
        s = pool.stream()
        got = [next(s)["v"].tobytes()]
        os.kill(pool._procs[0].pid, signal.SIGKILL)
        t0 = time.monotonic()
        for ex in s:
            got.append(ex["v"].tobytes())
        assert time.monotonic() - t0 < 30.0
    finally:
        telemetry.emit = orig_emit
    assert got == ref  # ordered, byte-identical despite the kill
    rec = [e for e in events if e["kind"] == "recovery"
           and e.get("event") == "input-worker-respawn"]
    assert len(rec) == 1 and rec[0]["worker"] == 0
    assert rec[0]["exitcode"] == -signal.SIGKILL
    _assert_no_leaks()


def test_worker_sigkill_escalates_when_budget_exhausted(monkeypatch):
    """With the respawn budget at 0, a dead worker is the old typed
    CRASH — bounded wait, exitcode preserved, full teardown."""
    monkeypatch.setenv("DLS_DATA_WORKER_MAX_RETRIES", "0")

    def work(x):
        time.sleep(0.01)
        return {"v": np.full(300, x, np.float32)}

    pool = WorkerPool(lambda: iter(range(10_000)), work, 2)
    s = pool.stream()
    next(s)
    victim = pool._procs[0]
    os.kill(victim.pid, signal.SIGKILL)
    t0 = time.monotonic()
    with pytest.raises(WorkerCrashed) as ei:
        for _ in s:
            pass
    assert time.monotonic() - t0 < 30.0
    assert ei.value.exitcode == -signal.SIGKILL
    assert "died" in str(ei.value)
    _assert_no_leaks()


def test_worker_repeated_kills_exhaust_budget():
    """Each respawn burns budget; kills past DLS_DATA_WORKER_MAX_RETRIES
    escalate. (Kill the same slot every time a replacement appears.)"""
    def work(x):
        time.sleep(0.005)
        return {"v": np.full(300, x, np.float32)}

    pool = WorkerPool(lambda: iter(range(10_000)), work, 2, max_retries=1)
    s = pool.stream()
    next(s)
    with pytest.raises(WorkerCrashed):
        killed = pool._procs[0]
        os.kill(killed.pid, signal.SIGKILL)
        for _ in s:
            if pool._procs[0] is not killed:  # replacement is up: kill it
                killed = pool._procs[0]
                os.kill(killed.pid, signal.SIGKILL)
    _assert_no_leaks()


def test_interpreter_exit_leaks_nothing(tmp_path):
    """A script that abandons a live pool mid-stream must still exit
    cleanly, reap its workers (daemon), and leave no shm segment behind
    (finalize/atexit + resource tracker)."""
    script = r"""
import numpy as np, sys
from distributeddeeplearningspark_tpu.data.workers import WorkerPool
pool = WorkerPool(lambda: iter(range(10_000)),
                  lambda x: {"v": np.full(500, x, np.float32)}, 2)
s = pool.stream()
for _ in range(5):
    next(s)
print("pid", __import__("os").getpid())
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    pid = int(out.stdout.split()[-1])
    if os.path.isdir("/dev/shm"):
        left = [f for f in os.listdir("/dev/shm")
                if f.startswith(f"dlsw-{pid}-")]
        assert not left, left


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------

def test_bounded_inflight_under_slow_consumer():
    pool = WorkerPool(lambda: iter(range(500)),
                      lambda x: {"v": np.full(300, x, np.float32)},
                      1, max_ahead=4)
    s = pool.stream()
    consumed = 0
    for _ in range(6):
        next(s)
        consumed += 1
        time.sleep(0.05)  # slow consumer; views dropped promptly
        g = pool.gauges()["per_worker"][0]
        # produced never runs past consumed + queue bound (+1 handoff)
        assert g["items"] <= consumed + 4 + 1, g
    # give the worker a beat: it must be parked at the bound, not running on
    time.sleep(0.3)
    g = pool.gauges()["per_worker"][0]
    assert g["items"] <= consumed + 4 + 1, g
    assert g["overflow"] == 0
    s.close()
    _assert_no_leaks()


def test_ring_backpressure_overflows_not_deadlocks():
    """A consumer that HOLDS every view (worst case) exceeds a tiny ring;
    the pool must degrade to queue transport (overflow gauge), never
    deadlock, and the stream must stay correct and ordered."""
    pool = WorkerPool(
        lambda: iter(range(40)),
        lambda x: {"v": np.full((64, 64), x, np.float32)},  # 16 KB each
        1, ring_bytes=1 << 20, max_ahead=8)
    held = list(pool.stream())  # holds all 40 views: 640 KB < ring, ok…
    assert [int(h["v"][0, 0]) for h in held] == list(range(40))
    # …now an actually-too-small ring: 3 examples fill it
    pool2 = WorkerPool(
        lambda: iter(range(12)),
        lambda x: {"v": np.full((128, 128, 3), x, np.float32)},  # 196 KB
        1, ring_bytes=1 << 19, max_ahead=4)
    t0 = time.monotonic()
    held2 = list(pool2.stream())
    assert time.monotonic() - t0 < 60.0
    assert [int(h["v"][0, 0, 0]) for h in held2] == list(range(12))
    _assert_no_leaks()


# ---------------------------------------------------------------------------
# WorkerMappedDataset + feed integration
# ---------------------------------------------------------------------------

def _toy_base(n=60, parts=3):
    return PartitionedDataset.parallelize(
        [{"x": np.full((16, 16), i, np.float32), "label": np.int32(i)}
         for i in range(n)], parts)


def _tf(ex):
    return {"x": ex["x"] * 2.0 + 1.0, "label": ex["label"]}


def test_worker_mapped_dataset_parity_and_fallback():
    base = _toy_base()
    serial = [[_tf(e) for e in base.iter_partition(i)] for i in range(3)]
    for nw in (0, 1, 4):
        ds = WorkerMappedDataset(base, _tf, nw)
        assert ds.num_partitions == 3
        assert ds.is_infinite is False
        for i in range(3):
            got = list(ds.iter_partition(i))
            assert len(got) == len(serial[i])
            for a, b in zip(got, serial[i]):
                assert np.asarray(a["x"]).tobytes() == b["x"].tobytes()
                assert int(a["label"]) == int(b["label"])
    _assert_no_leaks()


def test_host_batches_num_workers_knob():
    base = _toy_base(48, 2)
    ds = WorkerMappedDataset(base, _tf, 0)  # dataset says serial
    ref = list(host_batches(ds, 8))
    # the feed knob overrides the dataset's setting; bytes must not change
    got = list(host_batches(ds, 8, num_workers=3))
    assert len(ref) == len(got) == 6
    for a, b in zip(ref, got):
        for k in a:
            assert a[k].tobytes() == b[k].tobytes()
    # plain datasets ignore the knob (nothing to fan out)
    plain = base.map(_tf)
    got2 = list(host_batches(plain, 8, num_workers=3))
    for a, b in zip(ref, got2):
        for k in a:
            assert a[k].tobytes() == b[k].tobytes()
    _assert_no_leaks()


def test_fast_forward_resume_parity():
    """Trainer resume burns host batches with islice: batch k..k+2 of a
    fast-forwarded pooled feed must equal the uninterrupted stream's."""
    import itertools

    base = _toy_base(96, 2)
    ds = WorkerMappedDataset(base, _tf, 2)
    straight = list(itertools.islice(host_batches(ds, 8), 8))
    resumed = list(itertools.islice(host_batches(ds, 8), 5, 8))
    for a, b in zip(straight[5:], resumed):
        for k in a:
            assert a[k].tobytes() == b[k].tobytes()
    _assert_no_leaks()


def test_probe_snapshot_carries_worker_gauges():
    from distributeddeeplearningspark_tpu.data.prefetch import StarvationProbe

    base = _toy_base(40, 2)
    ds = WorkerMappedDataset(base, _tf, 2)
    probe = StarvationProbe()
    feed = host_batches(ds, 8)
    next(feed)
    snap = probe.snapshot()
    assert snap["input_workers"] == 2
    assert 0.0 <= snap["worker_util_mean"] <= 1.0
    assert snap["worker_items"] >= 8
    feed.close()
    # with no live pool the keys disappear (non-worker runs emit nothing new)
    assert "input_workers" not in probe.snapshot()
    _assert_no_leaks()


def test_dlstatus_reports_input_workers(tmp_path):
    from distributeddeeplearningspark_tpu import status, telemetry

    w = telemetry.EventWriter(str(tmp_path), process=0, host=0)
    w.step_metrics(10, steps=10, lap_s=1.0, metrics={"loss": 1.0},
                   input_wait_s=0.0, input_workers=4, worker_util_mean=0.97,
                   worker_util_min=0.91, worker_items=640,
                   worker_overflow=0, worker_ahead_mean=3.5,
                   worker_ring_used_mb=12.0)
    w.close()
    rep = status.report(str(tmp_path))
    assert rep["input_workers"]["input_workers"] == 4
    text = status.render(rep)
    assert "input workers: 4 process(es)" in text
    assert "util mean=0.97" in text
    assert "verdict:" in text


# ---------------------------------------------------------------------------
# real-path determinism: vision JPEG, records, batched-fused, text
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def jpeg_root(tmp_path_factory):
    from PIL import Image

    root = tmp_path_factory.mktemp("jpegs")
    rng = np.random.default_rng(0)
    for cls in range(2):
        d = root / f"class_{cls}"
        d.mkdir()
        for i in range(8):
            arr = rng.integers(0, 255, (72, 88, 3), np.uint8)
            Image.fromarray(arr).save(str(d / f"img_{i}.jpg"), quality=90)
    return str(root)


# Quarantine of the environmental byte-identity flake (ROADMAP:
# fails identically on clean HEAD and polluted every tier-1 read). Probed
# root cause on the shared CI box: the THREAD-POOL in-process arm
# (``map_parallel`` with its default thread count) is nondeterministic
# RUN-TO-RUN — concurrent native-kernel invocations race — while decode and
# transform are bit-stable called sequentially, and BOTH the truly serial
# map (``num_threads=0``) and the worker-pool arm (any width) reproduce
# exactly and agree byte-for-byte. The determinism tests therefore use the
# serial map as the in-process reference: the contract under test is
# pipeline alignment across worker counts, not the thread pool's scheduling.
_SERIAL_MAP = {"num_threads": 0}


def _take_batches(feed, n):
    return [next(feed) for _ in range(n)]


def test_vision_jpeg_path_byte_identical_across_workers(jpeg_root):
    from distributeddeeplearningspark_tpu.data.sources import imagenet_folder
    from distributeddeeplearningspark_tpu.data.vision import imagenet_train

    def batches(nw):
        ds = imagenet_train(
            imagenet_folder(jpeg_root, num_partitions=2, decode=False),
            seed=0, size=48, repeat=True, num_workers=nw,
            **(_SERIAL_MAP if nw == 0 else {}))
        feed = host_batches(ds, 8)
        out = _take_batches(feed, 3)
        feed.close()
        return out

    b0, b1, b4 = batches(0), batches(1), batches(4)
    for x, y, z in zip(b0, b1, b4):
        assert x.keys() == y.keys() == z.keys()
        for k in x:
            assert (x[k].tobytes() == np.asarray(y[k]).tobytes()
                    == np.asarray(z[k]).tobytes()), k
    _assert_no_leaks()


def test_records_and_batched_fused_byte_identical(jpeg_root, tmp_path):
    from distributeddeeplearningspark_tpu.data.records import (
        array_records, write_imagenet_records)
    from distributeddeeplearningspark_tpu.data.vision import (
        imagenet_train, imagenet_train_batched)

    rec = str(tmp_path / "recs")
    write_imagenet_records(jpeg_root, rec, size=56, num_shards=2)

    def per_example(nw):
        feed = host_batches(
            imagenet_train(array_records(rec), seed=0, size=48, repeat=True,
                           num_workers=nw,
                           **(_SERIAL_MAP if nw == 0 else {})), 8)
        out = _take_batches(feed, 3)
        feed.close()
        return out

    def fused(nw):
        feed = imagenet_train_batched(
            array_records(rec).shuffle(0).repeat(), 8, size=48, seed=0,
            num_workers=nw)
        out = _take_batches(feed, 3)
        feed.close()
        return out

    for a, b in zip(per_example(0), per_example(4)):
        for k in a:
            assert a[k].tobytes() == np.asarray(b[k]).tobytes(), k
    for a, b in zip(fused(0), fused(2)):
        for k in a:
            assert a[k].tobytes() == np.asarray(b[k]).tobytes(), k
    _assert_no_leaks()


def test_text_tokenize_paths_byte_identical():
    from distributeddeeplearningspark_tpu.data.text import (
        WordPieceTokenizer, lm_dataset, mlm_dataset, synthetic_wikipedia)

    docs = synthetic_wikipedia(20, num_partitions=2)
    tok = WordPieceTokenizer.train(docs.collect(), vocab_size=256)
    builders = [
        lambda nw: mlm_dataset(docs, tok, seq_len=32, segment_ids=True,
                               num_workers=nw),
        lambda nw: mlm_dataset(docs, tok, seq_len=32, pack=False,
                               num_workers=nw),
        lambda nw: lm_dataset(docs, tok, seq_len=32, segment_ids=True,
                              num_workers=nw),
    ]
    for build in builders:
        ref = [e for i in range(2) for e in build(0).iter_partition(i)]
        pooled = [e for i in range(2) for e in build(3).iter_partition(i)]
        assert len(ref) == len(pooled) > 0
        for a, b in zip(ref, pooled):
            assert a.keys() == b.keys()
            for k in a:
                assert (np.asarray(a[k]).tobytes()
                        == np.asarray(b[k]).tobytes()), k
    _assert_no_leaks()
