"""The program's own spans (telemetry/spans.py): one helper that writes a
profiler span and feeds the per-lap accumulators, on fake clocks and in one
real trace of a tiny ``Trainer.fit`` on the CPU."""

import glob
import threading
import time

import jax
import numpy as np
import optax
import pytest

from distributeddeeplearningspark_tpu import (
    PartitionedDataset,
    Session,
    Trainer,
    telemetry,
)
from distributeddeeplearningspark_tpu.checkpoint import Checkpointer
from distributeddeeplearningspark_tpu.data import feed, prefetch
from distributeddeeplearningspark_tpu.data.prefetch import StarvationProbe
from distributeddeeplearningspark_tpu.models import LeNet5
from distributeddeeplearningspark_tpu.telemetry import anatomy, spans
from distributeddeeplearningspark_tpu.train import losses

LOOP_SECTIONS = ("device_dispatch_s", "device_drain_s", "compile_in_lap_s",
                 "emit_s", "callbacks_s", "checkpoint_s", "eval_s")


class FakeClock:
    """Seconds that pass only when a test says so; counts who read it."""

    def __init__(self):
        self.t = 0.0
        self._lock = threading.Lock()
        self.reads: dict[str, int] = {}

    def tick(self, dt):
        with self._lock:
            self.t += dt

    def __call__(self):
        with self._lock:
            name = threading.current_thread().name
            self.reads[name] = self.reads.get(name, 0) + 1
            return self.t


def _mnist_like(n=64):
    rng = np.random.default_rng(0)
    return [{"image": rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
             "label": np.int32(i % 10)} for i in range(n)]


# -- the helper and the loop's accumulator ------------------------------------


def test_sections_and_unaccounted_tile_the_lap_nested_and_repeated():
    clock = FakeClock()
    anat = anatomy.StepAnatomy(clock=clock)
    anat.reset()
    clock.tick(0.5)                              # the loop's own Python
    for _ in range(3):                           # a repeated section
        with spans.span("dls.step/dispatch", anat):
            clock.tick(1.0)
    with spans.span("dls.fit/eval", anat):       # a nested one
        clock.tick(2.0)
        with spans.span("dls.fit/sync", anat):
            clock.tick(0.25)
            with spans.span("dls.fit/emit", anat):
                clock.tick(0.125)
        clock.tick(1.0)
    with spans.span("dls.fit/callbacks", anat):
        clock.tick(0.5)
    with pytest.raises(KeyError):                # a section that raises is
        with spans.span("dls.fit/checkpoint", anat):      # not counted
            clock.tick(4.0)
            raise KeyError("disk")
    rec = anat.lap(steps=3, input_wait_s=1.5, input_put_s=0.75)
    # nothing of the feed's ran on this clock: the lap is 11.375 s long and
    # the 2.25 s handed in as wait and put come off what no section covers
    assert rec["anatomy_wall_s"] == pytest.approx(11.375)
    assert rec["device_dispatch_s"] == pytest.approx(3.0)
    assert rec["eval_s"] == pytest.approx(3.0)           # its own time only
    assert rec["device_drain_s"] == pytest.approx(0.25)
    assert rec["emit_s"] == pytest.approx(0.125)
    assert rec["callbacks_s"] == pytest.approx(0.5)
    assert rec["checkpoint_s"] == 0.0
    assert rec["unaccounted_s"] == pytest.approx(0.5 + 4.0 - 1.5 - 0.75)
    assert (sum(rec[k] for k in LOOP_SECTIONS) + 1.5 + 0.75
            + rec["unaccounted_s"]) == pytest.approx(rec["anatomy_wall_s"])
    # host_s is the residual it always was
    assert rec["host_s"] == pytest.approx(11.375 - 3.0 - 0.25 - 1.5)
    # lap() started a new lap with every counter at zero
    clock.tick(1.0)
    rec2 = anat.lap(steps=0)
    assert rec2["unaccounted_s"] == pytest.approx(1.0)
    assert all(rec2[k] == 0.0 for k in LOOP_SECTIONS)


def test_every_counter_has_one_home():
    """One list of names: each counter of the list belongs to the loop's
    accumulator, to the feed's or to the start's, and each accumulator takes
    every name of its own."""
    anat, probe = anatomy.StepAnatomy(), StarvationProbe()
    start = anatomy.StartupLedger()
    of_start = {k for n, k in spans.COUNTERS.items()
                if n.startswith(spans.START_PREFIX)}
    loop = {k for k in spans.COUNTERS.values()
            if not k.startswith("input_")} - of_start
    assert loop == set(anat.lap(steps=0)) & set(spans.COUNTERS.values())
    for name, key in spans.COUNTERS.items():
        (probe if key.startswith("input_") else
         start if key in of_start else anat).add(name, 1.0)
    assert of_start <= set(start.first_lap(steps=0, lap={}, feed={},
                                           compiles=[]))
    snap = probe.snapshot()
    assert {k for k in spans.COUNTERS.values() if k.startswith("input_")} \
        <= set(snap)
    assert all(snap[k] == 1.0 for k in snap if k in spans.COUNTERS.values())
    assert spans.SPAN_NAMES == (*spans.COUNTERS, spans.PHASE_PREFIX, "train")


def test_without_a_sink_the_helper_is_a_bare_trace_annotation():
    assert type(spans.span("dls.fit/emit")) is jax.profiler.TraceAnnotation
    telemetry.reset()        # no writer: a phase is its profiler span alone
    assert type(telemetry.phase("restore")) is jax.profiler.TraceAnnotation


@pytest.mark.parametrize("first_fit", [True, False])
def test_fit_without_telemetry_builds_no_accumulator(monkeypatch, first_fit):
    monkeypatch.delenv(telemetry.WORKDIR_ENV, raising=False)
    telemetry.reset()
    # the process's first fit, whose start is still open, and a later one
    start = anatomy.StartupLedger()
    monkeypatch.setattr(anatomy, "STARTUP", start)
    if not first_fit:
        start.first_lap(steps=0, lap={}, feed={}, compiles=[])
    built = []
    for cls in (anatomy.StepAnatomy, StarvationProbe):
        real = cls.__init__
        monkeypatch.setattr(
            cls, "__init__",
            lambda self, *a, _real=real, **k: (built.append(type(self)),
                                               _real(self, *a, **k))[1])
    sinks = []
    real_span = spans.span
    monkeypatch.setattr(
        spans, "span",
        lambda name, sink=None: (sinks.append((name, sink)),
                                 real_span(name, sink))[1])
    spark = Session.builder.master("local[1]").getOrCreate()
    ds = PartitionedDataset.parallelize(_mnist_like(32), 2).repeat()
    trainer = Trainer(spark, LeNet5(), losses.softmax_xent, optax.sgd(0.01))
    trainer.fit(ds, batch_size=8, steps=4, log_every=2,
                callbacks=[lambda step, metrics: None])
    assert built == []
    # the sections of the start, which run once a process, have its ledger
    of_start = [s for n, s in sinks if n.startswith(spans.START_PREFIX)]
    assert of_start and all(s is (start if first_fit else None)
                            for s in of_start)
    rest = [s for n, s in sinks if not n.startswith(spans.START_PREFIX)]
    assert len(rest) > 10 and all(s is None for s in rest)
    assert trainer._train_step._anatomy is None
    assert (start.summary()["steps"] == 2) is first_fit


# -- the feed's accumulator ---------------------------------------------------


def test_blocked_and_stack_seconds_with_a_ring_of_one(monkeypatch):
    """Four batches through a ring of one. Only one thread moves at a time:
    the consumer ticks the clock while the producer stands in ``q.put`` (it
    knows from the number of clock reads the producer has made), and each
    example's copy ticks it on the producer's side."""
    clock = FakeClock()
    probe = StarvationProbe(clock=clock)
    real_copy = feed._copy_rows

    def slow_copy(arrays, at, examples):
        clock.tick(1.0 * len(examples))
        return real_copy(arrays, at, examples)

    monkeypatch.setattr(feed, "_copy_rows", slow_copy)
    ds = PartitionedDataset.parallelize(
        [{"x": np.float32(i)} for i in range(8)], 1)
    gen = prefetch._background(feed.host_batches(ds, 2), maxsize=1,
                               probe=probe)

    def producer_parked_after(reads):
        deadline = time.monotonic() + 30
        while clock.reads.get("dls-prefetch", 0) < reads:
            assert time.monotonic() < deadline, clock.reads
            time.sleep(0.001)
        time.sleep(0.02)   # past the read, into q.put
        assert clock.reads["dls-prefetch"] == reads

    got = [next(gen)]
    # a batch costs the producer eight reads (assemble, stack, its slot,
    # ring_full: in and out), the stream's first ten (its first example is
    # copied alone: the stream does not know its size yet); it now holds b3
    # with b2 in the ring: 10 + 8 + 7 reads
    producer_parked_after(25)
    clock.tick(5.0)
    got.append(next(gen))
    producer_parked_after(33)        # b3 went in; it holds b4
    clock.tick(3.0)
    got += list(gen)
    assert [b["x"].tolist() for b in got] == [[0, 1], [2, 3], [4, 5], [6, 7]]
    snap = probe.snapshot()
    assert snap["input_blocked_s"] == pytest.approx(8.0)
    assert snap["input_stack_s"] == pytest.approx(8.0)
    # the assembly's time is inclusive: the stack is a part of it
    assert snap["input_assembly_s"] == pytest.approx(8.0)
    assert "input_map_s" not in snap
    # ``got`` keeps every batch, so none could be filled into a kept slot
    assert (snap["input_slot_new"], snap["input_slot_reused"]) == (4, 0)
    assert probe.snapshot()["input_blocked_s"] == 0.0


def test_map_parallel_keeps_order_and_counts_thread_seconds():
    def slow_square(x):
        time.sleep(0.01)
        return x * x

    ds = PartitionedDataset.parallelize(list(range(24)), 2).map_parallel(
        slow_square, num_threads=4)
    probe = StarvationProbe()
    spans.bind_sink(probe)       # as the feed's thread does
    try:
        got = [list(ds.iter_partition(i)) for i in range(2)]
    finally:
        spans.bind_sink(None)
    assert got == [[x * x for x in range(0, 12)],
                   [x * x for x in range(12, 24)]]
    assert probe.snapshot()["input_map_s"] >= 24 * 0.01
    # a thread that bound nothing: the same results, nothing counted
    assert list(ds.iter_partition(0)) == got[0]
    assert "input_map_s" not in StarvationProbe().snapshot()


def test_decode_seconds_reach_step_metrics_inside_the_maps(tmp_path,
                                                          monkeypatch):
    """``decode_jpeg`` opens ``dls.feed/decode`` itself and finds the feed's
    sink bound in ``map_parallel``'s threads: its thread-seconds ride
    ``step_metrics`` as ``input_decode_s``, a part of ``input_map_s``."""
    import io

    Image = pytest.importorskip("PIL.Image")
    from distributeddeeplearningspark_tpu.data import vision

    assert "dls.feed/decode" in spans.SPAN_NAMES
    assert spans.COUNTERS["dls.feed/decode"] == "input_decode_s"
    rng = np.random.default_rng(0)
    jpegs = []
    for i in range(32):
        buf = io.BytesIO()
        Image.fromarray(rng.integers(0, 255, (28, 28), np.uint8), "L").save(
            buf, format="JPEG", quality=90)
        jpegs.append({"jpeg": buf.getvalue(), "label": np.int32(i % 10)})

    def to_example(ex):
        assert spans.bound_sink() is not None    # bound when the thread began
        img = vision.decode_jpeg(ex["jpeg"])
        time.sleep(0.001)     # the rest of the map: a call that ends in one
        # lap with its decode counted in the lap before cannot tip the sums
        return {"image": img.astype(np.float32) / 255.0, "label": ex["label"]}

    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path / "tele"))
    spark = Session.builder.master("local[1]").getOrCreate()
    ds = (PartitionedDataset.parallelize(jpegs, 2).repeat()
          .map_parallel(to_example, num_threads=2))
    trainer = Trainer(spark, LeNet5(), losses.softmax_xent, optax.sgd(0.01))
    trainer.init(feed.stack_examples(_mnist_like(16)))
    try:
        trainer.fit(ds, batch_size=16, steps=8, log_every=4)
    finally:
        telemetry.reset()
    laps = [e for e in telemetry.read_events(tmp_path / "tele")
            if e["kind"] == "step_metrics"]
    assert len(laps) == 2
    assert all(e["input_decode_s"] > 0 for e in laps)
    assert (sum(e["input_decode_s"] for e in laps)
            <= sum(e["input_map_s"] for e in laps))
    # a feed that decodes nothing has no such key
    assert "input_decode_s" not in StarvationProbe().snapshot()


def test_the_benchmarks_reader_of_decode_seconds():
    """``benchmark/layer_metrics/feed_decode_us_per_item.py`` on hand-made
    laps: thread-microseconds per item over the laps that have the counter;
    the parent's laps, which lack it, read nothing and raise nothing."""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "feed_decode_us_per_item", os.path.join(
            root, "benchmark", "layer_metrics", "feed_decode_us_per_item.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    lap = {"steps": 2, "lap_s": 1.0, "input_map_s": 4.0}
    ctx = {"laps": [{**lap, "input_decode_s": 1.0},
                    {**lap, "input_decode_s": 2.0}], "items_per_step": 250}
    assert reader.read(ctx) == pytest.approx(3000.0)
    assert reader.read({"laps": [lap, lap], "items_per_step": 250}) is None
    assert reader.read({"laps": [], "items_per_step": 250}) is None
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "feed_decode_us_per_item"]
    assert entry == [{
        "name": "feed_decode_us_per_item", "unit": "us/item",
        "better": "lower", "source": "program_counter", "layer": "input",
        "moves": "throughput", "workloads": ["resnet50_imagenet.fit_jpeg"]}]


def test_put_seconds_are_the_loop_threads_and_ride_the_snapshot():
    clock = FakeClock()
    probe = StarvationProbe(clock=clock)

    def put(batch, mesh):
        clock.tick(0.5)
        return batch

    out = list(prefetch.prefetch_to_device(
        iter([{"x": 1}, {"x": 2}, {"x": 3}]), mesh=None, put=put,
        background=False, probe=probe))
    assert len(out) == 3
    snap = probe.snapshot()
    assert snap["input_put_s"] == pytest.approx(1.5)
    assert snap["input_waits"] == 3 and snap["input_wait_s"] == 0.0


# -- the compile ledger -------------------------------------------------------


def test_compile_event_splits_lower_from_backend(tmp_path):
    import jax.numpy as jnp

    telemetry.configure(tmp_path)
    try:
        fn = anatomy.instrument(jax.jit(lambda x: x @ x + 1), name="sq")
        anat = anatomy.StepAnatomy()
        fn.attach_anatomy(anat)
        fn(jnp.ones((8, 8), jnp.float32))
        (e,) = [e for e in telemetry.read_events(tmp_path)
                if e["kind"] == "compile"]
    finally:
        telemetry.reset()
    assert e["lower_s"] > 0 and e["backend_s"] > 0
    assert e["lower_s"] + e["backend_s"] == pytest.approx(e["compile_s"],
                                                          abs=2e-6)
    summary = fn.compile_summary()
    assert summary["total_lower_s"] == e["lower_s"]
    assert summary["total_backend_s"] == e["backend_s"]
    lap = anat.lap(steps=1)
    assert lap["compile_in_lap_s"] == pytest.approx(e["compile_s"], rel=0.2)


# -- one real trace -----------------------------------------------------------


def _lines_with_spans(xplane):
    """[(line name, {span name: [(start_ns, end_ns)]})] of the host lines
    that hold one of the program's spans."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane).planes:
        for ln in plane.lines:
            found: dict[str, list] = {}
            for e in ln.events:
                if e.name.startswith("dls.") or e.name == "train":
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
            if found:
                out.append((ln.name, found))
    return out


def test_a_traced_fit_holds_every_span_on_named_lines(tmp_path, monkeypatch):
    # a process whose start is on record, whatever ran before this test: no
    # `dls.start/*` section on the loop's line (tests/test_startup_spans.py
    # has the profile that holds them)
    done = anatomy.StartupLedger()
    done.first_lap(steps=0, lap={}, feed={}, compiles=[])
    monkeypatch.setattr(anatomy, "STARTUP", done)
    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path / "tele"))
    spark = Session.builder.master("local[1]").getOrCreate()
    # one partition: the feed asks its pool to fill the rows (a stream that
    # is pulled opens the same sections but ``dls.feed/filled_by_map``)
    ds = (PartitionedDataset.parallelize(_mnist_like(), 1).repeat()
          .map_parallel(dict, num_threads=2))
    trainer = Trainer(spark, LeNet5(), losses.softmax_xent, optax.sgd(0.01))
    # fit would draw this sample itself, through pools of its own whose
    # threads end at once; the profiler tells threads apart by an id that a
    # later thread may take over, and would then merge the two lines
    trainer.init(feed.stack_examples(_mnist_like(16)))
    # the caller's own profiler, as the benchmark starts it: no `profile=`
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        trainer.fit(ds, batch_size=16, steps=8, log_every=4,
                    callbacks=[lambda step, metrics: None])
    finally:
        jax.profiler.stop_trace()
        telemetry.reset()
    (xplane,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
    lines = _lines_with_spans(xplane)
    names = [n for n, _ in lines]
    # no two lines that hold the program's spans share a name (the
    # benchmark's extract keys host lines by name and keeps the last)
    assert len(names) == len(set(names)), names
    by_line = dict(lines)
    # this run saves no checkpoint, evaluates nothing and decodes no JPEG
    of_start = {n for n in spans.COUNTERS if n.startswith(spans.START_PREFIX)}
    expected = (set(spans.COUNTERS) - of_start
                - {"dls.fit/checkpoint", "dls.fit/eval", "dls.feed/decode"}
                | {"train", spans.PHASE_PREFIX + "compile"})
    # (whether eight steps get to fill a kept slot again is the threads' race)
    reused = {"dls.feed/slot_reused"}
    assert {s for found in by_line.values() for s in found} | reused == expected
    producer = by_line["dls-prefetch"]
    assert set(producer) | reused == {
        "dls.feed/assemble", "dls.feed/stack", "dls.feed/ring_full",
        "dls.feed/slot_new", "dls.feed/slot_reused", "dls.feed/filled_by_map"}
    # a row's copy is a part of the assembly, and a batch's first takes the
    # slot (the assembly that the end of the trace cut off is not in it)
    for outer, inner in (("dls.feed/assemble", "dls.feed/stack"),
                         ("dls.feed/stack", "dls.feed/slot_new")):
        whole = max(hi for _, hi in producer[outer])
        for a, b in producer[inner]:
            assert b > whole or any(lo <= a and b <= hi
                                    for lo, hi in producer[outer])
    pool = {n: f for n, f in by_line.items() if n.startswith("dls-map-")}
    assert pool and all(set(f) == {"dls.feed/map"} for f in pool.values())
    (loop,) = [f for n, f in by_line.items()
               if n != "dls-prefetch" and n not in pool]
    assert set(loop) == expected - set(producer) - reused - {"dls.feed/map"}
    assert len(loop["train"]) == len(loop["dls.step/dispatch"]) == 8
    assert len(loop["dls.fit/sync"]) == len(loop["dls.fit/emit"]) == 2
    # and the same sections as counters, in every lap, tiling it
    laps = [e for e in telemetry.read_events(tmp_path / "tele")
            if e["kind"] == "step_metrics"]
    assert len(laps) == 2
    for e in laps:
        assert (sum(e[k] for k in LOOP_SECTIONS) + e["input_wait_s"]
                + e["input_put_s"] + e["unaccounted_s"]) == pytest.approx(
                    e["anatomy_wall_s"], abs=1e-4)
        assert e["input_map_s"] > 0 and e["input_stack_s"] > 0
    assert laps[0]["compile_in_lap_s"] > 0 and laps[1]["emit_s"] > 0


def test_checkpoint_and_eval_are_sections_of_the_lap(tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path / "tele"))
    spark = Session.builder.master("local[1]").getOrCreate()
    data = _mnist_like(32)
    ds = PartitionedDataset.parallelize(data, 2)
    try:
        with Checkpointer(tmp_path / "ck", async_save=False) as ck:
            trainer = Trainer(spark, LeNet5(), losses.softmax_xent,
                              optax.sgd(0.01), checkpointer=ck)
            trainer.fit(ds.repeat(), batch_size=8, steps=6, log_every=2,
                        checkpoint_every=2, eval_dataset=ds, eval_every=2)
    finally:
        telemetry.reset()
    laps = [e for e in telemetry.read_events(tmp_path / "tele")
            if e["kind"] == "step_metrics"]
    # the save and the eval at a boundary step run after its lap closed:
    # they are the next lap's
    assert all(e["checkpoint_s"] > 0 and e["eval_s"] > 0 for e in laps[1:])
    for e in laps:
        assert e["unaccounted_s"] < 0.5 * e["anatomy_wall_s"]
