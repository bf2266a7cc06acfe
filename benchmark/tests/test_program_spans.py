"""The eight layer metrics that read the program's own spans and counters
(PR 24): each reader on hand-made laps and a hand-made extract, the extract of
a real CPU trace with a named producer thread, and what a program without the
spans (the parent) gives: nothing, and no error."""

import json
import os
import threading

import pytest

from benchmark.harness import runner, trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ("feed_put_ms_per_step", "feed_stack_us_per_item",
       "feed_map_us_per_item", "feed_blocked_share", "loop_emit_share",
       "loop_unaccounted_share", "idle_attributed_share", "step_lower_s")


def reader(name):
    return runner.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def lap(**over):
    """A lap of 10 steps in 2 s as the program writes it with telemetry on."""
    return {"steps": 10, "lap_s": 2.0, "anatomy_wall_s": 2.0,
            "input_wait_s": 0.1, "input_put_s": 0.05,
            "input_assembly_s": 1.2, "input_stack_s": 0.4,
            "input_blocked_s": 0.6, "emit_s": 0.02, "callbacks_s": 0.001,
            "unaccounted_s": 0.08, **over}


def ctx(laps, trace=None, compile_=None):
    return {"laps": laps, "trace": trace, "items_per_step": 100,
            "compile": compile_ or {}, "facts": {}}


def ev(name, start_us, dur_us):
    return [name, start_us * 1e3, dur_us * 1e3, {}]


def extract(ops, host):
    return {"devices": {"0": {tr.OP_LINE: ops}}, "host": host, "planes": []}


def test_the_eight_are_listed_last_and_each_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert tuple(m["name"] for m in bench["per_layer"][-8:]) == NEW
    for m in bench["per_layer"][-8:]:
        assert callable(reader(m["name"]).read)
        assert m["moves"] == ("setup_s" if m["name"] == "step_lower_s"
                              else "throughput")
    cells = {m["name"]: m.get("workloads") for m in bench["per_layer"][-8:]}
    assert cells.pop("feed_map_us_per_item") == ["resnet50_imagenet.fit_jpeg"]
    assert set(cells.values()) == {None}


def test_counter_readers_on_hand_made_laps():
    laps = [lap(), lap(input_put_s=0.15, input_blocked_s=0.2, emit_s=0.06)]
    c = ctx(laps, compile_={"total_compile_s": 13.0, "total_lower_s": 11.5})
    # 0.20 s of put over 20 steps; 0.8 s of stack over 2,000 items
    assert reader("feed_put_ms_per_step").read(c) == pytest.approx(10.0)
    assert reader("feed_stack_us_per_item").read(c) == pytest.approx(400.0)
    assert reader("feed_blocked_share").read(c) == pytest.approx(20.0)
    assert reader("loop_emit_share").read(c) == pytest.approx(2.0)
    assert reader("loop_unaccounted_share").read(c) == pytest.approx(4.0)
    assert reader("step_lower_s").read(c) == 11.5


def test_a_cell_without_a_parallel_map_leaves_its_metric_out():
    assert reader("feed_map_us_per_item").read(ctx([lap(), lap()])) is None
    laps = [lap(input_map_s=1.5), lap(input_map_s=2.5)]
    # 4 thread-seconds over 2,000 items: more than the 2 ms of wall an item
    assert reader("feed_map_us_per_item").read(ctx(laps)) == \
        pytest.approx(2000.0)


def test_a_program_without_the_counters_reads_nothing():
    """The parent's laps and ledger, and no laps at all: every reader
    returns None and none raises."""
    old = {"steps": 10, "lap_s": 2.0, "anatomy_wall_s": 2.0, "host_s": 0.7,
           "input_wait_s": 0.1, "input_assembly_s": 1.2}
    old_trace = extract([ev("fusion.1", 0, 100), ev("fusion.2", 300, 100)],
                        {"python3": [ev("shard_args", 90, 220)]})
    for c in (ctx([old, old], old_trace, {"total_compile_s": 13.0}),
              ctx([], None, {})):
        for name in NEW:
            assert reader(name).read(c) is None, name
        assert c["facts"] == {}


def test_idle_half_under_wait_and_half_under_nothing_is_fifty_percent():
    # device 0 is busy 0-100 us and 300-400 us: one gap of 200 us. The loop
    # waits for a batch from 80 to 200 us (100 us of the gap), then a span
    # of the runtime's covers the rest, which is not the program's
    ops = [ev("fusion.1", 0, 100), ev("fusion.2", 300, 100)]
    host = {"python3": [ev("dls.feed/wait", 80, 120),
                        ev("shard_args", 200, 100),
                        ev("dls.feed/assemble", 0, 400)],  # not the loop's
            "dls-prefetch": [ev("dls.feed/stack", 100, 200)]}
    c = ctx([lap()], extract(ops, host))
    assert reader("idle_attributed_share").read(c) == pytest.approx(50.0)
    assert c["facts"]["idle_by_span"] == pytest.approx(
        {"dls.feed/wait": 100e-6, "none": 100e-6})


def test_idle_by_span_names_each_of_the_loops_spans():
    # gaps: 100-300 us and 400-500 us; put covers 100-150, the sync 250-300
    # and dispatch, wherever its line is, 420-480: 160 of 300 us
    ops = [ev("fusion.1", 0, 100), ev("fusion.2", 300, 100),
           ev("fusion.3", 500, 50)]
    host = {"python3": [ev("dls.feed/put", 50, 100),
                        ev("dls.fit/sync", 250, 100)],
            "main": [ev("dls.step/dispatch", 420, 60)]}
    c = ctx([lap()], extract(ops, host))
    assert reader("idle_attributed_share").read(c) == \
        pytest.approx(100 * 160 / 300)
    assert c["facts"]["idle_by_span"] == pytest.approx(
        {"dls.feed/put": 50e-6, "dls.fit/sync": 50e-6,
         "dls.step/dispatch": 60e-6, "none": 140e-6})
    # idle_gaps names the longest gap by its own rule: the shortest span
    # that covers half of it, and no span covers half of this one
    gaps = tr.idle_gaps(c["trace"], "0")
    assert gaps[0][1] == pytest.approx(200e-6)


def test_the_extract_keeps_the_loops_and_the_producers_spans_apart(tmp_path):
    """A real trace on the CPU: a producer thread that names itself for the
    operating system, as ``data/prefetch`` does, comes out as a host line of
    its own, so ``extract`` (which keys host lines by name) keeps both."""
    import jax

    from distributeddeeplearningspark_tpu.telemetry import spans

    def producer():
        spans.name_thread("dls-prefetch")
        for _ in range(3):
            with spans.span("dls.feed/assemble"):
                with spans.span("dls.feed/stack"):
                    sum(range(1000))

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t = threading.Thread(target=producer, name="dls-prefetch")
        t.start()
        for _ in range(2):
            with spans.span("dls.feed/wait"):
                sum(range(1000))
        t.join(30)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    ex = tr.extract(tr.find_xplane(str(tmp_path)))
    holds = {line: sorted({e[0] for e in evs if e[0].startswith("dls.")})
             for line, evs in ex["host"].items()}
    holds = {line: names for line, names in holds.items() if names}
    assert holds.pop("dls-prefetch") == ["dls.feed/assemble",
                                        "dls.feed/stack"]
    assert list(holds.values()) == [["dls.feed/wait"]]
