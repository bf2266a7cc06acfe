"""The trace reduction against values worked out by hand, on hand-made
events and on a small recorded extract of a real v5e trace."""

import os

import pytest

from benchmark.harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start_us, dur_us, **stats):
    return [name, start_us * 1e3, dur_us * 1e3, stats]


def extract(ops, host=None, modules=None, device="0"):
    return {"devices": {device: {tr.OP_LINE: ops,
                                 tr.MODULE_LINE: modules or []}},
            "host": host or {}, "planes": []}


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == \
        [(0, 4), (5, 7), (10, 11)]
    assert tr.length(tr.union([(0, 10), (2, 3), (4, 12)])) == 12


def test_subtract():
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], [(0, 4)]) == []
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_busy_is_the_union_not_the_sum():
    # fusion.1 0-100 us, an async all-reduce 50-250 us overlapping it,
    # fusion.2 300-400 us: the sum is 400 us, the union 350 us
    ex = extract([ev("fusion.1", 0, 100), ev("all-reduce.3", 50, 200),
                  ev("fusion.2", 300, 100)])
    assert tr.busy_s(ex, "0") == pytest.approx(350e-6)
    assert tr.summed_s(tr.device_ops(ex, "0")) == pytest.approx(400e-6)


def test_exposed_collective_time():
    # of the all-reduce's 200 us, 50-100 is hidden behind fusion.1 and
    # 200-250 behind fusion.9: 100 us are exposed
    ex = extract([ev("fusion.1", 0, 100), ev("all-reduce.3", 50, 200),
                  ev("fusion.9", 200, 100)])
    assert tr.exposed_s(ex, "0", r"all-reduce") == pytest.approx(100e-6)
    # two all-reduces overlapping each other expose their union once
    ex = extract([ev("all-reduce.1", 0, 100), ev("all-reduce.2", 50, 100)])
    assert tr.exposed_s(ex, "0", r"all-reduce") == pytest.approx(150e-6)


ATTENTION = ('%attention.36 = (bf16[384,512,64]{2,1,0:T(8,128)(2,1)S(1)}, '
             'f32[384,512,8]{2,1,0:T(8,128)}) custom-call(bf16[384,512,64]'
             '{2,1,0:T(8,128)(2,1)} %bitcast.2788), '
             'custom_call_target="tpu_custom_call", frontend_attributes={}')


def test_describe_cuts_the_hlo_text_the_trace_uses_as_a_name():
    name, info = tr.describe(ATTENTION)
    assert name == "attention.36"
    assert info == {"kind": "attention", "op": "custom-call",
                    "target": "tpu_custom_call",
                    "result": "(bf16[384,512,64], f32[384,512,8])"}
    name, info = tr.describe("%all-reduce.5 = f32[768]{0:T(1024)} "
                             "all-reduce(f32[768]{0} %x), replica_groups={}")
    assert (name, info["kind"], info["op"]) == ("all-reduce.5", "all-reduce",
                                                "all-reduce")
    assert tr.describe("jit_train_step(123)") == ("jit_train_step(123)", {})


def test_events_by_kind_and_select():
    ex = extract([ev("fusion.1", 0, 10, kind="fusion", result="bf16[8]"),
                  ev("fusion.2", 20, 30, kind="fusion", result="bf16[8]"),
                  ev("fusion.3", 50, 5, kind="fusion", result="f32[4]"),
                  ev("attention.7", 60, 5, kind="attention",
                     target="tpu_custom_call")])
    assert tr.by_kind(ex, "0")["fusion bf16[8]"] == [pytest.approx(40e-6), 2]
    assert [e[0] for e in tr.select(ex, "0", r"tpu_custom_call")] == \
        ["attention.7"]
    assert tr.top_ops(ex, "0")[0] == ["fusion bf16[8]", pytest.approx(40e-6)]


def test_exposed_time_counts_an_asynchronous_span_too():
    # the collective as an async span 0-300 us; ops cover 0-100 and 250-300
    ex = extract([ev("fusion.1", 0, 100), ev("fusion.2", 250, 50)])
    ex["devices"]["0"][tr.ASYNC_LINE] = [ev("all-reduce-start.1", 0, 300)]
    assert tr.exposed_s(ex, "0", r"all-reduce") == pytest.approx(150e-6)


def test_recorded_v5e_trace():
    """Two steps of bert_base_mlm.fit on a TPU v5 lite (PR 22). The numbers
    were worked out apart from the reduction: the op line holds 9,541 events
    that never overlap (checked pairwise), so its busy time is their plain
    sum, 258,977,533 ns, inside a window of 259,224,286 ns; 72 of them are
    Mosaic calls (12 layers x 3 kernels x 2 steps) summing to 53,721,031 ns."""
    ex = tr.load_extract(os.path.join(DATA, "bert_v5e_two_steps.json.gz"))
    ops = tr.device_ops(ex, "0")
    assert len(ops) == 9541
    ivs = sorted(tr.intervals(ops))
    assert all(b[0] >= a[1] for a, b in zip(ivs, ivs[1:]))
    assert tr.busy_s(ex, "0") == pytest.approx(258_977_533e-9, rel=1e-12)
    assert ex["steps"] == tr.steps_traced(ex, "0", r"train_step") == 2
    mosaic = tr.select(ex, "0", r"tpu_custom_call")
    assert len(mosaic) == 72 and {e[3]["kind"] for e in mosaic} == {"attention"}
    assert tr.summed_s(mosaic) == pytest.approx(53_721_031e-9, rel=1e-12)
    summary = tr.summarize(ex, window_s=ex["window_s"])
    idle = 1 - summary["busy_s"] / summary["window_s"]
    assert idle == pytest.approx(246_753 / 259_224_286, rel=1e-6)
    assert len(summary["device_ops"]) == 10
    assert summary["device_ops"][0][0].startswith("convert_reduce_fusion ")
    # nothing in this step is a collective, and nothing is exposed
    assert tr.exposed_s(ex, "0", r"all-reduce") == 0.0
    # no gap is long (69 us at most) and today no host span explains one
    assert summary["idle_gaps"][0][1] == pytest.approx(69_297e-9)


def test_a_device_without_an_op_line_reduces_to_nothing():
    ex = {"devices": {"0": {"Steps": [ev("step", 0, 1000)]}}, "host": {
        "python": [ev("busy host thread", 0, 5000)]}, "planes": []}
    assert tr.busy_s(ex, "0") == 0.0
    summary = tr.summarize(ex, window_s=1.0)
    assert "busy_s" not in summary and "device_ops" not in summary


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    ops = [ev("fusion.1", 0, 100), ev("fusion.2", 400, 100),
           ev("fusion.3", 520, 100)]
    host = {"main": [ev("fit", 0, 1000), ev("device_put", 120, 250)]}
    gaps = tr.idle_gaps(extract(ops, host), "0")
    assert gaps[0] == ["device_put", pytest.approx(300e-6)]
    # "fit" overlaps the 20 us gap as fully as anything: the only cover
    assert gaps[1] == ["fit", pytest.approx(20e-6)]
    assert tr.idle_gaps(extract(ops), "0")[0][0] == "unattributed"


def test_summarize_means_busy_over_the_devices_that_ran():
    ex = {"devices": {
        "0": {tr.OP_LINE: [ev("fusion.1", 0, 600)]},
        "1": {tr.OP_LINE: [ev("fusion.1", 0, 400)]}}, "host": {}, "planes": []}
    s = tr.summarize(ex, window_s=1e-3)
    assert s["busy_s"] == pytest.approx(500e-6)
    assert s["window_s"] == 1e-3


def test_steps_traced_counts_the_program_by_name():
    ex = extract([], modules=[ev("jit_train_step(123)", 0, 100),
                              ev("jit_train_step(123)", 200, 100),
                              ev("jit_convert(9)", 350, 1)])
    assert tr.steps_traced(ex, "0", r"train_step") == 2


def test_idle_share_reader_prints_the_untraced_figure_beside_the_traced():
    from benchmark.harness import runner

    reader = runner.load_module(os.path.join(
        os.path.dirname(DATA), os.pardir, "layer_metrics",
        "device_idle_share.py"))
    # two steps of 100 ms busy each; the profiler stretched them to 1.2 s a
    # step, the untraced window's laps ran at 600 ms a step
    ex = extract([ev("fusion.1", 0, 100_000), ev("fusion.2", 1_200_000, 100_000)])
    ex.update(window_s=2.4, steps=2)
    ctx = {"trace": ex, "window": {"lap_step_ms": [590.0, 600.0, 640.0]},
           "facts": {}}
    assert reader.read(ctx) == pytest.approx(100 * (1 - 0.2 / 2.4))
    assert ctx["facts"]["device_idle_share_untraced"] == \
        pytest.approx(100 * (1 - 100 / 600))
    assert reader.read({"trace": None, "window": {}, "facts": {}}) is None
