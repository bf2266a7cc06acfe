"""The window arithmetic on fake lap clocks."""

import math

import pytest

from benchmark.harness.window import LapWindow, WindowClosed, window_result


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def drive(win, clock, *, step_s, steps, loss=lambda s: 5.0 - 0.001 * s,
          stall=None):
    """Call ``win`` as fit would: every step, the clock advancing
    ``step_s`` a step (plus ``stall[step]`` seconds before that step)."""
    for s in range(1, steps + 1):
        clock.t += step_s + (stall or {}).get(s, 0.0)
        try:
            win(s, {"loss": loss(s)})
        except WindowClosed:
            return s
    return None


def test_throughput_is_items_between_first_and_last_sync():
    clock = FakeClock()
    win = LapWindow(log_every=8, warmup_steps=16, seconds=10.0, clock=clock)
    closed_at = drive(win, clock, step_s=0.125, steps=1000)
    res = window_result(win, items_per_step=32 * 512, chips=1, loss_band=0.5)
    # first step at t=100.125; 5 s of warm-up end at 105.125: the first
    # boundary at or after it is step 48 (t=106). 10 s later is step 128.
    assert win.open_at.step == 48 and closed_at == 128
    assert res["steps"] == 80 and res["laps"] == 10
    assert res["wall_s"] == 10.0
    assert res["throughput"] == 80 * 32 * 512 / 10.0
    assert res["attempted"] == 80 and res["failed"] == 0
    assert res["reasons"] == []
    assert res["lap_step_ms"] == [125.0] * 10


def test_per_chip_division_and_warmup_steps_rule():
    clock = FakeClock()
    win = LapWindow(log_every=4, warmup_steps=200, seconds=2.0, clock=clock)
    drive(win, clock, step_s=0.125, steps=1000)
    # 5 s have passed by step 44, but warm-up is 200 steps
    assert win.open_at.step == 200
    res = window_result(win, items_per_step=128, chips=4, loss_band=0.5)
    assert res["throughput"] == 16 * 128 / 2.0 / 4


def test_warmup_is_never_under_five_seconds():
    clock = FakeClock()
    win = LapWindow(log_every=2, warmup_steps=2, seconds=1.0, clock=clock)
    drive(win, clock, step_s=0.015625, steps=2000)
    assert win.open_at.t - win.first_step_t >= 5.0


def test_window_closes_at_first_boundary_after_seconds():
    clock = FakeClock()
    win = LapWindow(log_every=8, warmup_steps=8, seconds=10.0, clock=clock)
    # a 3 s stall inside the window: fewer steps fit, the wall stays honest
    closed_at = drive(win, clock, step_s=0.125, steps=1000, stall={75: 3.0})
    res = window_result(win, items_per_step=1, chips=1, loss_band=0.5)
    assert win.open_at.step == 48
    assert closed_at == 104          # 7 laps: six of 1 s and one of 4 s
    assert res["wall_s"] == 10.0
    assert res["throughput"] == 56 / 10.0


def test_nonfinite_lap_counts_as_failed_steps():
    clock = FakeClock()
    win = LapWindow(log_every=8, warmup_steps=8, seconds=3.0, clock=clock)
    drive(win, clock, step_s=0.125, steps=1000,
          loss=lambda s: math.nan if s == 64 else 4.0)
    res = window_result(win, items_per_step=1, chips=1, loss_band=0.5)
    assert res["failed"] == 8 and res["attempted"] == 24
    assert any("step 64" in r for r in res["reasons"])


def test_fit_ending_early_loses_the_steps_after_the_last_boundary():
    clock = FakeClock()
    win = LapWindow(log_every=8, warmup_steps=8, seconds=60.0, clock=clock)
    assert drive(win, clock, step_s=0.125, steps=77) is None
    res = window_result(win, items_per_step=1, chips=1, loss_band=0.5)
    assert not res["closed"]
    assert res["steps"] == 24 and res["attempted"] == 29 and res["failed"] == 5
    assert any("before the window closed" in r for r in res["reasons"])


def test_never_opened():
    clock = FakeClock()
    win = LapWindow(log_every=8, warmup_steps=8, seconds=1.0, clock=clock)
    drive(win, clock, step_s=0.125, steps=30)
    res = window_result(win, items_per_step=1, chips=1, loss_band=0.5)
    assert not res["opened"] and res["reasons"]


def test_rising_loss_is_a_reason():
    clock = FakeClock()
    win = LapWindow(log_every=8, warmup_steps=8, seconds=5.0, clock=clock)
    drive(win, clock, step_s=0.125, steps=1000, loss=lambda s: 2.0 + 0.02 * s)
    # first lap 3.12; last three 3.44, 3.60, 3.76: the median is 0.48 above
    assert not window_result(win, items_per_step=1, chips=1,
                             loss_band=0.5)["reasons"]
    res = window_result(win, items_per_step=1, chips=1, loss_band=0.4)
    assert any("loss rose" in r for r in res["reasons"])


def test_traced_run_opens_the_window_after_the_trace_is_written():
    clock = FakeClock()
    calls = []

    def start():
        calls.append("start")
        clock.t += 0.5                # the profiler takes half a second to start

    def stop():
        calls.append("stop")
        clock.t += 2.0                # and two to write the trace

    sync = []
    win = LapWindow(log_every=8, warmup_steps=8, seconds=10.0,
                    trace_steps=16, start_trace=start, stop_trace=stop,
                    sync=lambda: sync.append(1), clock=clock)
    closed_at = drive(win, clock, step_s=0.125, steps=1000)
    assert sync == [1]                                  # once, on step 1
    assert calls == ["start", "stop"]
    assert win.trace_from.step == 48 and win.trace_to.step == 64
    # the traced wall is host clock from profiler-up to the stop boundary
    assert win.trace_to.t - win.trace_from.t == 2.0
    # the trace is written by t=110.5; the window opens at the first
    # boundary 5 s later, step 104 (t=115.5), not in the lap that paid for it
    assert win.open_at.step == 104 and closed_at == 184
    res = window_result(win, items_per_step=1, chips=1, loss_band=0.5)
    assert res["wall_s"] == 10.0 and res["throughput"] == 8.0
    assert res["lap_step_ms"] == [125.0] * 10
