"""The measured window inside one ``Trainer.fit`` call.

``fit`` calls its callbacks after every step. On a step that is a multiple of
``log_every`` it has just done ``device_get`` on that step's metrics, so a
clock read there is a true sync point: a *lap boundary*. :class:`LapWindow`
reads the clock at each boundary and decides, from boundaries alone:

* warm-up ends at the first boundary at or past ``warmup_steps`` that is also
  at least ``MIN_WARMUP_S`` (5 s) after the first step has synced (the batches
  the feed piled up during the compile have to drain);
* the window opens there and closes at the first boundary ``seconds`` later;
* with a trace asked for, the profiler starts at the boundary that ends
  warm-up and stops at the first boundary ``trace_steps`` steps later; the
  lap after that pays for writing the trace, and the window opens at the first
  boundary ``MIN_WARMUP_S`` after it was written (the feed piles batches up
  meanwhile, as it does during the compile).

Closing raises :class:`WindowClosed` out of the callback, which ends ``fit``
through its own ``finally``. Everything the metrics need is arithmetic on the
recorded boundaries (:func:`window_result`), so it is tested on fake clocks.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable


class WindowClosed(Exception):
    """Raised by the callback to end ``fit`` once the window has closed."""


@dataclasses.dataclass
class Boundary:
    step: int
    t: float
    loss: float | None


#: the window never opens sooner than this after the first step, or after a
#: trace was written
MIN_WARMUP_S = 5.0


class LapWindow:
    def __init__(self, *, log_every: int, warmup_steps: int, seconds: float,
                 trace_steps: int = 0,
                 start_trace: Callable[[], None] | None = None,
                 stop_trace: Callable[[], None] | None = None,
                 sync: Callable[[], object] | None = None,
                 clock: Callable[[], float] = time.perf_counter) -> None:
        self.log_every = int(log_every)
        self.warmup_steps = int(warmup_steps)
        self.seconds = float(seconds)
        self.trace_steps = int(trace_steps)
        self._start_trace, self._stop_trace = start_trace, stop_trace
        self._sync = sync
        self._clock = clock
        self.first_step_t: float | None = None
        self.boundaries: list[Boundary] = []
        self.open_at: Boundary | None = None
        self.close_at: Boundary | None = None
        self.trace_from: Boundary | None = None
        self.trace_to: Boundary | None = None
        self._trace_written_t = 0.0
        self.last_step = 0

    def __call__(self, step: int, metrics: dict) -> None:
        self.last_step = step
        if self.first_step_t is None:
            # the one sync the harness adds, on the first step of warm-up:
            # it dates the end of the program's start
            if self._sync is not None:
                self._sync()
            self.first_step_t = self._clock()
        if step % self.log_every:
            return
        loss = metrics.get("loss")
        b = Boundary(step, self._clock(),
                     None if loss is None else float(loss))
        self.boundaries.append(b)
        if self.open_at is None:
            self._before_window(b)
        elif b.t - self.open_at.t >= self.seconds:
            self.close_at = b
            raise WindowClosed

    def _before_window(self, b: Boundary) -> None:
        warm = (b.step >= self.warmup_steps
                and b.t - self.first_step_t >= MIN_WARMUP_S)
        if not warm:
            return
        if not self.trace_steps or self._start_trace is None:
            self.open_at = b
        elif self.trace_from is None:
            self._start_trace()
            # the traced wall starts once the profiler is up
            self.trace_from = Boundary(b.step, self._clock(), b.loss)
        elif self.trace_to is None:
            if b.step - self.trace_from.step >= self.trace_steps:
                self.trace_to = b
                self._stop_trace()
                self._trace_written_t = self._clock()
        elif b.t - self._trace_written_t >= MIN_WARMUP_S:
            # the batches the feed piled up while the trace was written
            # have drained, as after the compile
            self.open_at = b


def window_result(win: LapWindow, *, items_per_step: int, chips: int,
                  loss_band: float) -> dict:
    """Everything the end-to-end line needs, from the boundaries alone.

    ``throughput`` is the items that finished between the window's first and
    last sync over that wall time, over the chip count. ``failed`` counts the
    steps of laps whose loss is not finite, plus the steps dispatched after
    the last boundary if ``fit`` ended without closing the window.
    """
    reasons: list[str] = []
    if win.open_at is None:
        return {"opened": False, "attempted": 0, "failed": win.last_step,
                "reasons": [f"the window never opened: {len(win.boundaries)} "
                            f"lap boundaries, last step {win.last_step}"]}
    laps = [(a, b) for a, b in zip(win.boundaries, win.boundaries[1:])
            if a.step >= win.open_at.step]
    end = win.close_at or (laps[-1][1] if laps else win.open_at)
    attempted = max(win.last_step, end.step) - win.open_at.step
    failed = 0
    for a, b in laps:
        if b.loss is None or not math.isfinite(b.loss):
            failed += b.step - a.step
            reasons.append(f"loss at step {b.step} is {b.loss}")
    if win.close_at is None:
        lost = win.last_step - end.step
        failed += lost
        reasons.append(f"fit ended before the window closed: {len(laps)} "
                       f"whole laps, {lost} steps lost after the last one")
    wall = end.t - win.open_at.t
    steps = end.step - win.open_at.step
    out = {
        "opened": True, "closed": win.close_at is not None,
        "attempted": attempted, "failed": failed, "reasons": reasons,
        "first_step": win.open_at.step, "last_step": end.step,
        "steps": steps, "wall_s": wall, "laps": len(laps),
        "lap_step_ms": [(b.t - a.t) / (b.step - a.step) * 1e3 for a, b in laps],
        "losses": [b.loss for _, b in laps],
    }
    if wall > 0 and steps > 0:
        out["throughput"] = steps * items_per_step / wall / chips
    finite = [x for x in out["losses"] if x is not None and math.isfinite(x)]
    if len(finite) >= 2:
        tail = sorted(finite[-3:])[len(finite[-3:]) // 2]
        out["loss_first"], out["loss_tail_median"] = finite[0], tail
        if tail > finite[0] + loss_band:
            reasons.append(f"loss rose: first lap {finite[0]:.4f}, median of "
                           f"the last three {tail:.4f}, band {loss_band}")
    return out
