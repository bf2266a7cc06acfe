"""1 - busy union / traced wall, mean over the local devices.

The profiler slows the host, so where the host sets the pace (a feed-bound
cell) the traced laps are longer than the window's and this is the UPPER
figure. The same busy time against the untraced window's median lap is
printed beside it as the fact ``device_idle_share_untraced``."""

import statistics

from benchmark.harness import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"]:
        return None
    busy = [trace_reduce.busy_s(tr, d) for d in tr["devices"]]
    busy = [b for b in busy if b > 0]
    if not busy:
        return None
    busy_s = sum(busy) / len(busy)
    laps_ms = ctx["window"].get("lap_step_ms")
    if laps_ms and tr["steps"]:
        ctx["facts"]["device_idle_share_untraced"] = 100.0 * (
            1.0 - 1e3 * busy_s / tr["steps"] / statistics.median(laps_ms))
    return 100.0 * (1.0 - busy_s / tr["window_s"])
