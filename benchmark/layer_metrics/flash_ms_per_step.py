"""Summed device time of the Mosaic flash-attention kernels' events on device
0, per step, by what today's trace says of them: every Mosaic kernel is a
custom call with the target ``tpu_custom_call``, and the only Mosaic kernels
in a BERT step are the three of ``ops/flash_attention.py``. The trace names
all three ``attention.<n>``, so it cannot tell forward from backward."""

from benchmark.harness import trace_reduce

PATTERN = r"tpu_custom_call"


def flash_events(tr):
    dev = sorted(tr["devices"], key=int)[0]
    return trace_reduce.select(tr, dev, PATTERN)


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["steps"] or not tr["devices"]:
        return None
    events = flash_events(tr)
    if not events:
        return None
    ctx["facts"]["flash_events_per_step"] = len(events) / tr["steps"]
    return 1e3 * trace_reduce.summed_s(events) / tr["steps"]
