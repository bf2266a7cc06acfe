"""Per step, the part of the all-reduce events' time on device 0 during
which no other op runs there (collective time not hidden behind compute)."""

from benchmark.harness import trace_reduce

PATTERN = r"all-reduce"


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["steps"] or not tr["devices"]:
        return None
    dev = sorted(tr["devices"], key=int)[0]
    events = trace_reduce.select(tr, dev, PATTERN)
    if not events:
        return None
    ctx["facts"]["allreduce"] = {
        "events_per_step": len(events) / tr["steps"],
        "summed_ms_per_step": 1e3 * trace_reduce.summed_s(events) / tr["steps"]}
    return 1e3 * trace_reduce.exposed_s(tr, dev, PATTERN) / tr["steps"]
