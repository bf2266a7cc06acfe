"""Device time of one step: the busy time of device 0 (union of its op
intervals in the trace) over the steps traced."""

from benchmark.harness import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["steps"] or not tr["devices"]:
        return None
    dev = sorted(tr["devices"], key=int)[0]
    busy = trace_reduce.busy_s(tr, dev)
    return 1e3 * busy / tr["steps"] if busy else None
