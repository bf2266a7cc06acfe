"""Device time of a STAGE of the step, per step: the union of the intervals
of the events that belong to it on device 0's op line. A union and not a
sum, because the op line is not flat: a ``while`` or ``conditional`` event
spans the events of its body, so a stage that matches both a parent and its
children would otherwise count the children twice.

A stage is given as a predicate on ``(name, info)`` of an event, ``info``
being what ``trace_reduce.describe`` keeps of the op (``kind``, ``op``,
``result``: the result type without layouts, ``target``). A Pallas kernel is
found by its name; a stage left to XLA has no name of its own in the
extract, and is found by the result shapes that only it produces (each
reader says which)."""

from __future__ import annotations

import re

from benchmark.harness import trace_reduce


def device0_events(ctx, belongs) -> list:
    tr = ctx["trace"]
    if not tr or not tr["steps"] or not tr["devices"]:
        return []
    dev = sorted(tr["devices"], key=int)[0]
    return [e for e in trace_reduce.device_ops(tr, dev)
            if belongs(e[0], e[3])]


def union_ms_per_step(ctx, belongs, fact: str | None = None):
    events = device0_events(ctx, belongs)
    if not events:
        return None
    steps = ctx["trace"]["steps"]
    ms = trace_reduce.length(trace_reduce.union(
        trace_reduce.intervals(events))) / 1e6 / steps
    if fact:
        by_kind: dict[str, float] = {}
        for name, _, dur, info in events:
            key = f"{info.get('kind', name)} {info.get('result', '')}"[:90]
            by_kind[key] = by_kind.get(key, 0.0) + dur / 1e6 / steps
        top = sorted(by_kind.items(), key=lambda kv: -kv[1])[:8]
        ctx["facts"][fact] = {"events_per_step": len(events) / steps,
                              "summed_ms_by_kind": dict(top)}
    return ms


def has_shape(result: str, *dims: int) -> bool:
    """Does the result type hold an array of exactly these dimensions?"""
    return re.search(r"\[" + ",".join(str(d) for d in dims) + r"\]",
                     result or "") is not None


def kernel_roofline(ctx, kernels: dict[str, dict[str, float]], fact: str):
    """Least time for the operations and bytes of every execution of the
    named kernels (``kernels``: one execution each, by the name in the
    trace) over the time they took, in percent; executions are counted from
    the trace, so a kernel the remat replays counts as often as it ran."""
    from benchmark.harness import flops

    if not ctx["peaks"]:
        return None
    least_s = took_s = 0.0
    detail = {}
    for name, cost in kernels.items():
        events = device0_events(
            ctx, lambda n, info, name=name: name in n)
        if not events:
            continue
        t, bound = flops.least_seconds(cost["ops"], cost["bytes"],
                                       ctx["peaks"])
        took = trace_reduce.summed_s(events)
        least_s += t * len(events)
        took_s += took
        detail[name] = {"runs_per_step": len(events) / ctx["trace"]["steps"],
                        "ms_per_run": 1e3 * took / len(events),
                        "least_ms_per_run": 1e3 * t, "bound": bound}
    if not took_s:
        return None
    ctx["facts"][fact] = detail
    return 100.0 * least_s / took_s
