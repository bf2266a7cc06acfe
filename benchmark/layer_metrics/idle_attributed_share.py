"""Of device 0's idle time between its first and last op in the traced laps,
the part that lies under one of the loop thread's own spans (``dls.feed/wait``,
``dls.feed/put``, ``dls.step/...``, ``dls.fit/...``). Only the loop thread
writes these names, so they are taken from whichever host line holds them.
The seconds under each span name go into the fact ``idle_by_span``, the rest
under ``none``. A trace without such spans (a program that writes none)
reads nothing."""

import re

from benchmark.harness import trace_reduce

LOOP_SPAN = re.compile(r"^dls\.(feed/(wait|put)$|step/|fit/)")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["devices"]:
        return None
    dev = sorted(tr["devices"], key=int)[0]
    busy = trace_reduce.union(trace_reduce.intervals(
        trace_reduce.device_ops(tr, dev)))
    if not busy:
        return None
    idle = trace_reduce.subtract([(busy[0][0], busy[-1][1])], busy)
    by_name: dict[str, list] = {}
    for events in tr["host"].values():
        for e in events:
            if LOOP_SPAN.match(e[0]):
                by_name.setdefault(e[0], []).append(e)
    if not idle or not by_name:
        return None

    def under(events: list) -> float:
        """Nanoseconds of ``idle`` that ``events`` cover."""
        ivs = trace_reduce.intervals(events)
        return trace_reduce.length(idle) - trace_reduce.length(
            trace_reduce.subtract(idle, ivs))

    total = trace_reduce.length(idle)
    covered = under([e for evs in by_name.values() for e in evs])
    facts = {name: under(evs) / 1e9 for name, evs in sorted(by_name.items())}
    facts["none"] = (total - covered) / 1e9
    ctx["facts"]["idle_by_span"] = facts
    return 100.0 * covered / total
