"""Share of the window's examples that the worker processes of
``data/workers.py`` mapped: sum of ``input_worker_items`` (span
``dls.feed/worker_example``, one for each example the feed's thread took
from a worker, counted by the feed's probe) over the items of the same laps.
0 where the map runs on threads. A program without the counter has no such
key and reads nothing."""


def read(ctx):
    laps = [e for e in ctx["laps"] if "input_worker_items" in e]
    steps = sum(e["steps"] for e in laps)
    if not steps:
        return None
    return (100.0 * sum(e["input_worker_items"] for e in laps)
            / (steps * ctx["items_per_step"]))
