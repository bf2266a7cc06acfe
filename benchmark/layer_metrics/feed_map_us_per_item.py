"""Thread-seconds the ``map_parallel`` pool spent inside the mapped function
(span ``dls.feed/map``, one per example), per item: sum of ``input_map_s``
over the items of the same laps. Summed over the pool's threads, so it may
exceed the wall time an item takes. A feed without a parallel map has no
such key and reads nothing."""


def read(ctx):
    laps = [e for e in ctx["laps"] if "input_map_s" in e]
    steps = sum(e["steps"] for e in laps)
    if not steps:
        return None
    return (1e6 * sum(e["input_map_s"] for e in laps)
            / (steps * ctx["items_per_step"]))
