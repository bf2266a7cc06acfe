"""Loop-thread time inside ``put(hb, mesh)`` (``device_put`` of a batch the
loop already has; span ``dls.feed/put``), per step: sum of ``input_put_s``
over the steps of the same laps. A program without the counter reads
nothing."""


def read(ctx):
    steps = sum(e["steps"] for e in ctx["laps"])
    if not steps or any("input_put_s" not in e for e in ctx["laps"]):
        return None
    return 1e3 * sum(e["input_put_s"] for e in ctx["laps"]) / steps
