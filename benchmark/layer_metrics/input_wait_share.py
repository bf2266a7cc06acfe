"""Share of lap wall time the loop stood waiting for the feed: sum of
``input_wait_s`` over sum of ``lap_s`` in the window's ``step_metrics``
records (``StarvationProbe``, host clock around the feed's ``next``)."""


def read(ctx):
    wall = sum(e["lap_s"] for e in ctx["laps"])
    if not wall or any("input_wait_s" not in e for e in ctx["laps"]):
        return None
    return 100.0 * sum(e["input_wait_s"] for e in ctx["laps"]) / wall
