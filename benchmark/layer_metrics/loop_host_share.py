"""Share of lap wall time that is neither dispatch, drain, compile nor input
wait: ``host_s`` over ``anatomy_wall_s`` (``StepAnatomy``, host-side times)."""


def read(ctx):
    wall = sum(e.get("anatomy_wall_s", 0.0) for e in ctx["laps"])
    if not wall:
        return None
    return 100.0 * sum(e.get("host_s", 0.0) for e in ctx["laps"]) / wall
