"""Share of lap wall time that no named section of the loop thread covers:
``unaccounted_s`` (lap wall less wait, put, dispatch, sync, compile, emit,
callbacks, checkpoint and eval) over ``anatomy_wall_s``. What is left is the
loop's own Python between the sections."""


def read(ctx):
    wall = sum(e.get("anatomy_wall_s", 0.0) for e in ctx["laps"])
    if not wall or any("unaccounted_s" not in e for e in ctx["laps"]):
        return None
    return 100.0 * sum(e["unaccounted_s"] for e in ctx["laps"]) / wall
