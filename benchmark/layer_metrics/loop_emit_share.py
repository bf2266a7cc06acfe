"""Share of lap wall time the loop thread spent emitting at lap boundaries
(span ``dls.fit/emit``: the metric log line, the heartbeat file, the
``step_metrics`` and ``memory`` records, the comms probe): ``emit_s`` over
``anatomy_wall_s``. A named part of ``loop_host_share``'s residual."""


def read(ctx):
    wall = sum(e.get("anatomy_wall_s", 0.0) for e in ctx["laps"])
    if not wall or any("emit_s" not in e for e in ctx["laps"]):
        return None
    return 100.0 * sum(e["emit_s"] for e in ctx["laps"]) / wall
