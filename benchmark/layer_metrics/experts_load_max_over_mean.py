"""Rows of the fullest held expert over the mean of the held experts behind
the sigmoid router whose bias the step moves: the program's own counter
``moe_load_max_over_mean`` (``models/moe.py``, averaged over the expert layers
by ``models/hybrid_decoder.py`` and carried into ``step_metrics`` by
``losses.hybrid_moe_lm``), mean over the window's laps; a lap's value is that
of its last step. 1 is perfectly even. Does the bias flatten what Zipf tokens
skew? Only for a configuration with ``router_width``; a program that lacks the
counter gives nothing."""

COUNTER = "moe_load_max_over_mean"


def read(ctx):
    if "router_width" not in ctx["cfg"]:
        return None
    values = [e["metrics"][COUNTER] for e in ctx["laps"]
              if COUNTER in (e.get("metrics") or {})]
    if not values:
        return None
    laps = [e["metrics"] for e in ctx["laps"]
            if COUNTER in (e.get("metrics") or {})]
    ctx["facts"]["experts_load_max_over_mean"] = {
        "laps": len(values), "first": values[0], "last": values[-1],
        "min": min(values), "max": max(values),
        "router_bias_abs_max_last": laps[-1].get("router_bias_abs_max"),
        "moe_rows_held_share_by_lap": [m.get("moe_rows_held_share")
                                       for m in laps]}
    return sum(values) / len(values)
