"""The largest magnitude of a state the LAST Mamba layer's scan handed from
one chunk to the next (float32, ``[heads, head_dim, state_size]`` a chunk):
the recurrent state's health. The program's own counter
``ssm_state_abs_max`` (``ops/ssd.ssd_scan``, carried into ``step_metrics``
by ``losses.hybrid_moe_lm``), the LARGEST over the window's laps; a lap's
value is that of its last step. A program that lacks the counter gives
nothing."""

COUNTER = "ssm_state_abs_max"


def read(ctx):
    values = [e["metrics"][COUNTER] for e in ctx["laps"]
              if COUNTER in (e.get("metrics") or {})]
    if not values:
        return None
    ctx["facts"][COUNTER] = {"laps": len(values), "first": values[0],
                             "last": values[-1]}
    return max(values)
