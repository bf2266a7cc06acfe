"""Of the window's 128-position scan chunks, the share that hold a
document's first position other than the window's own: where the chunked
scan masks inside the chunk and cuts the state it was handed. The program's
own counter ``ssm_chunks_reset_share`` (``ops/ssd.chunks_reset_share`` from
the segment ids on the device, carried into ``step_metrics`` by
``losses.hybrid_moe_lm``), in percent, mean over the window's laps; a lap's
value is that of its last step. A program that lacks the counter gives
nothing."""

COUNTER = "ssm_chunks_reset_share"


def read(ctx):
    values = [e["metrics"][COUNTER] for e in ctx["laps"]
              if COUNTER in (e.get("metrics") or {})]
    if not values:
        return None
    ctx["facts"][COUNTER] = {"laps": len(values), "min": min(values),
                             "max": max(values)}
    return 100.0 * sum(values) / len(values)
