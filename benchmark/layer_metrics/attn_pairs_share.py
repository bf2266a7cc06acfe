"""Causal (query, key) pairs that lie inside one document over the window's
``S (S + 1) / 2``, in percent: the step's own counter ``attn_pairs_share``
(``models/hybrid_decoder.py``, from the segment ids on the device), mean over
the window's laps; a lap's value is that of its last step. What attention
REQUIRES of a packed window; a program that lacks the counter gives
nothing."""

COUNTER = "attn_pairs_share"


def read(ctx):
    values = [e["metrics"][COUNTER] for e in ctx["laps"]
              if COUNTER in (e.get("metrics") or {})]
    if not values:
        return None
    ctx["facts"][COUNTER] = {"laps": len(values), "min": min(values),
                             "max": max(values)}
    return 100.0 * sum(values) / len(values)
