"""Blocks the band kernels of the sliding-window layers walk over the ``n (n +
1) / 2`` on or under the diagonal, in percent: the step's own counter
``window_blocks_walked_share`` (``models/hybrid_decoder.py``, from the
predicate of ``ops/flash_attention.segment_block_walk`` with the window, the
one that also hands the kernels their bounds), mean over the window's laps; a
lap's value is that of its last step. ``2 n - 1`` blocks, about ``4 / (n +
1)``, for a window of one block (11.9% at 32 blocks of 512), less where a
document starts inside the band; a walk of the causal triangle reads 100. A program that lacks the counter
gives nothing."""

COUNTER = "window_blocks_walked_share"


def read(ctx):
    values = [e["metrics"][COUNTER] for e in ctx["laps"]
              if COUNTER in (e.get("metrics") or {})]
    if not values:
        return None
    ctx["facts"][COUNTER] = {"laps": len(values), "min": min(values),
                             "max": max(values)}
    return 100.0 * sum(values) / len(values)
