"""Blocks of 512 x 512 the flash kernels walk under the window's segment ids
over the ``n (n + 1) / 2`` on or under the diagonal, in percent: the step's
own counter ``attn_blocks_walked_share`` (``models/hybrid_decoder.py``, from
the predicate of ``ops/flash_attention.segment_block_walk`` that also hands
the kernels their bounds), mean over the window's laps; a lap's value is that
of its last step. What the kernels DO of a packed window, where
``attn_pairs_share`` is what attention requires of it; a program that lacks
the counter (it walks every block) gives nothing."""

COUNTER = "attn_blocks_walked_share"


def read(ctx):
    values = [e["metrics"][COUNTER] for e in ctx["laps"]
              if COUNTER in (e.get("metrics") or {})]
    if not values:
        return None
    ctx["facts"][COUNTER] = {"laps": len(values), "min": min(values),
                             "max": max(values)}
    return 100.0 * sum(values) / len(values)
