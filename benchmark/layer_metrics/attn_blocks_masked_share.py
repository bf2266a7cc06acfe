"""Of the blocks of 512 x 512 the flash kernels walk, the share that needs
its mask, in percent: the step's own counter ``attn_blocks_masked_share``
(``models/hybrid_decoder.py``, from the predicate of
``ops/flash_attention._block_whole`` that the backward kernels branch on),
mean over the window's laps; a lap's value is that of its last step. A block
the diagonal or a document boundary crosses needs its mask; every other
walked block allows every pair, and dQ and dK/dV run the body without a mask
there: 2 / (n + 1) of a causal window of n blocks that is one document. A
program that lacks the counter (it masks every walked block) gives
nothing."""

COUNTER = "attn_blocks_masked_share"


def read(ctx):
    values = [e["metrics"][COUNTER] for e in ctx["laps"]
              if COUNTER in (e.get("metrics") or {})]
    if not values:
        return None
    ctx["facts"][COUNTER] = {"laps": len(values), "min": min(values),
                             "max": max(values)}
    return 100.0 * sum(values) / len(values)
