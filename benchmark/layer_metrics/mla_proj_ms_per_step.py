"""Device time a step of XLA's part of latent attention, forward and
backward: the down- and up-projections, the two latents' norms, the rotary
embedding, the concatenation of the position-free and rotary parts, the
broadcast of the shared rotary key to every head and the transposes to and
from the kernels' head-major layout. None of it has a name of its own in the
trace; it is found by the result shapes that only it produces
(``mla_proj_shapes`` of the configuration, ``tokens`` = the chip's rows x the
window, as ``[tokens, w]`` or ``[rows, window, w]``): the query latent
``[tokens, 1536]``, the joint down-projection ``[tokens, 576]``, the key-value
latent ``[tokens, 512]``, and anything a position and head that ends
``window, 32, 192]`` (q, k and their cotangents) or ``window, 32, 256]``
(``[k_nope | v]``), or holds those two head-major (``[32, window, 192]``). NOT counted, because other stages produce the same
shapes: the output projection and the cotangent into the block (``[tokens,
2048]``), v, o and do at ``32, 128]``, and the weights' gradient products,
whose results have the weights' shapes as the optimizer's fusions do."""

import re

from benchmark.harness import stage_time


def read(ctx):
    shapes = getattr(ctx["cell"]["config_mod"], "mla_proj_shapes", None)
    if shapes is None:
        return None
    traffic = ctx["traffic"]
    rows, window = traffic["per_chip_batch"], traffic["seq_len"]
    patterns = []
    for dims in shapes(ctx["cfg"], traffic):
        if dims[0] == rows * window:       # per position
            patterns += [rf"\[{dims[0]},{dims[1]}\]",
                         rf"\[{rows},{window},{dims[1]}\]"]
        else:   # per head and position (the weights end alike: not those)
            patterns += [rf"[\[,]({window}|{rows * window}),{dims[0]},"
                         rf"{dims[1]}\]",
                         rf"[\[,]{dims[0]},{window},{dims[1]}\]"]
    found = re.compile("|".join(patterns))

    def belongs(name, info):
        # (the kernels' own results have q's shape: they are the other stage)
        if info.get("op") in ("while", "conditional") or "flash_" in name:
            return False
        return bool(found.search(info.get("result", "")))

    return stage_time.union_ms_per_step(ctx, belongs, "mla_proj_ms_per_step")
