"""Device time a step of the state-space layers' UNGATED short convolution
with its bias and SiLU (``ops/short_conv.silu_short_conv`` of the program),
forward, replay and backward. XLA runs it (no kernel, so no name): it is
found by the result shapes that only it produces, anything ``[rows, window,
W]`` or ``[tokens, W]`` with ``W`` the convolution's channels
(``mamba_conv_width`` of the configuration: 4096 + 2 x 8 x 128 = 6,144; the
slice of the input projection that feeds it, its shifted sums, its SiLU and
their cotangents), and the taps' gradient sums ``[W, 1]``. NOT counted: the
input projection itself (``[tokens, 10304]``), and the optimizer's fusions,
whose results have the taps' own shape ``[W, 4]``."""

import re

from benchmark.harness import stage_time


def read(ctx):
    width = getattr(ctx["cell"]["config_mod"], "mamba_conv_width", None)
    if width is None:
        return None
    traffic = ctx["traffic"]
    rows, window = traffic["per_chip_batch"], traffic["seq_len"]
    w = width(ctx["cfg"], traffic)
    found = re.compile(rf"\[{rows},{window},{w}\]|\[{rows * window},{w}\]"
                       rf"|\[{w},1\]")

    def belongs(name, info):
        if info.get("op") in ("while", "conditional"):
            return False
        return bool(found.search(info.get("result", "")))

    return stage_time.union_ms_per_step(ctx, belongs,
                                        "mamba_conv_ms_per_step")
