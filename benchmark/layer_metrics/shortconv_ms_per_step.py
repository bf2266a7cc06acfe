"""Device time a step of the gated short convolution between its two
projections: the kernels ``shortconv_fwd`` and ``shortconv_bwd`` of
``ops/short_conv.py``, by name (the forward runs again in the remat replay
and counts as often as it ran). A program without the kernels gives
nothing."""

from benchmark.harness import stage_time


def read(ctx):
    return stage_time.union_ms_per_step(
        ctx, lambda name, info: "shortconv_" in name, "shortconv_ms_per_step")
