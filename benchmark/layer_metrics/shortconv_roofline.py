"""The short-convolution kernels' share of their roofline: the least time
the chip could take for the bytes and operations of ``shortconv_fwd`` and
``shortconv_bwd`` (``harness/flops_hybrid.shortconv_kernels``: each array
moved once, at ``peaks.json``'s peaks; memory-bound), every execution counted
from the trace, over the time they took."""

from benchmark.harness import stage_time


def read(ctx):
    shapes = getattr(ctx["cell"]["config_mod"], "shortconv_shapes", None)
    if shapes is None:
        return None
    from benchmark.harness import flops_hybrid

    return stage_time.kernel_roofline(
        ctx, flops_hybrid.shortconv_kernels(
            **shapes(ctx["cfg"], ctx["traffic"])), "shortconv_roofline")
