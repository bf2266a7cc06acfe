"""The latent-attention kernels' share of the roofline of the REQUIRED work:
the least time the chip could take for the products over the causal triangle
(``S (S + 1) / 2`` pairs a row and head, QK^T-shaped products at the query /
key head size, PV-shaped ones at the value's: 192 and 128, nothing padded)
and each array moved once (``harness/flops_mla.mla_attn_kernels``), every
execution counted from the trace, over the time the kernels took, whatever
the kernels' tiles hold. It can never read over 100%. Compute-bound at
16,384."""

from benchmark.harness import stage_time


def read(ctx):
    shapes = getattr(ctx["cell"]["config_mod"], "mla_attn_shapes", None)
    if shapes is None:
        return None
    from benchmark.harness import flops_mla

    return stage_time.kernel_roofline(
        ctx, flops_mla.mla_attn_kernels(**shapes(ctx["cfg"], ctx["traffic"])),
        "mla_attn_roofline")
