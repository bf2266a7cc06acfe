"""The index kernels' share of their roofline: the least time the chip
could take for the operations and bytes of ``dsa_index_fwd``,
``dsa_index_select`` and ``dsa_index_bwd`` as they are written (``harness/flops_sparse.dsa_kernels``,
at ``peaks.json``'s peaks), every execution counted from the trace, over the
time they took. Which peak bounds each is a printed fact. What XLA adds to the selection (the mask from
threshold and cut, the log-sum-exp) is in ``dsa_index_ms_per_step`` only."""

from benchmark.harness import flops_sparse, stage_time

KERNELS = ("dsa_index_fwd", "dsa_index_select", "dsa_index_bwd")


def read(ctx):
    shapes = getattr(ctx["cell"]["config_mod"], "dsa_shapes", None)
    if shapes is None:
        return None
    costs = flops_sparse.dsa_kernels(**shapes(ctx["cfg"], ctx["traffic"]))
    return stage_time.kernel_roofline(
        ctx, {k: costs[k] for k in KERNELS}, "dsa_index_roofline")
