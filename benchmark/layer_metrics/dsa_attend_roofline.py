"""The sparse-attention kernels' share of their roofline: the least time the
chip could take for the operations and bytes of ``dsa_attend_fwd``,
``dsa_attend_bwd_dq``, ``dsa_attend_bwd_dkv`` and ``dsa_kl_target`` as they
are written (``harness/flops_sparse.dsa_kernels``: the tiles on or under the
diagonal, masked-dense, at ``peaks.json``'s peaks), every execution counted
from the trace, over the time they took. Which peak bounds each is a printed
fact."""

from benchmark.harness import flops_sparse, stage_time

KERNELS = ("dsa_attend_fwd", "dsa_attend_bwd_dq", "dsa_attend_bwd_dkv",
           "dsa_kl_target")


def read(ctx):
    shapes = getattr(ctx["cell"]["config_mod"], "dsa_shapes", None)
    if shapes is None:
        return None
    costs = flops_sparse.dsa_kernels(**shapes(ctx["cfg"], ctx["traffic"]))
    return stage_time.kernel_roofline(
        ctx, {k: costs[k] for k in KERNELS}, "dsa_attend_roofline")
