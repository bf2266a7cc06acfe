"""Device time a step of the attention over the selection and of the pass
that makes the indexer's target: the kernels ``dsa_attend_fwd``,
``dsa_attend_bwd_dq``, ``dsa_attend_bwd_dkv`` and ``dsa_kl_target``, by
name (the forward and the target pass run again in the remat replay, and
count as often as they ran)."""

from benchmark.harness import stage_time


def read(ctx):
    return stage_time.union_ms_per_step(
        ctx, lambda name, info: "dsa_attend" in name or "dsa_kl" in name,
        "dsa_attend_ms_per_step")
