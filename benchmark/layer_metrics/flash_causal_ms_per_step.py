"""Device time a step of the flash kernels in the regime causal + grouped
keys + segment ids: ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv`` of
``ops/flash_attention.py``, by name, in a cell whose configuration describes
that regime (``flash_causal_shapes``); elsewhere nothing."""

from benchmark.harness import stage_time


def read(ctx):
    if getattr(ctx["cell"]["config_mod"], "flash_causal_shapes", None) is None:
        return None
    return stage_time.union_ms_per_step(
        ctx, lambda name, info: "flash_fwd" in name or "flash_bwd" in name,
        "flash_causal_ms_per_step")
