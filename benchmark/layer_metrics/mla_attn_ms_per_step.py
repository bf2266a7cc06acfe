"""Device time a step of the latent-attention kernels: ``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv`` of ``ops/flash_attention.py`` at a query /
key head size that differs from the value's (192 / 128), by name, in a cell
whose configuration describes that regime (``mla_attn_shapes``: every
attention layer of such a model is latent, so there the names mean it);
elsewhere, and for a program without the kernels, nothing."""

from benchmark.harness import stage_time


def read(ctx):
    if getattr(ctx["cell"]["config_mod"], "mla_attn_shapes", None) is None:
        return None
    return stage_time.union_ms_per_step(
        ctx, lambda name, info: "flash_fwd" in name or "flash_bwd" in name,
        "mla_attn_ms_per_step")
