"""Device time a step of the band kernels of the sliding-window layers:
``flash_window_fwd``, ``flash_window_bwd_dq`` and ``flash_window_bwd_dkv`` of
``ops/flash_attention.py`` (the regime with a ``window``), by name, the union
of their intervals (``harness/stage_time``); a program without them, or a
cell whose configuration describes no band (``flash_window_shapes``), gives
nothing."""

from benchmark.harness import stage_time


def read(ctx):
    if getattr(ctx["cell"]["config_mod"], "flash_window_shapes", None) is None:
        return None
    return stage_time.union_ms_per_step(
        ctx, lambda name, info: "flash_window_" in name,
        "window_attn_ms_per_step")
