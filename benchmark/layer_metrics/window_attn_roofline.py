"""The band kernels' share of the roofline of the REQUIRED work of the
sliding-window layers: the least time the chip could take for the products
over the in-document pairs inside the window (the step's own counter
``window_pairs_share``, mean over the window's laps, of ``S x window`` a row
and query head: ``harness/flops_window.flash_window_kernels``), each array
moved once, at ``peaks.json``'s peaks, every execution counted from the trace,
over the time the kernels took, whatever blocks they walk. A kernel that walks
more than the band (the causal triangle, or whole blocks of which the band
holds a part) reads low; it can never read over 100%. A program without the
counter or the kernels gives nothing."""

from benchmark.harness import stage_time

COUNTER = "window_pairs_share"


def read(ctx):
    shapes = getattr(ctx["cell"]["config_mod"], "flash_window_shapes", None)
    shares = [e["metrics"][COUNTER] for e in ctx["laps"]
              if COUNTER in (e.get("metrics") or {})]
    if shapes is None or not shares:
        return None
    from benchmark.harness import flops_window

    share = sum(shares) / len(shares)
    value = stage_time.kernel_roofline(
        ctx, flops_window.flash_window_kernels(
            **shapes(ctx["cfg"], ctx["traffic"]), pairs_share=share),
        "window_attn_roofline")
    if value is not None:
        ctx["facts"]["window_attn_roofline"]["pairs_share_of_the_laps"] = share
    return value
