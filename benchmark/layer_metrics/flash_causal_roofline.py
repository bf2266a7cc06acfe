"""The flash kernels' share of the roofline of the REQUIRED work in the
regime causal + segment ids: the least time the chip could take for the
products over the in-document causal pairs (the step's own counter
``attn_pairs_share``, mean over the window's laps, of ``S (S + 1) / 2`` a row
and head: ``harness/flops_hybrid.flash_causal_kernels``), every execution
counted from the trace, over the time the kernels took, whatever blocks they
walk. A kernel that computes and masks blocks no document spans reads low; it
can never read over 100%. A program without the counter gives nothing."""

from benchmark.harness import stage_time

COUNTER = "attn_pairs_share"


def read(ctx):
    shapes = getattr(ctx["cell"]["config_mod"], "flash_causal_shapes", None)
    shares = [e["metrics"][COUNTER] for e in ctx["laps"]
              if COUNTER in (e.get("metrics") or {})]
    if shapes is None or not shares:
        return None
    from benchmark.harness import flops_hybrid

    share = sum(shares) / len(shares)
    value = stage_time.kernel_roofline(
        ctx, flops_hybrid.flash_causal_kernels(
            **shapes(ctx["cfg"], ctx["traffic"]), pairs_share=share),
        "flash_causal_roofline")
    if value is not None:
        ctx["facts"]["flash_causal_roofline"]["pairs_share_of_the_laps"] = share
    return value
