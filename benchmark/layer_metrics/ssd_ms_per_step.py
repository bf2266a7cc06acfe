"""Device time a step of the state-space scan alone (``ops/ssd.ssd_scan`` of
the program: the chunked form with its resets), forward, replay under remat
and backward, in every Mamba layer: the union of the scan's loops, found by
the shapes only they carry (``harness/ssd_stage.py``; XLA runs the scan, so
it has no name) and of whatever kernels named ``ssd_...`` run it. Not in it: the layer's projections, its convolution
(``mamba_conv_ms_per_step``), the gate and the grouped norm. For a
configuration that describes the scan (``ssd_shapes``); elsewhere, and for a
program without the scan, nothing."""

from benchmark.harness import ssd_stage


def read(ctx):
    loops = ssd_stage.scan_loops(ctx)
    if loops is None:
        return None
    forward, backward, masks = loops
    kernels = ssd_stage.scan_kernels(ctx)
    if not (forward or backward or kernels):
        return None
    steps = ctx["trace"]["steps"]
    ms = lambda events: ssd_stage.union_ms(events) / steps
    ctx["facts"]["ssd_ms_per_step"] = {
        "forward_loops_per_step": len(forward) / steps,
        "backward_loops_per_step": len(backward) / steps,
        "masks_loops_per_step": len(masks) / steps,
        "forward_ms": ms(forward), "backward_ms": ms(backward),
        "masks_ms": ms(masks), "kernels_per_step": len(kernels) / steps,
        "kernels_ms": ms(kernels)}
    return ms(forward + backward + masks + kernels)
