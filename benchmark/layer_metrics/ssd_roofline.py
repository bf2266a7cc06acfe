"""The state-space scan's share of the roofline of the REQUIRED work: the
least time the chip could take for the four products of the chunked form and
each of ``x``, ``dt``, ``B``, ``C``, ``y`` and their cotangents moved once,
the states once a chunk (``harness/flops_ssm.ssd_scan_work``, at
``peaks.json``'s peaks), every execution counted from the trace (a forward
pass that remat replays counts as often as it ran), over the time the scan's
loops took (``ssd_ms_per_step``'s union). The same required work whether XLA
or a later kernel runs the scan, so it can never read over 100%. A reader
that missed part of the stage would read HIGH: where the trace does not hold
the scan of every Mamba layer in every pass (forward, replay and backward:
two forward loops and one backward loop a layer and step) it gives
nothing; nor where kernels named ``ssd_...`` run part of the stage, whose
executions it cannot count yet (``PERF.md`` section 7)."""

from benchmark.harness import ssd_stage


def read(ctx):
    loops = ssd_stage.scan_loops(ctx)
    if loops is None or not ctx["peaks"] or not (loops[0] or loops[1]):
        return None
    from benchmark.harness import flops, flops_ssm

    forward, backward, masks = loops
    shapes = ctx["cell"]["config_mod"].ssd_shapes(ctx["cfg"], ctx["traffic"])
    layers = shapes.pop("layers")
    steps = ctx["trace"]["steps"]
    kernels = ssd_stage.scan_kernels(ctx)
    if (len(forward) != 2 * layers * steps
            or len(backward) != layers * steps or kernels):
        ctx["facts"]["ssd_roofline"] = {
            "refused": "the trace does not hold two forward loops and one "
                       "backward loop a layer and step, and nothing else",
            "forward_loops": len(forward), "backward_loops": len(backward),
            "kernels": len(kernels), "layers": layers, "steps": steps}
        return None
    work = flops_ssm.ssd_scan_work(**shapes)
    least = {k: flops.least_seconds(v["ops"], v["bytes"], ctx["peaks"])
             for k, v in work.items()}
    least_s = (len(forward) * least["forward"][0]
               + len(backward) * least["backward"][0])
    took_s = ssd_stage.union_ms(forward + backward + masks) / 1e3
    if not took_s:
        return None
    ctx["facts"]["ssd_roofline"] = {
        "least_ms_per_forward": 1e3 * least["forward"][0],
        "least_ms_per_backward": 1e3 * least["backward"][0],
        "bound": {k: v[1] for k, v in least.items()},
        "took_ms_per_step": 1e3 * took_s / steps}
    return 100.0 * least_s / took_s
