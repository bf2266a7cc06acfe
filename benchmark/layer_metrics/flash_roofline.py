"""The flash kernels' share of their roofline: the least time the chip could
take for the operations and bytes the three kernels need as they are written
(``harness/flops.flash_kernels``, at the peaks of ``peaks.json``) over the
time they took (``flash_ms_per_step``). Which peak bounds is a printed fact."""

import os

from benchmark.harness import flops, runner


def read(ctx):
    flash_shapes = getattr(ctx["cell"]["config_mod"], "flash_shapes", None)
    shapes = flash_shapes(ctx["cfg"], ctx["traffic"]) if flash_shapes else None
    if shapes is None or not ctx["peaks"]:
        return None
    took_ms = runner.load_module(os.path.join(
        os.path.dirname(__file__), "flash_ms_per_step.py")).read(ctx)
    if not took_ms:
        return None
    calls = shapes.pop("calls")
    kernels = flops.flash_kernels(**shapes)
    ops = calls * sum(k["ops"] for k in kernels.values())
    nbytes = calls * sum(k["bytes"] for k in kernels.values())
    least_s, bound = flops.least_seconds(ops, nbytes, ctx["peaks"])
    ctx["facts"]["flash_roofline"] = {
        "bound": bound, "ops_per_step": ops, "bytes_per_step": nbytes,
        "least_ms": least_s * 1e3, "took_ms": took_ms}
    return 100.0 * least_s * 1e3 / took_ms
