"""Model FLOP/s utilisation: throughput (items/s/chip over the window) x analytic operations per item (forward + backward, no
recomputation: ``harness/flops.py`` through the configuration's file) over
the chip's bf16 peak in ``peaks.json``."""


def read(ctx):
    rate = ctx["window"].get("throughput")
    if rate is None or not ctx["peaks"]:
        return None
    per_item = ctx["cell"]["config_mod"].flops_per_item(ctx["cfg"],
                                                        ctx["traffic"])
    ctx["facts"]["flops_per_item"] = per_item
    return 100.0 * rate * per_item / ctx["peaks"]["bf16_flops_per_s"]
