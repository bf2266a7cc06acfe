"""Host time the feed's thread spent assembling batches, per item: sum of
``input_assembly_s`` over the items of the same laps."""


def read(ctx):
    steps = sum(e["steps"] for e in ctx["laps"])
    if not steps or any("input_assembly_s" not in e for e in ctx["laps"]):
        return None
    return (1e6 * sum(e["input_assembly_s"] for e in ctx["laps"])
            / (steps * ctx["items_per_step"]))
