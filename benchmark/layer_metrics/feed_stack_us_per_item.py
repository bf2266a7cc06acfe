"""Host time the feed's thread spent in ``stack_examples`` (span
``dls.feed/stack``), per item: sum of ``input_stack_s`` over the items of the
same laps, as ``input_assembly_us_per_item`` counts items. It is a part of
that metric's time."""


def read(ctx):
    steps = sum(e["steps"] for e in ctx["laps"])
    if not steps or any("input_stack_s" not in e for e in ctx["laps"]):
        return None
    return (1e6 * sum(e["input_stack_s"] for e in ctx["laps"])
            / (steps * ctx["items_per_step"]))
