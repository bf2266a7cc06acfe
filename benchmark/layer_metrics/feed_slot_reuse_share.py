"""Share of the window's batches that ``host_batches`` filled into a slot it
had kept (spans ``dls.feed/slot_reused`` and ``dls.feed/slot_new``, counted by
the feed's probe): sum of ``input_slot_reused`` over the sum of both. The rest
got new memory because every kept slot was still referred to. A program
without the counters has no such keys and reads nothing."""


def read(ctx):
    laps = [e for e in ctx["laps"]
            if "input_slot_reused" in e and "input_slot_new" in e]
    reused = sum(e["input_slot_reused"] for e in laps)
    batches = reused + sum(e["input_slot_new"] for e in laps)
    if not batches:
        return None
    return 100.0 * reused / batches
