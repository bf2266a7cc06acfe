"""Share of the window's batches whose local rows were all written by the
threads of the ``map_parallel`` pool that made them (span
``dls.feed/filled_by_map``, counted by the feed's probe), so that the one
producer thread did nothing once an example: sum of ``input_filled_by_map``
over the batches assembled (``input_slot_reused`` + ``input_slot_new``). 0
where no stream of the feed offers ``fill``. A program without the counter
has no such key and reads nothing."""


def read(ctx):
    laps = [e for e in ctx["laps"]
            if "input_filled_by_map" in e and "input_slot_reused" in e
            and "input_slot_new" in e]
    batches = sum(e["input_slot_reused"] + e["input_slot_new"] for e in laps)
    if not batches:
        return None
    return 100.0 * sum(e["input_filled_by_map"] for e in laps) / batches
