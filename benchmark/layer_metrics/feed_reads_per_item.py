"""Files the worker processes of ``data/workers.py`` read for each example
they mapped: sum of ``input_worker_reads`` over the sum of
``input_worker_items`` (counters ``dls.feed/worker_read`` and
``dls.feed/worker_example`` of the feed's probe; each example brings along
the files its worker read since the one before, its walk's among them). 1
where a worker reads only its own examples' files, the number of workers
where each walks the epoch's bytes. Nothing where no worker process mapped
an example, or where the program has no such counters."""


def read(ctx):
    laps = [e for e in ctx["laps"]
            if "input_worker_reads" in e and "input_worker_items" in e]
    items = sum(e["input_worker_items"] for e in laps)
    if not items:
        return None
    return sum(e["input_worker_reads"] for e in laps) / items
