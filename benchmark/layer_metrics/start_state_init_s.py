"""From the sample batch to a state on the devices: ``sample_s``
(``dls.start/sample``: the dataset's pipeline run for a few examples) +
``init_state_s`` (``dls.start/init_state``: ``eval_shape``, the jitted init's
trace, compile or cache load, and dispatch) of the program's ``startup``
record. Each of the two is a printed fact."""

from benchmark.harness import startup

PARTS = ("sample_s", "init_state_s")


def read(ctx):
    startup.facts(ctx, "start_state_init_s", *PARTS)
    return startup.seconds(ctx, *PARTS)
