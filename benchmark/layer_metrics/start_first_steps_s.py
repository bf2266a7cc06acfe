"""The first lap's dispatches and the drain at its boundary:
``first_dispatch_s`` + ``first_drain_s`` of the program's ``startup`` record
(that lap's ``device_dispatch_s`` and ``device_drain_s``). How many steps the
lap had is a printed fact."""

from benchmark.harness import startup


def read(ctx):
    startup.facts(ctx, "start_first_steps_s", "steps")
    return startup.seconds(ctx, "first_dispatch_s", "first_drain_s")
