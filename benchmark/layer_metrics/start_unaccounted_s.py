"""What is left of the program's start under no section: the own time of
``dls.start/fit`` (the first line of ``fit`` to the close of its first lap)
less the lap's compile, feed wait, dispatches, drain and callbacks:
``fit_unaccounted_s`` of the program's ``startup`` record. With ``compile_s``
and the other five ``start_*`` columns it tiles ``to_first_lap_s`` less
``caller_s`` (the caller's own code: in the harness ``import jax``, the seed
data, the model's build, its first-step sync). Both, and the whole record,
are printed facts."""

from benchmark.harness import startup


def read(ctx):
    startup.facts(ctx, "start_unaccounted_s", "caller_s", "to_first_lap_s")
    rec = startup.record(ctx)
    if rec is not None:
        ctx["facts"]["startup"] = rec
    return startup.seconds(ctx, "fit_unaccounted_s")
