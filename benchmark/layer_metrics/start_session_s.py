"""``Session.builder...getOrCreate()`` making a session (span
``dls.start/session``) with, inside it, the session's first look at the
devices, where the TPU runtime comes up (``dls.start/backend``): ``session_s``
+ ``backend_s`` of the program's ``startup`` record. ``backend_s`` alone is a
printed fact."""

from benchmark.harness import startup


def read(ctx):
    startup.facts(ctx, "start_session_s", "backend_s")
    return startup.seconds(ctx, "session_s", "backend_s")
