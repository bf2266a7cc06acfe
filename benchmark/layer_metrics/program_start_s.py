"""Harness clock: ``Session.builder...getOrCreate()`` begins -> the first
training step has synced (dataset built, state initialised, step compiled or
loaded from the cache, first batch fed)."""


def read(ctx):
    if ctx["first_step_t"] is None:
        return None
    ctx["facts"]["compile_stages_s"] = ctx["compile_stages_s"]
    return ctx["first_step_t"] - ctx["clock"]["session_begin"]
