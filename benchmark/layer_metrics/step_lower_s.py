"""Tracing and lowering the train step (span ``dls.step/lower``), as the
program's ledger has it: ``compile_summary()["total_lower_s"]``. A warm start
pays it with the cache hit too; ``compile_s`` is this plus the backend's
compile or cache load."""


def read(ctx):
    return ctx["compile"].get("total_lower_s")
