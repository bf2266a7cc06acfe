"""The train step's own compile time as the program's ledger has it
(``InstrumentedFunction.compile_summary()``): trace, lower, and backend
compile or cache load."""


def read(ctx):
    return ctx["compile"].get("total_compile_s")
