"""``memory_stats()`` after the window and before the reference check:
``peak_bytes_in_use`` (arrays) plus ``peak_bytes_reserved`` (the running
program's temporaries, which the TPU's allocator counts apart), largest over
the devices."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 2 ** 30 or None
