"""The program's own account of its start: the one ``startup`` record a
process writes when the first lap of its first ``fit`` closes
(``telemetry/anatomy.StartupLedger``; docs/OBSERVABILITY.md). Read where an
operator would read it, from the stream under ``DLS_TELEMETRY_DIR``. A
program that writes no such record (one older than the record) reads
nothing, and nothing raises."""

from __future__ import annotations

import os


def record(ctx) -> dict | None:
    """The stream's first ``startup`` record, or ``None``; read once a run."""
    if "startup" not in ctx:
        from distributeddeeplearningspark_tpu import telemetry

        workdir = os.environ.get(telemetry.WORKDIR_ENV)
        events = telemetry.read_events(workdir) if workdir else []
        ctx["startup"] = next(
            (e for e in events if e.get("kind") == "startup"), None)
    return ctx["startup"]


def seconds(ctx, *keys: str) -> float | None:
    """The sum of the record's ``keys``; ``None`` without a record, or with
    one that lacks a key."""
    rec = record(ctx)
    if rec is None or any(k not in rec for k in keys):
        return None
    return sum(float(rec[k]) for k in keys)


def facts(ctx, metric: str, *keys: str) -> None:
    """Print ``keys`` of the record under ``metric`` in ``layer_facts``."""
    rec = record(ctx)
    if rec is not None:
        ctx["facts"][metric] = {k: rec.get(k) for k in keys}
