#!/usr/bin/env python
"""perf_guard — within-run decline sentinel over a run's series store.

The health engine (``dlstatus WORKDIR --health``) records each run's series
(``telemetry/series.py``). This tool splits every guarded series into time
quartiles and compares the last quartile's mean with the first's: a decline,
direction-aware, past ``--band`` (default 15%) is ``REGRESSED`` (rc 1) and
names the series; ``OK`` and ``INSUFFICIENT_HISTORY`` (fewer than
:data:`SERIES_MIN_BUCKETS` buckets in every guarded series) exit 0; no store
under WORKDIR exits 2.

::

    python tools/perf_guard.py --series WORKDIR [--band 0.10] [--json]

Speed across PRs is not judged here: the driver measures every cell of
``BENCHMARK.json`` on the chip and keeps ``PERF_LEDGER.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any

#: series the within-run judge guards, with direction (names from
#: telemetry/series.py; the store's per-replica/tenant keys are matched by
#: base name, so ``queue_depth{replica=p0}`` judges as ``queue_depth``).
SERIES_HIGHER_BETTER = ("steps_per_sec", "goodput_frac", "mfu",
                        "hbm_headroom_frac")
SERIES_LOWER_BETTER = ("queue_depth", "shed_rate", "request_p99_s",
                       "slo_burn_rate", "shuffle_spill_rate",
                       "heartbeat_age_s", "engine_tick_s",
                       "engine_lag_bytes")

#: a quartile needs at least this many finest-resolution buckets to be a
#: judgment rather than a guess (2 per quartile).
SERIES_MIN_BUCKETS = 8


def guard_series(buckets_by_key: dict[str, list[dict]], *,
                 band: float = 0.15) -> dict:
    """Within-run decline judgment from the series store.

    For each guarded series: split its buckets into time quartiles and
    compare the last quartile's mean against the first's — a decline
    (direction-aware) past ``band`` is REGRESSED naming the series. Pure
    function over a :func:`telemetry.series.read_buckets` result; the CLI
    wraps it with ``--series WORKDIR``. ``INSUFFICIENT_HISTORY`` when no
    guarded series has :data:`SERIES_MIN_BUCKETS` buckets yet."""
    checks: list[dict] = []
    for key, bs in sorted(buckets_by_key.items()):
        base_name = key.split("{", 1)[0]
        if base_name in SERIES_HIGHER_BETTER:
            direction = "higher"
        elif base_name in SERIES_LOWER_BETTER:
            direction = "lower"
        else:
            continue
        row: dict[str, Any] = {"check": key, "direction": direction,
                               "buckets": len(bs)}
        if len(bs) < SERIES_MIN_BUCKETS:
            row["status"] = "insufficient-history"
            checks.append(row)
            continue
        q = len(bs) // 4
        first = [b["mean"] for b in bs[:q]]
        last = [b["mean"] for b in bs[-q:]]
        first_mean = sum(first) / len(first)
        last_mean = sum(last) / len(last)
        row["first_quartile_mean"] = round(first_mean, 6)
        row["last_quartile_mean"] = round(last_mean, 6)
        if first_mean == 0:
            # nothing to decline from (and a lower-better series that
            # started at 0 and grew is the trend rules' beat, not a
            # within-run throughput regression)
            row["status"] = "ok"
            checks.append(row)
            continue
        delta = (last_mean - first_mean) / abs(first_mean)
        row["delta_pct"] = round(100.0 * delta, 2)
        worse = -delta if direction == "higher" else delta
        row["status"] = "REGRESSED" if worse > band else "ok"
        checks.append(row)
    regressed = [c for c in checks if c["status"] == "REGRESSED"]
    judged = [c for c in checks if c["status"] != "insufficient-history"]
    if regressed:
        verdict = "REGRESSED"
    elif judged:
        verdict = "OK"
    else:
        verdict = "INSUFFICIENT_HISTORY"
    return {
        "verdict": verdict,
        "mode": "series",
        "band": band,
        "checks": checks,
        "regressed": [c["check"] for c in regressed],
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_guard",
        description="Within-run decline sentinel over a series store.")
    ap.add_argument("--series", metavar="WORKDIR", required=True,
                    help="judge within-run decline from WORKDIR's series "
                         "store (last quartile vs first quartile of each "
                         "guarded series, finest resolution)")
    ap.add_argument("--band", type=float, default=0.15,
                    help="noise band as a fraction (default 0.15 = 15%%)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    from distributeddeeplearningspark_tpu.telemetry import series as series_lib

    ladder = series_lib.list_resolutions(args.series)
    if not ladder:
        print(f"perf_guard: no series store under {args.series} — run the "
              f"health engine first (dlstatus WORKDIR --health)",
              file=sys.stderr)
        return 2
    buckets = series_lib.read_buckets(args.series, ladder[0][0])
    rep = guard_series(buckets, band=args.band)
    rep["workdir"] = args.series
    rep["resolution_s"] = ladder[0][0]
    if args.json:
        print(json.dumps(rep))
    else:
        print(f"perf_guard: {rep['verdict']}  mode=series  "
              f"workdir={args.series}  resolution={ladder[0][0]:g}s  "
              f"band={100 * args.band:.0f}%")
        for c in rep["checks"]:
            line = (f"  [{c['status']:>22}] {c['check']}: "
                    f"buckets={c['buckets']}")
            if c.get("first_quartile_mean") is not None:
                line += (f"  first-quartile={c['first_quartile_mean']}"
                         f"  last-quartile={c['last_quartile_mean']}")
            if c.get("delta_pct") is not None:
                line += f"  delta={c['delta_pct']:+.1f}%"
            print(line)
        if rep["regressed"]:
            print(f"perf_guard: REGRESSED on {', '.join(rep['regressed'])}")
    return 1 if rep["verdict"] == "REGRESSED" else 0


if __name__ == "__main__":
    sys.exit(main())
