"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, every chip of the machine, no CPU mode: without a TPU whose
``device_kind`` is in ``peaks.json``, or with another number of chips than the
cell is defined on, it exits non-zero and prints no result line. Facts go on
``# ``-prefixed lines; the LAST line of stdout is the result object.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device",
               "breakdown")


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = p.parse_args()

    sys.path.insert(0, ROOT)
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        print("benchmark: JAX_PLATFORMS=cpu: there is no CPU mode",
              file=sys.stderr)
        return 1
    try:
        import distributeddeeplearningspark_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 1
    from benchmark.harness import runner

    try:
        result = runner.measure(ROOT, args.workload, seed=args.seed,
                                seconds=args.seconds, trace=bool(args.trace),
                                t_process=T_PROCESS)
    except runner.Refused as e:
        for reason in e.args[0]:
            print(f"benchmark: refused: {reason}", file=sys.stderr)
        return 1
    if result["device"]["platform"] != "tpu":
        print("benchmark: not a TPU run: no result", file=sys.stderr)
        return 1
    if not result["metrics"]:
        print("benchmark: the run produced no metric", file=sys.stderr)
        return 1
    print(json.dumps({k: result[k] for k in RESULT_KEYS if k in result}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
