"""On the chip: where the start of ``resnet50_imagenet.fit_jpeg``'s feed goes.

Runs the cell through the benchmark's own runner, in one process as
``benchmark/run.py`` does, from the checkout ``--tree`` (default: this one),
with every ``data/workers.WorkerPool`` watched: the seconds its constructor
took (each process's start among them), when each worker had mapped its
first example, when the consumer had taken a batch's worth of examples from
the pool, and a series of the examples mapped and taken over its first
seconds. Where ``fit`` opens its feed it also times what a page of shared
and of anonymous memory costs this process to map the first time, and a
fork of this process and of that child. Prints one JSON line: those, the
program's ``startup`` record, the largest ``worker_overflow`` of the laps,
and the run's ``throughput`` and ``setup_s``. A tree whose ``take`` runs
through the pool shows the trainer's sample and the check's examples as
pools of their own; the thread pool's path starts none, and its first
batch is the record's ``first_batch_s``.

    python3 tools/feed_start_split.py --seed 7 --seconds 20 \\
        [--tree DIR] [--workers N] [--freeze]

on a host with one TPU chip, from the root of a checkout.

``--workers N`` sets ``DLS_DATA_WORKERS`` for the run.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "resnet50_imagenet.fit_jpeg"
#: how often the watcher reads a pool's counters
POLL_S = 0.002
#: how long, and how often, it keeps the counters as a series
SERIES_S, SERIES_EVERY_S = 8.0, 0.25


def watch(pool, workers, t0: float, rec: dict, batch: int) -> None:
    """Until the pool closes or has run ``SERIES_S``: each worker's first
    example, the taking of the first ``batch`` examples, in seconds after
    the pool began, and every ``SERIES_EVERY_S`` the examples the workers
    had mapped, the consumer had taken, and the workers' seconds in the
    map."""
    first = [None] * pool.n
    series, next_at = [], 0.0

    def stat(cell):
        return sum(pool._stats[w * workers._ST_STRIDE + cell]
                   for w in range(pool.n))

    while True:
        closed = pool._closed  # read what it left once it has closed
        now = time.perf_counter() - t0
        for w in range(pool.n):
            if first[w] is None and pool._stats[
                    w * workers._ST_STRIDE + workers._ST_PRODUCED] >= 1:
                first[w] = round(now, 4)
        if rec.get("batch_taken_s") is None and sum(pool._consumed) >= batch:
            rec["batch_taken_s"] = round(now, 4)
        if now >= next_at:
            series.append((round(now, 3), int(stat(workers._ST_PRODUCED)),
                           sum(pool._consumed),
                           round(stat(workers._ST_BUSY), 3)))
            next_at += SERIES_EVERY_S
        rec["first_example_s"] = first
        rec["series"] = series
        if closed or now > SERIES_S:
            return
        time.sleep(POLL_S)


def fault_costs(size: int = 32 << 20) -> dict:
    """Microseconds a 4-KiB page costs this process to map for the first
    time, over ``size`` bytes of shared memory and of anonymous memory
    (written a page at a time)."""
    import mmap

    import numpy as np
    from multiprocessing import shared_memory

    pages = size // mmap.PAGESIZE
    shm = shared_memory.SharedMemory(create=True, size=size)
    try:
        t = time.perf_counter()
        a = np.frombuffer(shm.buf, np.uint8)
        a[::mmap.PAGESIZE] = 1
        del a
        shm_s = time.perf_counter() - t
    finally:
        shm.close()
        shm.unlink()
    t = time.perf_counter()
    a = np.empty(size, np.uint8)
    a[::mmap.PAGESIZE] = 1
    anon_s = time.perf_counter() - t
    return {"shm_us_per_page": round(1e6 * shm_s / pages, 3),
            "anon_us_per_page": round(1e6 * anon_s / pages, 3)}


def fork_costs() -> dict:
    """Seconds a fork of this process takes, and a fork of that child by
    itself (a process forked once, forking in its turn)."""
    r, w = os.pipe()
    t = time.perf_counter()
    pid = os.fork()
    if pid == 0:
        t1 = time.perf_counter()
        grandchild = os.fork()
        if grandchild == 0:
            os._exit(0)
        os.write(w, f"{time.perf_counter() - t1:.5f}".encode())
        os.waitpid(grandchild, 0)
        os._exit(0)
    parent_s = time.perf_counter() - t
    os.close(w)
    with os.fdopen(r) as f:
        child_s = float(f.read())
    os.waitpid(pid, 0)
    return {"from_this_process_s": round(parent_s, 5),
            "from_its_child_s": child_s}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--tree", default=ROOT)
    p.add_argument("--workers", type=int, default=None)
    p.add_argument("--freeze", action="store_true",
                   help="gc.freeze() this process's objects before each "
                        "pool forks, and leave them frozen")
    args = p.parse_args()

    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    if args.workers is not None:
        os.environ["DLS_DATA_WORKERS"] = str(args.workers)
    from benchmark.harness import runner
    from distributeddeeplearningspark_tpu import telemetry
    from distributeddeeplearningspark_tpu.data import workers

    batch = runner.resolve_cell(tree, CELL)["traffic"]["per_chip_batch"]
    pools: list[dict] = []
    construct = workers.WorkerPool.__init__

    import gc
    import multiprocessing.process as mp_process

    # the full collections of this process: when, how long, how many freed
    pauses: list = []
    began: list[float] = []

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            began[:] = [time.perf_counter()]
        elif began:
            pauses.append((round(began[0] - T_PROCESS, 2),
                           round(time.perf_counter() - began[0], 4),
                           info["collected"]))

    gc.callbacks.append(on_gc)

    starts: list[float] = []
    start = mp_process.BaseProcess.start

    def timed_start(self):
        t = time.perf_counter()
        start(self)
        starts.append(round(time.perf_counter() - t, 4))

    mp_process.BaseProcess.start = timed_start

    def timed(self, *a, **k):
        del starts[:]
        t0 = time.perf_counter()
        if args.freeze:
            gc.freeze()
        construct(self, *a, **k)
        rec = {"label": self.label, "workers": self.n,
               "fork_s": round(time.perf_counter() - t0, 4),
               "starts_s": list(starts),
               "at_s": round(t0 - T_PROCESS, 3)}
        pools.append(rec)
        threading.Thread(target=watch, args=(self, workers, t0, rec, batch),
                         daemon=True).start()

    workers.WorkerPool.__init__ = timed
    from distributeddeeplearningspark_tpu.train import trainer as trainer_lib

    feed_opened: list[float] = []
    open_feed = trainer_lib.Trainer._feed

    faults: dict = {}

    def timed_feed(self, *a, **k):
        feed_opened.append(round(time.perf_counter() - T_PROCESS, 3))
        if not faults:
            faults.update(fault_costs())
            faults["fork"] = fork_costs()
        return open_feed(self, *a, **k)

    trainer_lib.Trainer._feed = timed_feed
    result = runner.measure(tree, CELL, seed=args.seed, seconds=args.seconds,
                            trace=False, t_process=T_PROCESS)
    tele = os.path.join(result["facts"]["out_dir"], "telemetry")
    events = telemetry.read_events(tele)
    startup = [e for e in events if e.get("kind") == "startup"]
    overflow = [e.get("worker_overflow", 0) for e in events
                if e.get("kind") == "step_metrics"]
    print(json.dumps({
        "tree": tree, "workers_env": args.workers, "seed": args.seed,
        "cpus": len(os.sched_getaffinity(0)),
        "correct": result["correct"],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "feed_opened_at_s": feed_opened, "costs_at_feed_open": faults,
        "freeze": args.freeze, "full_gc_pauses": pauses,
        "pools": pools,
        "startup": startup[0] if startup else None,
        "worker_overflow_max": max(overflow, default=None),
    }, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
