"""End-to-end serving example: train LeNet a few steps, serve it hot.

The minimal train→serve loop on one CPU (runs in CI — `tools/ci.sh serve`):

1. train a LeNet-5 for a few steps (config-1 setup, synthetic MNIST) and
   checkpoint it;
2. start the dynamic-batching engine in-process on the trained params;
3. fire concurrent synthetic clients through it (and, for comparison, an
   engine pinned to single-request batches);
4. mid-traffic, save a NEWER checkpoint and let the hot-reloader swap it
   in — zero dropped requests;
5. print a latency/throughput summary (one JSON line).

::

    python examples/serve_mnist.py --steps 8 --clients 16 --requests 4
"""

import argparse
import json
import sys
import tempfile
import threading
import time

import numpy as np
import optax

from distributeddeeplearningspark_tpu import Checkpointer, Session, Trainer
from distributeddeeplearningspark_tpu.data.sources import synthetic_mnist
from distributeddeeplearningspark_tpu.models import LeNet5
from distributeddeeplearningspark_tpu.serve import HotReloader, InferenceEngine
from distributeddeeplearningspark_tpu.serve.cli import _pct, run_load
from distributeddeeplearningspark_tpu.train import losses


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--master", default="local[2]")
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--clients", type=int, default=16)
    p.add_argument("--requests", type=int, default=4,
                   help="requests per client")
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--workdir", default=None,
                   help="checkpoint + telemetry dir (default: a tmp dir)")
    args = p.parse_args()

    workdir = args.workdir or tempfile.mkdtemp(prefix="serve_mnist_")

    # -- 1. train a few steps (train_mnist-style setup) ----------------------
    spark = Session.builder.master(args.master).appName("serve-mnist").getOrCreate()
    ds = synthetic_mnist(2048, num_partitions=spark.default_parallelism, seed=0)
    model = LeNet5()
    with Checkpointer(workdir, async_save=False) as ckpt:
        trainer = Trainer(spark, model, losses.softmax_xent,
                          optax.sgd(0.05, momentum=0.9), checkpointer=ckpt)
        trainer.fit(ds.repeat(), batch_size=args.batch_size, steps=args.steps,
                    log_every=args.steps, checkpoint_every=args.steps)

        # -- 2. serve the trained checkpoint ---------------------------------
        params, step = ckpt.restore_params()
        print(f"serving checkpoint step {step}", file=sys.stderr)
        rng = np.random.default_rng(1)

        def example(i: int):
            return {"image": rng.normal(0, 1, (28, 28, 1)).astype(np.float32)}

        engine = InferenceEngine.for_model(
            model, {"params": params}, max_batch=args.max_batch,
            max_wait_ms=5.0, max_queue=4096, workdir=workdir, name="lenet")
        with engine:
            engine.warmup(example(0))

            # -- 4. hot-reload drill: newer checkpoint lands mid-traffic ----
            trainer.fit(ds.repeat(), batch_size=args.batch_size,
                        steps=args.steps * 2, log_every=args.steps,
                        checkpoint_every=args.steps)
            from distributeddeeplearningspark_tpu.serve.reload import (
                checkpoint_params_loader,
            )

            reloader = HotReloader(
                engine, workdir, current_step=step,
                load_params=checkpoint_params_loader(
                    workdir, wrap_in_variables=True))

            # the reload must land MID-traffic to mean anything: a helper
            # thread waits until the engine has requests in flight, then
            # polls once — the swap races real batches, and the zero-drop
            # assertion below attests the property the docs claim
            def reload_when_traffic_flows():
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    st = engine.stats()
                    if st["queue_depth"]:  # requests in flight right now
                        break
                    time.sleep(0.001)
                reloader.poll()

            swapper = threading.Thread(target=reload_when_traffic_flows)
            swapper.start()
            try:
                # -- 3. concurrent load --------------------------------------
                lat, shed, wall = run_load(
                    engine, example, clients=args.clients,
                    requests_per_client=args.requests)
            finally:
                swapper.join()
                reloader.stop()
            stats = engine.stats()

        # single-request comparison arm (same machinery, no coalescing);
        # no workdir — its events would pollute the run's serving rollup
        seq = InferenceEngine.for_model(
            model, {"params": params}, max_batch=1, max_wait_ms=0.0,
            batch_sizes=(1,), max_queue=4096, name="lenet-seq")
        with seq:
            seq.warmup(example(0))
            seq_lat, _, seq_wall = run_load(
                seq, example, clients=args.clients,
                requests_per_client=args.requests)
    spark.stop()

    # -- 5. summary ----------------------------------------------------------
    rps = len(lat) / wall if wall > 0 else 0.0
    seq_rps = len(seq_lat) / seq_wall if seq_wall > 0 else 0.0
    rec = {
        "metric": "serve_mnist_requests_per_sec",
        "value": round(rps, 1),
        "unit": "req/s",
        "extra": {
            "clients": args.clients,
            "requests_ok": len(lat),
            "requests_shed": shed,
            "latency_p50_ms": round(_pct(lat, 0.5) * 1e3, 2) if lat else None,
            "latency_p99_ms": round(_pct(lat, 0.99) * 1e3, 2) if lat else None,
            "sequential_requests_per_sec": round(seq_rps, 1),
            "batching_speedup": round(rps / seq_rps, 2) if seq_rps else None,
            "served_params_version": stats["params_version"],
            "hot_reloads": stats["reloads"],
            "checkpoint_step_at_start": step,
            "workdir": workdir,
        },
    }
    assert stats["reloads"] >= 1, "hot reload never fired during the load"
    assert shed == 0 and len(lat) == args.clients * args.requests, \
        "requests were dropped across the hot reload"
    print(json.dumps(rec))
    print(f"dlstatus {workdir}   # p50/p99 rollup from the request telemetry",
          file=sys.stderr)


if __name__ == "__main__":
    main()
