"""What the state-space scan costs, on the chip: ``ops/ssd.ssd_scan`` ALONE,
forward and backward at ``nemotron3_nano_30b_a3b.fit_seg16k``'s shapes, in
several trees, and how close its running sum of ``dt A`` stays to float32.

    chiprun --chips 1 -- python3 tools/ssd_scan_cost.py \
        --tree parent=<a parent checkout>/distributeddeeplearningspark_tpu/ops/ssd.py

Trees: each ``--tree LABEL=FILE`` (another checkout's ``ops/ssd.py``,
first), then ``product`` (this checkout) and ``cumsum_minor`` (this checkout
with ``running_sum`` a ``jnp.cumsum`` along the chunk's positions, which are
minor there). One JSON line a tree: ms a call of the gradient (``jax.vjp``,
the forward inside it) by the host clock, the median of interleaved blocks,
and the largest difference of ``y`` and of each gradient from the first
tree's, over its largest magnitude. Then one line a chunk of 8 and 128:
the largest relative error of ``running_sum`` at ``Precision.HIGHEST``, of
``jnp.cumsum`` and of the product at the default precision (one bf16 pass
on the TPU), each against a float64 sum, beside ``chunk x 2**-23``. Refuses
to run off the TPU: a CPU time is no time.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributeddeeplearningspark_tpu.ops import ssd as this_tree

#: the cell's Mamba layer: a window of 16,384, 64 heads of 64, 8 groups of B
#: and C with a state of 128, chunks of 128
B, S, H, P, G, N, CHUNK = 1, 16384, 64, 64, 8, 128, 128


def _load(label, path):
    spec = importlib.util.spec_from_file_location(label + "_ssd", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _inputs(seed):
    """The scan's six arrays in the dtypes the mixer hands them, and segment
    ids of seeded log-normal documents (median 6,000, as the cell's feed)."""
    rng = np.random.default_rng(seed)
    bf16, f32 = jnp.bfloat16, jnp.float32
    arr = lambda shape, dtype, scale=1.0: jnp.asarray(
        rng.normal(0, scale, shape), dtype)
    x, bm, cm = arr((B, S, H, P), bf16), arr((B, S, G, N), bf16), arr(
        (B, S, G, N), bf16)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                        (B, S, H))), f32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, H), f32)
    d = jnp.ones((H,), f32)
    ends = np.cumsum(np.clip(rng.lognormal(np.log(6000), 1.0, 64), 64,
                             65536).astype(np.int64))
    seg = jnp.asarray(np.searchsorted(ends, np.arange(S), side="right")
                      .astype(np.int32)[None, :])
    ct = arr((B, S, H, P), bf16)
    return (x, dt, a, bm, cm, d), seg, ct


def _gradient(mod, seg):
    def fn(args, ct):
        y, back = jax.vjp(lambda *a: mod.ssd_scan(*a, seg, chunk=CHUNK)[0],
                          *args)
        return y, back(ct)
    return jax.jit(fn)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _precision(chunk, seed):
    """``dt`` log-uniform in [0.01, 1], ``A`` in [-16, -1], one row at the
    harshest (``dt`` 1, ``A`` -16): one sign, so ``|cs|`` is the sum of
    the terms' magnitudes and a relative error is well defined."""
    rng = np.random.default_rng(seed)
    dt = np.exp(rng.uniform(np.log(0.01), 0.0, (64, 64, chunk)))
    a = -rng.uniform(1.0, 16.0, (64, 64, 1))
    dt[0], a[0] = 1.0, -16.0
    v64 = dt * a
    want = np.cumsum(v64, axis=-1)
    v = jnp.asarray(v64, jnp.float32)
    upper = jnp.triu(jnp.ones((chunk, chunk), jnp.float32))
    rel = lambda got: float(np.max(np.abs(np.asarray(got, np.float64) - want)
                                   / np.abs(want)))
    return {"chunk": chunk,
            "highest": rel(jax.jit(this_tree.running_sum)(v)),
            "cumsum": rel(jax.jit(lambda t: jnp.cumsum(t, axis=-1))(v)),
            "default": rel(jax.jit(lambda t: jnp.matmul(t, upper))(v)),
            "float32_bound": chunk * 2.0 ** -23}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tree", action="append", default=[],
                   help="LABEL=FILE: another checkout's ops/ssd.py")
    p.add_argument("--blocks", type=int, default=4)
    p.add_argument("--calls", type=int, default=30)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("ssd_scan_cost: not on a TPU; a CPU time is no time",
              file=sys.stderr)
        return 1

    cumsum_minor = _load("cumsum_minor", this_tree.__file__)
    cumsum_minor.running_sum = lambda v: jnp.cumsum(v, axis=-1)
    trees = [(label, _load(label, path)) for label, path in
             (t.split("=", 1) for t in args.tree)]
    trees += [("product", this_tree), ("cumsum_minor", cumsum_minor)]

    arrays, seg, ct = _inputs(args.seed)
    fns = {label: _gradient(mod, seg) for label, mod in trees}
    outs = {label: jax.block_until_ready(fn(arrays, ct))
            for label, fn in fns.items()}                # compiled, warm
    ms = {label: [] for label in fns}
    for _ in range(args.blocks):
        for label, fn in fns.items():
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = fn(arrays, ct)
            jax.block_until_ready(out)
            ms[label].append((time.perf_counter() - t0) / args.calls * 1e3)

    first = outs[trees[0][0]]
    names = ("x", "dt", "A", "B", "C", "D")
    for label, (y, grads) in outs.items():
        print(json.dumps({
            "tree": label, "ms_a_call": statistics.median(ms[label]),
            "ms_blocks": ms[label], "y_rel_diff": _rel(y, first[0]),
            "grad_rel_diff": {n: _rel(g, f) for n, g, f in
                              zip(names, grads, first[1])}}), flush=True)
    for chunk in (8, CHUNK):
        print(json.dumps(_precision(chunk, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
