"""What the Mamba layers' ungated convolution costs, on the chip:
``ops/short_conv.silu_short_conv`` ALONE at ``nemotron3_nano_30b_a3b.
fit_seg16k``'s shapes (16,384 positions, 6,144 channels, four taps, a bias,
segment ids), as the two kernels at each ``--block`` and as the plain XLA path.

    python3 tools/mamba_conv_cost.py --block 512,512   # on one TPU chip

The arrays come and go with their positions minor, as the layer's input
projection hands them over, so no transpose is timed. One JSON line a path
and pass: ms a call by the host clock (the median of interleaved rounds) of
the forward alone and of the gradient (``jax.vjp``, the forward inside it),
beside the least time of the bytes the pass must move at the peak bandwidth of
``benchmark/peaks.json`` (forward: read ``v``, write ``y``; backward: read
``v`` and ``dy``, write ``dv``; all bf16), and the largest difference of ``y``
and of each gradient from the XLA path's, over its largest magnitude. Refuses
to run off the TPU: a CPU time is no time.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from distributeddeeplearningspark_tpu.ops import short_conv

#: the cell's Mamba layer: x, B and C of 4,096 + 2 x 8 x 128 channels
B, S, C, K = 1, 16384, 6144, 4


def _inputs(seed):
    """``v`` and the cotangent in bf16 as the mixer hands them, positions
    minor (``[B, C, S]``), the taps and the bias in float32, segment ids of
    seeded log-normal documents (median 6,000, as the cell's feed)."""
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.normal(size=(B, C, S)), jnp.bfloat16)
    ct = jnp.asarray(rng.normal(size=(B, C, S)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (C, K)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.1, C), jnp.float32)
    ends = np.cumsum(np.clip(rng.lognormal(np.log(6000), 1.0, 64), 64,
                             65536).astype(np.int64))
    seg = jnp.asarray(np.searchsorted(ends, np.arange(S), side="right")
                      .astype(np.int32)[None, :])
    return (v, w, bias), seg, ct


def _least_ms(n_bytes: float) -> float:
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    kind = jax.devices()[0].device_kind
    return n_bytes / peaks[kind]["hbm_bytes_per_s"] * 1e3


def _time(fns, args, rounds, calls):
    """Interleaved rounds of ``calls`` calls each: ms a call, a list a fn."""
    ms = {label: [] for label in fns}
    for _ in range(rounds):
        for label, fn in fns.items():
            t0 = time.perf_counter()
            for _ in range(calls):
                out = fn(*args[label])
            jax.block_until_ready(out)
            ms[label].append((time.perf_counter() - t0) / calls * 1e3)
    return ms


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--rounds", type=int, default=4)
    p.add_argument("--calls", type=int, default=30)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--block", action="append", default=[],
                   help="T,C: the kernels' positions and channels a block")
    args = p.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("mamba_conv_cost: not on a TPU; a CPU time is no time",
              file=sys.stderr)
        return 1

    arrays, seg, ct = _inputs(args.seed)
    paths = {"xla": short_conv.silu_short_conv_xla}
    for block in args.block or [f"{short_conv.MAMBA_BLOCK_T},"
                                f"{short_conv.MAMBA_BLOCK_C}"]:
        t, c = (int(n) for n in block.split(","))
        paths[f"pallas_{t}x{c}"] = functools.partial(
            short_conv.silu_short_conv_pallas, block_t=t, block_c=c,
            interpret=False)

    def minor(conv):
        """``conv`` on ``[B, C, S]`` arrays (a transpose each way that XLA
        makes a change of layout)."""
        return lambda vt, w, bias: jnp.swapaxes(
            conv(jnp.swapaxes(vt, 1, 2), w, bias, seg), 1, 2)

    def forward(conv):
        return jax.jit(minor(conv))

    def gradient(conv):
        def fn(arrays, ct):
            y, back = jax.vjp(minor(conv), *arrays)
            return y, back(ct)
        return jax.jit(fn)

    fns = {f"{label}.{kind}": make(conv) for label, conv in paths.items()
           for kind, make in (("forward", forward), ("gradient", gradient))}
    call_args = {label: (arrays if label.endswith("forward") else
                         (arrays, ct)) for label in fns}
    outs = {label: jax.block_until_ready(fn(*call_args[label]))
            for label, fn in fns.items()}                # compiled, warm
    ms = _time(fns, call_args, args.rounds, args.calls)

    one = B * S * C * 2                                  # a bf16 [B, S, C]
    least = {"forward": _least_ms(2 * one), "gradient": _least_ms(5 * one)}
    for label in fns:
        path, kind = label.split(".")
        line = {"path": path, "pass": kind,
                "ms_a_call": statistics.median(ms[label]),
                "ms_rounds": ms[label], "least_ms": least[kind]}
        got, want = outs[label], outs[f"xla.{kind}"]
        if kind == "forward":
            line["y_rel_diff"] = _rel(got, want)
        else:
            line["y_rel_diff"] = _rel(got[0], want[0])
            line["grad_rel_diff"] = {
                n: _rel(g, f) for n, g, f in
                zip(("v", "taps", "bias"), got[1], want[1])}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
