"""What a grid step of the flash kernels costs, on the chip: the three kernels
of ``ops/flash_attention.py`` ALONE at a cell's shape, each in several trees,
timed from a profiler trace by the kernels' names, and compared to the bit.

    chiprun --chips 1 -- python3 tools/flash_step_cost.py --shape s16k \
        --tree parent=<a parent checkout>/distributeddeeplearningspark_tpu/ops/flash_attention.py

Trees: each ``--tree LABEL=FILE`` (another checkout's
``ops/flash_attention.py``, first), then ``classed`` (this checkout) and
``all_edge`` (this checkout with ``_block_whole`` answering False: every
walked block takes the masking body, which leaves the index maps' clamping
alone). Windows: ``s16k`` (1 x 16,384, 32 heads, q and k 192 wide, v
128, no segment ids) runs ``causal`` and ``full`` (not causal: every step the
unmasked body and its copies); ``seg32k`` (1 x 32,768, 32 / 8 heads of 64,
causal, segment ids) runs ``one_doc``, ``diagonal`` (documents of one block)
and ``documents`` (seeded log-normal lengths, median 6,000). One JSON line a
(window, tree): ms a run of each kernel, the grid's steps by class (from this
checkout's predicates) and the largest difference of o, lse, dq, dk, dv from
the first tree's. Refuses to run off the TPU: a CPU time is no time.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import trace_reduce
from distributeddeeplearningspark_tpu.ops import flash_attention as this_tree

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
BLOCK = this_tree.DEFAULT_BLOCK
#: shape -> (S, q heads, kv heads, d_qk, d_v, windows)
SHAPES = {
    "s16k": (16384, 32, 32, 192, 128, ("causal", "full")),
    "seg32k": (32768, 32, 8, 64, 64, ("one_doc", "diagonal", "documents")),
}


def _load(label, path):
    spec = importlib.util.spec_from_file_location(label + "_flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _segment_ids(window, s, seed):
    if window in ("causal", "full"):
        return None
    if window == "one_doc":
        return np.zeros((1, s), np.int32)
    if window == "diagonal":
        return (np.arange(s, dtype=np.int32) // BLOCK)[None, :]
    lengths = np.random.default_rng(seed).lognormal(np.log(6000), 1.0, 64)
    ends = np.cumsum(np.clip(lengths, 64, 65536).astype(np.int64))
    return np.searchsorted(ends, np.arange(s), side="right").astype(
        np.int32)[None, :]


def _classes(segs, s, causal):
    """(nothing, whole, edge) grid steps a head, by this checkout's own
    predicates (a window without ids is one document)."""
    ids = jnp.asarray(np.zeros((1, s), np.int32) if segs is None else segs)
    walk, whole = this_tree.segment_block_classes(
        ids, ids, causal=causal, block_q=BLOCK, block_k=BLOCK)
    walked, whole = int(jnp.sum(walk)), int(jnp.sum(whole))
    return {"nothing": walk[0].size - walked, "whole": whole,
            "edge": walked - whole}


def _run(mod, window, arrays, segs, group, reps, trace_dir):
    """The three kernels ``reps`` times under the profiler -> (ms a run of
    each kernel, the outputs of the last run)."""
    q, k, v, do = arrays
    opts = dict(scale=q.shape[-1] ** -0.5, causal=window != "full",
                group=group, block_q=BLOCK, block_k=BLOCK, interpret=False)

    @jax.jit
    def step(q, k, v, do, segs):
        o, lse = mod._flash_fwd(q, k, v, None, q_segs=segs, kv_segs=segs,
                                **opts)
        dq, dk, dv = mod._flash_bwd((q, k, v, None, o, lse, segs, segs), do,
                                    **opts)
        return o, lse, dq, dk, dv

    out = jax.block_until_ready(step(q, k, v, do, segs))   # compiles
    jax.profiler.start_trace(trace_dir)
    for _ in range(reps):
        out = jax.block_until_ready(step(q, k, v, do, segs))
    jax.profiler.stop_trace()
    ex = trace_reduce.extract(trace_reduce.find_xplane(trace_dir))
    ms = {}
    for name in KERNELS:
        events = trace_reduce.select(ex, "0", name + r"\b")
        assert len(events) == reps, (name, len(events))
        ms[name] = 1e3 * trace_reduce.summed_s(events) / reps
    return ms, out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", choices=sorted(SHAPES), action="append")
    ap.add_argument("--tree", action="append", default=[],
                    metavar="LABEL=FILE",
                    help="another tree's ops/flash_attention.py")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("flash_step_cost: no TPU here, and a CPU time is no time")

    # (tree, its module, whether every walked block is to be an edge)
    trees = [(label, _load(label, path), False) for label, path in
             (t.split("=", 1) for t in args.tree)]
    trees += [("classed", this_tree, False), ("all_edge", this_tree, True)]
    for shape in args.shape or sorted(SHAPES):
        s, h, hkv, d_qk, d_v, windows = SHAPES[shape]
        rng = np.random.default_rng(args.seed)
        arrays = [jnp.asarray(rng.normal(0, 1, (heads, s, d)), jnp.bfloat16)
                  for heads, d in ((h, d_qk), (hkv, d_qk), (hkv, d_v),
                                   (h, d_v))]
        for window in windows:
            ids = _segment_ids(window, s, args.seed)
            segs = None if ids is None else jnp.asarray(ids)
            steps = _classes(ids, s, window != "full")
            first = None
            for label, mod, every_block_an_edge in trees:
                whole = this_tree._block_whole
                if every_block_an_edge:
                    this_tree._block_whole = lambda *a, **kw: False
                try:
                    with tempfile.TemporaryDirectory() as trace_dir:
                        ms, out = _run(mod, window, arrays, segs, h // hkv,
                                       args.reps, trace_dir)
                finally:
                    this_tree._block_whole = whole
                first = first or out
                differ = [float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                    for a, b in zip(out, first)]
                print(json.dumps({
                    "shape": shape, "window": window, "tree": label,
                    "ms": ms, "ms_sum": sum(ms.values()),
                    "steps_a_head": steps,
                    "max_abs_diff_from_first_tree": dict(
                        zip(("o", "lse", "dq", "dk", "dv"), differ)),
                    "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
