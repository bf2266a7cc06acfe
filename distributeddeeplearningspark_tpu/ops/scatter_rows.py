"""Pallas row-scatter kernel — the DLRM 92 ns/row falsification experiment.

VERDICT r2 weak-#7 / next-#9: the sparse-embed step's remaining floor is
XLA's TPU scatter applying ~213k row updates at ~92 ns/row (19.6 ms of the
29.5 ms DLRM step), hypothesized DMA-issue-bound. One A/B decided the
current layout; this kernel is the falsification experiment: a minimal
Pallas scatter-ADD over dynamically indexed rows, so the hypothesis "the
floor is the per-row DMA issue rate, not XLA's scatter emitter" gets a
direct measurement (``bench.py --model dlrm --scatter-ab`` on a chip).

Design: scalar-prefetched indices drive the output BlockSpec's index map —
grid step i addresses table row ``idx[i]`` as a (1, 1, D) block of the
[V, 1, D] view (the unit middle dim satisfies Mosaic's sublane block rule
for row-granular access). ``input_output_aliases`` makes it an in-place
read-modify-write: each step reads the current row block, adds its update
row, writes back. Indices MUST be unique (duplicate rows would race across
grid steps — same contract the XLA path's ``unique_indices=True`` asserts)
and STRICTLY in-range: unlike the XLA path there is no ``mode='drop'`` —
an OOB id would address a block row past V (OOB DMA in compiled mode).
The real embed caller (train/embed.py rowwise_adagrad_update) pads with
OOB sentinels and relies on drop semantics — that caller must go through
:func:`scatter_add_rows_dropping`, the guarded boundary that redirects
sentinels to a discarded scratch row (and is what ``scatter_impl="pallas"``
wires); the raw kernel cannot be called with sentinel inputs safely.

If this measures at ≈92 ns/row, the DMA-bound floor stands confirmed and
BASELINE.md records it; if it beats XLA, it becomes the embed path's
scatter. Either way the question closes with data.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributeddeeplearningspark_tpu.utils.env import on_tpu, pallas_interpret


def _scatter_add_kernel(idx_ref, upd_ref, table_ref, out_ref):
    """One grid step: out row (aliased table row idx[i]) += update row i."""
    del idx_ref  # consumed by the index maps, not the body
    out_ref[:] = table_ref[:] + upd_ref[:].astype(table_ref.dtype)


def scatter_add_rows(
    table: jax.Array,     # [V, D]
    idx: jax.Array,       # [K] int32, UNIQUE, in-range
    updates: jax.Array,   # [K, D]
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """``table[idx] += updates`` via a Pallas grid of per-row DMAs.

    Semantically ``table.at[idx].add(updates, unique_indices=True)`` —
    parity-tested against it; exists to measure whether a hand-rolled
    row-granular scatter can beat XLA's emitter at the DLRM shape.
    """
    v, d = table.shape
    k = idx.shape[0]
    if updates.shape != (k, d):
        raise ValueError(f"updates must be [{k}, {d}], got {updates.shape}")
    interpret = pallas_interpret(interpret)

    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=[
            # update row i: (1, 1, D) of the [K, 1, D] view
            pl.BlockSpec((1, 1, d), lambda i, idx_ref: (i, 0, 0)),
            # table row idx[i] (aliased with the output)
            pl.BlockSpec((1, 1, d), lambda i, idx_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, idx_ref: (idx_ref[i], 0, 0)),
    )
    out = pl.pallas_call(
        _scatter_add_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((v, 1, d), table.dtype),
        input_output_aliases={2: 0},  # args: (idx, updates, table) → out
        interpret=interpret,
    )(idx.astype(jnp.int32), updates[:, None, :], table[:, None, :])
    return out[:, 0, :]


def scatter_add_rows_dropping(
    table: jax.Array,     # [V, D]
    idx: jax.Array,       # [K] int32 — UNIQUE among in-range ids; ids >= V
                          # are drop sentinels (train/embed.py's padding)
    updates: jax.Array,   # [K, D]
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Drop-semantics boundary for :func:`scatter_add_rows` (VERDICT r3
    weak-#7 / next-#6): its intended caller pads with out-of-range sentinel
    ids and relies on XLA's ``mode='drop'``, which the raw kernel does NOT
    have — an OOB id would issue an OOB DMA in compiled mode. This wrapper
    makes sentinel inputs safe to wire:

    - sentinel ids (``>= V``) are redirected to a scratch row appended at
      index V, and their update rows zeroed;
    - the scratch row is sliced off afterward, so repeated sentinel hits
      can only corrupt a row nobody reads (grid-step write pipelining makes
      repeated-row read-modify-write unordered — confining the repeats to
      the scratch row is what makes them harmless);
    - duplicate IN-RANGE ids remain the caller's contract, exactly as with
      ``unique_indices=True`` on the XLA path.

    Costs one [V+1, D] concat (a table copy) vs the raw kernel's in-place
    alias — acceptable for wiring safety; the falsification A/B
    (``bench.py --model dlrm --scatter-ab``) measures the raw kernel.
    """
    v, d = table.shape
    pad = idx >= v
    safe_idx = jnp.where(pad, v, idx).astype(jnp.int32)
    safe_upd = jnp.where(pad[:, None], jnp.zeros_like(updates), updates)
    ext = jnp.concatenate([table, jnp.zeros((1, d), table.dtype)], axis=0)
    out = scatter_add_rows(ext, safe_idx, safe_upd, interpret=interpret)
    return out[:v]


def bench_scatter_ab(k: int = 212_992, v: int = 2_600_000, d: int = 64,
                     iters: int = 20, repeats: int = 3,
                     max_repeats: int = 9,
                     spread_target_pct: float = 1.5) -> dict:
    """Timed A/B at the DLRM bench shape: XLA ``.at[].add`` vs the Pallas
    row kernel. Returns ns/row for both (run on a real chip).

    Discipline mirrors bench.bench_steps: the table CHAINS through
    iterations (a data dependency, so async dispatch can't stack ~665 MB
    output buffers k-deep in HBM), each timing ends in a device_get of the
    chained table, and ``repeats`` windows report median + spread so
    run-to-run noise can't silently flip the experiment's verdict.

    Adaptive windows (VERDICT r4 weak-#6: the r4 record's 7.23% spread was
    5× the repo's own ≤1.5% discipline): after the first ``repeats``
    windows, each arm keeps adding windows until its min-to-max spread is
    ≤ ``spread_target_pct`` or ``max_repeats`` is reached; the record says
    which, so a still-noisy row can't masquerade as a clean one.
    """
    import time

    import numpy as np

    if not on_tpu():
        raise RuntimeError(
            "scatter A/B is a device experiment; interpret-mode Pallas at "
            "k=212k rows would loop for hours — run on a TPU backend")

    rng = np.random.default_rng(0)
    # unique sorted in-range ids (the A/B isolates the scatter itself; the
    # embed path's OOB-sentinel handling is a separate call-site concern —
    # see module docstring)
    ids = np.sort(rng.choice(v, size=k, replace=False)).astype(np.int32)
    table = jnp.zeros((v, d), jnp.float32)
    upd = jnp.asarray(rng.normal(0, 1, (k, d)).astype(np.float32))
    idx = jnp.asarray(ids)

    @jax.jit
    def xla(t, i, u):
        return t.at[i].add(u, unique_indices=True, indices_are_sorted=True)

    pallas_fn = jax.jit(scatter_add_rows)

    spread = lambda w: round((max(w) - min(w)) / min(w) * 100, 1) if min(w) else 0.0

    def timed(fn):
        # convergence and the reported number both use the TRAILING
        # ``repeats`` windows: cumulative min-to-max spread can only grow
        # as windows are added, so checking the full list could never
        # converge in exactly the noisy case this exists for — a settling
        # tail (drained host) is what a clean number means
        t = fn(table, idx, upd)  # warmup/compile
        float(jax.device_get(t[0, 0]))  # sync
        windows = []
        while len(windows) < max_repeats:
            t0 = time.perf_counter()
            for _ in range(iters):
                t = fn(t, idx, upd)  # chained: output feeds the next call
            float(jax.device_get(t[0, 0]))
            windows.append((time.perf_counter() - t0) / iters)
            if (len(windows) >= repeats
                    and spread(windows[-repeats:]) <= spread_target_pct):
                break
        tail = windows[-repeats:]
        return float(np.median(tail)), tail, windows

    t_xla, tail_xla, w_xla = timed(xla)
    t_pl, tail_pl, w_pl = timed(pallas_fn)
    return {
        "rows": k, "vocab": v, "dim": d,
        "iters_per_window": iters,
        "windows_run": {"xla": len(w_xla), "pallas": len(w_pl)},
        "tail_windows_reported": repeats,
        "spread_target_pct": spread_target_pct,
        "spread_met": (spread(tail_xla) <= spread_target_pct
                       and spread(tail_pl) <= spread_target_pct),
        "xla_ns_per_row": round(t_xla / k * 1e9, 1),
        "xla_spread_pct": spread(tail_xla),
        "pallas_ns_per_row": round(t_pl / k * 1e9, 1),
        "pallas_spread_pct": spread(tail_pl),
        "winner": "pallas" if t_pl < t_xla else "xla",
    }
