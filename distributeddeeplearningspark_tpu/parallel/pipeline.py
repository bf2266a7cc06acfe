"""Pipeline parallelism — GPipe-style microbatched stages over the ``pipe`` axis.

The reference has no pipeline parallelism (SURVEY.md §2 marks PP "unknown —
no evidence"; no config needs it), so this is a forward-looking primitive, not
a port: homogeneous-stage pipelining in the style GSPMD cannot express on its
own, built the TPU way — ``shard_map`` over the ``pipe`` mesh axis with
``lax.ppermute`` stage-to-stage handoffs (point-to-point on ICI) and a
``lax.scan`` over pipeline ticks.

Model fit: scanned-transformer layers are already stacked [L, ...]
(models/llama.py ``nn.scan``); grouping L layers into P stages of L/P layers
makes ``stage_params`` exactly a reshape of that stack — no model rewrite.

Schedule: classic GPipe. M microbatches flow through P stages in M + P - 1
ticks (bubble fraction (P-1)/(M+P-1)); each tick every stage runs one
microbatch and hands its activation to the next stage. Backward is plain
autodiff through the scan (activations rematerialized per-tick under
``jax.checkpoint`` if the caller wraps ``stage_fn`` — models/llama_pp.py
does, via ``cfg.remat``).

Why GPipe-with-remat and not hand-interleaved 1F1B: 1F1B's advantage over
GPipe is holding P (not M) microbatch activations live. Under XLA, remat
already bounds the scan's saved state to the per-tick boundary activations
(O(M + P) boundary tensors, recompute inside stages), and a hand-written
interleaved forward/backward schedule would require a custom VJP that
fights — instead of rides — XLA's scheduler and rematerialization. The
compiler-friendly scan keeps the bubble identical ((P-1)/(M+P-1)); raise M
to amortize it.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distributeddeeplearningspark_tpu.parallel.mesh import AXIS_PIPE, BATCH_AXES

StageFn = Callable[[Any, jax.Array], jax.Array]


def _pipeline_local(stage_params: Any, x_mb: jax.Array, *, stage_fn: StageFn,
                    num_stages: int, num_microbatches: int) -> jax.Array:
    """Per-device body (inside shard_map): run my stage for M + P - 1 ticks.

    ``stage_params``: this stage's params (leading stage axis already sliced
    to size 1 by shard_map). ``x_mb``: [M, mb, ...] microbatched input
    (replicated across stages; only stage 0 reads it).
    """
    idx = lax.axis_index(AXIS_PIPE)
    m, p = num_microbatches, num_stages
    params = jax.tree.map(lambda a: a[0], stage_params)
    mb_shape = x_mb.shape[1:]
    # send activations forward: stage i → i+1 (last wraps to 0, ignored there)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (zeros once input is exhausted —
        # those ticks only flush the tail of the pipeline)
        mb_idx = jnp.minimum(t, m - 1)
        mb = lax.dynamic_index_in_dim(x_mb, mb_idx, axis=0, keepdims=False)
        mb = jnp.where(t < m, mb, jnp.zeros_like(mb))
        inp = jnp.where(idx == 0, mb, state)
        out = stage_fn(params, inp)
        # last stage banks finished microbatch t - (P - 1)
        done_idx = jnp.clip(t - (p - 1), 0, m - 1)
        take = jnp.logical_and(idx == p - 1, t >= p - 1)
        current = lax.dynamic_index_in_dim(outputs, done_idx, axis=0, keepdims=False)
        banked = jnp.where(take, out, current)
        outputs = lax.dynamic_update_index_in_dim(outputs, banked, done_idx, axis=0)
        state = lax.ppermute(out, AXIS_PIPE, perm)
        return (state, outputs), None

    init = (
        jnp.zeros(mb_shape, x_mb.dtype),
        jnp.zeros((m,) + mb_shape, x_mb.dtype),
    )
    (_, outputs), _ = lax.scan(tick, init, jnp.arange(m + p - 1))
    # outputs are valid on the last stage only; broadcast them to every stage
    # so the result is replicated over `pipe` (psum of one-hot contribution)
    outputs = jnp.where(idx == p - 1, outputs, jnp.zeros_like(outputs))
    return lax.psum(outputs, AXIS_PIPE)


def pipeline(
    stage_fn: StageFn,
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
) -> jax.Array:
    """Run ``x`` through P pipeline stages; returns the final activations.

    ``stage_fn(params_one_stage, activation) -> activation`` must preserve the
    activation shape (transformer-block shaped). ``stage_params`` is a pytree
    whose leaves have a leading stage axis of size P = mesh.shape['pipe'].
    ``x`` is the global batch [B, ...]; B must divide by ``num_microbatches``.

    Composes with data parallelism: on a data×pipe mesh the microbatch rows
    stay sharded over (data, fsdp) inside the shard_map — the ring only spans
    ``pipe``. (The [B] → [M, B/M] reshape regroups rows across data shards,
    so GSPMD inserts one input all-to-all per step; activations inside the
    pipeline never leave their data shard.)

    Differentiable end-to-end (ppermute/scan are); params stay sharded over
    ``pipe`` so each device stores only its stage — PP is also a param-memory
    partitioning, like the reference's FSDP but along depth.
    """
    p = mesh.shape[AXIS_PIPE]
    leading = {leaf.shape[0] for leaf in jax.tree.leaves(stage_params)}
    if leading != {p}:
        raise ValueError(
            f"stage_params leading axes {sorted(leading)} must all equal "
            f"pipe degree {p}")
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} must divide by microbatches {num_microbatches}")
    x_mb = x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])

    act_spec = P(None, BATCH_AXES)  # [M, mb, ...]: rows sharded, rest replicated
    fn = jax.shard_map(
        functools.partial(
            _pipeline_local, stage_fn=stage_fn, num_stages=p,
            num_microbatches=num_microbatches,
        ),
        mesh=mesh,
        in_specs=(P(AXIS_PIPE), act_spec),
        out_specs=act_spec,
        check_vma=False,
    )
    out_mb = fn(stage_params, x_mb)
    return out_mb.reshape((b,) + x.shape[1:])


def stack_stages(layer_params: Any, num_stages: int) -> Any:
    """[L, ...]-stacked layer params → [P, L/P, ...] stage-stacked params.

    The bridge from ``nn.scan``-stacked transformer layers to pipeline
    stages; use a ``stage_fn`` that scans its L/P layers internally.
    """
    def regroup(a):
        l = a.shape[0]
        if l % num_stages:
            raise ValueError(f"{l} layers not divisible into {num_stages} stages")
        return a.reshape((num_stages, l // num_stages) + a.shape[1:])

    return jax.tree.map(regroup, layer_params)
