"""Collective ops: the NCCL/Horovod surface, rebuilt on XLA collectives.

The reference's only native layer is NCCL ring all-reduce reached through
``hvd.allreduce`` / ``dist.all_reduce`` (SURVEY.md §2 "Gradient aggregation",
§5 "Distributed communication backend"). On TPU those calls do not translate
one-to-one: XLA *is* the collective runtime, scheduling ``psum`` /
``all_gather`` / ``reduce_scatter`` / ``all_to_all`` over ICI links at compile
time. Two styles are provided:

- **Implicit (preferred)**: don't call anything — jit a step whose batch is
  sharded over (data, fsdp) and whose params are replicated; GSPMD inserts the
  gradient all-reduce. This is the production path used by
  :mod:`..train.step`.
- **Explicit**: the functions below, valid inside ``shard_map``/``pmap``
  bodies, mirroring the Horovod verb set for code that wants manual control
  (and for tests that pin down collective semantics).

Also here: ``tree_aggregate`` — a driver-side reduction that reproduces the
reference's *round-synchronous* Spark path (``rdd.mapPartitions`` →
``treeAggregate`` → driver update, SURVEY.md §3.1) for CPU parity tests, and
the cross-replica desync sanitizer from SURVEY.md §5.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from distributeddeeplearningspark_tpu import telemetry
from distributeddeeplearningspark_tpu.parallel.mesh import BATCH_AXES

AxisNames = str | Sequence[str]


# --- opt-in comms probes -----------------------------------------------------
#
# A hung collective is the canonical silent SPMD failure: every host blocks,
# nobody crashes, the step log just stops. These probes put the waiting on
# the record: `collective` telemetry events carrying per-call host-side wait
# time, which telemetry.fleet folds into the per-host comms-wait column. Off
# by default (zero cost); enabled via enable_collective_probes() or
# DLS_COMMS_PROBE=1 (how a supervisor-launched gang opts its workers in).

#: Env toggle for the comms probes (any value but ""/"0" enables).
COMMS_PROBE_ENV = "DLS_COMMS_PROBE"

_probe_override: bool | None = None


def enable_collective_probes(enabled: bool = True) -> None:
    """Force the probes on/off for this process (wins over the env var)."""
    global _probe_override
    _probe_override = enabled


def collective_probes_enabled() -> bool:
    if _probe_override is not None:
        return _probe_override
    return os.environ.get(COMMS_PROBE_ENV, "") not in ("", "0")


def _is_tracing() -> bool:
    """True whenever ANY trace is being built (jit, grad, shard_map, ...) —
    checked globally, not by sniffing the operands: a concrete constant
    captured inside a jit trace would pass a per-leaf Tracer check and emit
    one bogus trace-time event that looks like an execution-time wait."""
    return not jax.core.trace_ctx.is_top_level()


def _probed(op: str, fn: Callable) -> Callable:
    """Wrap an explicit-mode collective with the opt-in wait-time probe.

    Only concrete EAGER calls are timed — dispatch through completion
    (``block_until_ready``), emitted as a ``collective`` event. Under any
    active trace the wrapper is a transparent no-op: XLA schedules the op
    at compile time and there is no per-call host wait to measure. Since
    the named-axis verbs are today only legal inside shard_map/pmap bodies
    (always traced), the live comms-wait signal is :func:`barrier_probe`;
    these wrappers exist so any future eager-collective call site is
    covered without another instrumentation pass.
    """

    @functools.wraps(fn)
    def wrapper(tree: Any, axis: AxisNames = BATCH_AXES, **kw: Any) -> Any:
        if not collective_probes_enabled() or _is_tracing():
            return fn(tree, axis, **kw)
        t0 = time.perf_counter()
        out = fn(tree, axis, **kw)
        jax.block_until_ready(out)
        axis_label = axis if isinstance(axis, str) else ",".join(axis)
        telemetry.emit("collective", op=op, axis=axis_label,
                       wait_s=time.perf_counter() - t0)
        return out

    return wrapper


def transfer_probe(op: str, nbytes: int, wall_s: float,
                   **fields: Any) -> None:
    """Report one explicit bulk transfer (the live-reshard engine's
    schedule, a handoff ingest) as a ``collective`` event when probes are
    on. These moves run eagerly host-side, so unlike the named-axis verbs
    there is no trace-time ambiguity — the caller hands us the measured
    wall directly."""
    if not collective_probes_enabled():
        return
    telemetry.emit("collective", op=op, bytes=int(nbytes),
                   wait_s=float(wall_s), **fields)


_barrier_fns: dict = {}


def barrier_probe(mesh, *, tag: str = "barrier") -> float:
    """Time one full-mesh scalar psum from dispatch to completion.

    The cheapest honest measure of "how long does this host wait for the
    gang": a replicated scalar psum cannot return before every device has
    joined, so its host-side latency IS the barrier wait — in a straggling
    gang the fast hosts' samples grow by exactly the straggler's lag. The
    first call per mesh compiles (untimed — warm-up, not wait); each later
    call emits a ``collective`` event (``op=tag``) through the process-wide
    telemetry writer and returns the wait in seconds. Costs one tiny
    dispatch, so calling it once per metrics lap is noise.
    """
    fn = _barrier_fns.get(mesh)
    names = tuple(mesh.axis_names)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        body = jax.shard_map(lambda x: lax.psum(x, names), mesh=mesh,
                         in_specs=P(), out_specs=P())
        fn = jax.jit(body)
        jax.block_until_ready(fn(jnp.zeros((), jnp.float32)))  # compile
        _barrier_fns[mesh] = fn
    t0 = time.perf_counter()
    jax.block_until_ready(fn(jnp.ones((), jnp.float32)))
    wait = time.perf_counter() - t0
    telemetry.emit("collective", op=tag, axis=",".join(names), wait_s=wait)
    return wait


def all_reduce_sum(tree: Any, axis: AxisNames = BATCH_AXES) -> Any:
    """Horovod ``allreduce(op=Sum)`` ≙ ``lax.psum`` over the mesh axis."""
    return jax.tree.map(lambda x: lax.psum(x, axis), tree)


def all_reduce_mean(tree: Any, axis: AxisNames = BATCH_AXES) -> Any:
    """Horovod's default ``allreduce`` (average) ≙ ``lax.pmean``."""
    return jax.tree.map(lambda x: lax.pmean(x, axis), tree)


def all_gather(tree: Any, axis: AxisNames = BATCH_AXES, *, tiled: bool = True) -> Any:
    """``hvd.allgather`` ≙ ``lax.all_gather`` (tiled: concat along dim 0)."""
    return jax.tree.map(lambda x: lax.all_gather(x, axis, tiled=tiled), tree)


def reduce_scatter(tree: Any, axis: AxisNames = BATCH_AXES, *, scatter_dim: int = 0) -> Any:
    """ZeRO grad sync: ``lax.psum_scatter`` (each shard owns a slice of the sum)."""
    return jax.tree.map(
        lambda x: lax.psum_scatter(x, axis, scatter_dimension=scatter_dim, tiled=True),
        tree,
    )


# opt-in wait-time probes around the Horovod verb set (no-ops unless
# enabled, transparent under tracing — see _probed)
all_reduce_sum = _probed("all_reduce_sum", all_reduce_sum)
all_reduce_mean = _probed("all_reduce_mean", all_reduce_mean)
all_gather = _probed("all_gather", all_gather)
reduce_scatter = _probed("reduce_scatter", reduce_scatter)


def all_to_all(x: jax.Array, axis: str, *, split_dim: int, concat_dim: int) -> jax.Array:
    """``all_to_all`` — the sharded-embedding-lookup exchange (DLRM, config 4)."""
    return lax.all_to_all(x, axis, split_axis=split_dim, concat_axis=concat_dim, tiled=True)


def broadcast_from(tree: Any, axis: AxisNames = BATCH_AXES, *, root: int = 0) -> Any:
    """Driver parameter broadcast ≙ select root's copy on every member.

    Inside SPMD code replication normally makes this a no-op; it exists for
    explicit-mode parity with ``sc.broadcast`` semantics (e.g. re-syncing after
    a deliberately divergent step in the desync tests).
    """
    names = (axis,) if isinstance(axis, str) else tuple(axis)

    def bcast(x):
        y = x
        for name in names:
            y = lax.all_gather(y, name, tiled=False)[root]
        return y

    return jax.tree.map(bcast, tree)


def ppermute_shift(x: jax.Array, axis: str, *, shift: int = 1) -> jax.Array:
    """Ring shift over a mesh axis — the building block of ring attention."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


# --- driver-side (round-synchronous Spark) parity path ----------------------


def tree_aggregate(
    partitions: Sequence[Sequence[Any]],
    zero: Any,
    seq_op: Callable[[Any, Any], Any],
    comb_op: Callable[[Any, Any], Any],
) -> Any:
    """Spark ``RDD.treeAggregate`` semantics on the driver.

    ``partitions`` is a sequence of element sequences. Each partition is
    folded from a fresh copy of ``zero`` with ``seq_op`` (the executor-side
    fold); per-partition results are then combined with ``comb_op`` (the
    driver-side merge). Tree depth only changes scheduling, not the result, so
    the combine is flat. The reference's PR1 pure-CPU path (BASELINE.json
    config 1) aggregates per-partition gradients this way (SURVEY.md §3.1);
    tests use it to assert the SPMD ``psum`` step computes the *same numbers*
    as the round-synchronous Spark loop.
    """
    import copy

    per_part = []
    for part in partitions:
        acc = copy.deepcopy(zero)
        for x in part:
            acc = seq_op(acc, x)
        per_part.append(acc)
    if not per_part:
        return zero
    return functools.reduce(comb_op, per_part)


def grad_average(partition_grads: Sequence[Any]) -> Any:
    """Average per-partition gradient pytrees on the driver (parity mode).

    float32 numpy leaves accumulate through the native (C++) ``sum_into``
    kernel — the host equivalent of the reference's driver-side gradient
    reduction, parallel and GIL-free; other leaves fall back to Python sum.
    """
    import numpy as np

    from distributeddeeplearningspark_tpu.utils import native

    n = len(partition_grads)

    def avg(*xs):
        if all(isinstance(x, np.ndarray) and x.dtype == np.float32 for x in xs):
            acc = np.ascontiguousarray(xs[0]).copy()
            for x in xs[1:]:
                native.sum_into(acc, x)
            return acc / n
        return sum(xs) / n

    return jax.tree.map(avg, *partition_grads)


# The desync sanitizer lives in utils/sanitize.py (one API for both the
# local-device and cross-process checks); re-exported for callers that think
# of it as a collective-layer concern.
from distributeddeeplearningspark_tpu.utils.sanitize import (  # noqa: E402
    assert_replicas_in_sync,  # noqa: F401
)
