"""TPU-native distributed deep-learning framework with a Spark-shaped user model.

This package re-implements the capabilities of the reference
``chenhuims/DistributedDeepLearningSpark`` (a Spark-orchestrated, Horovod/NCCL
data-parallel trainer — see SURVEY.md; the reference mount was empty when this
was built, so parity is against the capability contract in BASELINE.json) as a
from-scratch TPU-first design:

- The Spark driver/executor *user model* is kept: a ``Session`` with a
  ``builder`` (SparkSession lifecycle), ``parallelize`` producing lazy
  partitioned datasets (RDD-shaped), executor-count knobs, and a
  ``dlsubmit`` CLI shaped like ``spark-submit``.
- The *engine* is SPMD JAX: one ``jax.jit``-compiled train step under GSPMD
  sharding replaces the per-partition forward/backward/optimizer closure;
  ``jax.lax.psum`` over the ICI/DCN device mesh replaces NCCL all-reduce;
  replicated sharding replaces driver parameter broadcast; a device-side
  prefetch iterator streams partitions into HBM.

Public API (stable surface):

    Session, PartitionedDataset, MeshSpec, Trainer, TrainState
"""

import time

#: the first line the package runs: where ``telemetry.anatomy.STARTUP``, the
#: ledger of the process's start, is anchored
_T_IMPORT = time.perf_counter()

import importlib  # noqa: E402
from typing import TYPE_CHECKING  # noqa: E402

__version__ = "0.1.0"

#: public name -> defining submodule. Resolved lazily (PEP 562) so that
#: importing a light submodule (``telemetry``, ``status`` — what the
#: ``dlstatus`` CLI does, possibly on a box without jax while inspecting a
#: copied-out run directory) does not drag in the whole jax/flax/orbax
#: training stack through this package __init__.
_EXPORTS = {
    "Session": "distributeddeeplearningspark_tpu.session",
    "PartitionedDataset": "distributeddeeplearningspark_tpu.rdd",
    "MeshSpec": "distributeddeeplearningspark_tpu.parallel.mesh",
    "TrainState": "distributeddeeplearningspark_tpu.train.state",
    "Trainer": "distributeddeeplearningspark_tpu.train.trainer",
    "Checkpointer": "distributeddeeplearningspark_tpu.checkpoint",
}

if TYPE_CHECKING:  # static analyzers see the real names
    from distributeddeeplearningspark_tpu.checkpoint import Checkpointer
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
    from distributeddeeplearningspark_tpu.rdd import PartitionedDataset
    from distributeddeeplearningspark_tpu.session import Session
    from distributeddeeplearningspark_tpu.train.state import TrainState
    from distributeddeeplearningspark_tpu.train.trainer import Trainer


def __getattr__(name: str):
    if name in _EXPORTS:
        # both jax-free; the lazy imports below pull in jax, flax, optax, orbax
        from distributeddeeplearningspark_tpu.telemetry import anatomy, spans

        with spans.span("dls.start/import", anatomy.STARTUP.sink()):
            module = importlib.import_module(_EXPORTS[name])
        value = getattr(module, name)
        globals()[name] = value  # cache: next access skips the import
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))


__all__ = [
    "Session",
    "PartitionedDataset",
    "MeshSpec",
    "TrainState",
    "Trainer",
    "Checkpointer",
    "__version__",
]
