"""Session lifecycle — the SparkSession surface over a JAX device mesh.

The reference's user lifecycle (SURVEY.md §1 L6, §3.1) is::

    spark = SparkSession.builder.master("local[2]").appName("mnist").getOrCreate()
    rdd = spark.sparkContext.parallelize(data, numSlices=2)
    ... train ...
    spark.stop()

BASELINE.json's north star requires that this lifecycle "stay unchanged", so
the same builder API is kept verbatim — but ``getOrCreate`` provisions a
:class:`jax.sharding.Mesh` (and, on multi-host TPU pods, runs
``jax.distributed.initialize``) instead of spawning JVM executors. The
"executor count" maps to the number of data shards of the mesh.

Master URL forms:

- ``local[N]``  — N-way data parallelism over the first N local devices
  (the reference's 2-local-executor PR1 config is ``local[2]``);
- ``local[*]`` / ``local`` — all local devices, pure DP;
- ``auto`` — all devices of whatever platform jax found, with a mesh shaped
  by ``MeshSpec`` conf keys (see below); on a multi-host pod, call
  :func:`Session.initialize_distributed` first (done automatically when the
  standard TPU pod env vars are present);
- ``tpu`` — like ``auto``, but a ``ValueError`` unless the platform really is
  a TPU (jax falls back to the host CPU with only a warning when it finds no
  accelerator; a ``tpu`` session must not train there quietly).

Recognized ``.config()`` keys (Spark names kept where they exist):

- ``spark.executor.instances``  → data-parallel degree (mesh ``data`` axis)
- ``spark.app.name``            → app name
- ``mesh.data`` / ``mesh.fsdp`` / ``mesh.pipe`` / ``mesh.tensor`` /
  ``mesh.seq`` / ``mesh.expert`` → mesh axis sizes (one may be -1 = wildcard;
                                ``spark.executor.instances`` overrides ``mesh.data``)

The persistent XLA compilation cache is placed from outside, by
``JAX_COMPILATION_CACHE_DIR`` (:func:`..utils.env.configure_compile_cache`);
there is no conf key for it.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from typing import Any, Iterable, Sequence

import jax

from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec, num_data_shards
from distributeddeeplearningspark_tpu.rdd import PartitionedDataset
from distributeddeeplearningspark_tpu.telemetry import anatomy, spans

logger = logging.getLogger("distributeddeeplearningspark_tpu")

_LOCK = threading.Lock()


class Session:
    """An active training session bound to a device mesh.

    Construct via ``Session.builder`` (SparkSession-style); direct
    construction is for tests.
    """

    _active: "Session | None" = None

    def __init__(self, app_name: str, conf: dict[str, str], mesh, spec: MeshSpec):
        self.app_name = app_name
        self.conf = dict(conf)
        self.mesh = mesh
        self.spec = spec
        self._stopped = False

    # -- SparkSession-shaped surface ----------------------------------------

    class Builder:
        def __init__(self) -> None:
            self._conf: dict[str, str] = {}

        def appName(self, name: str) -> "Session.Builder":
            self._conf["spark.app.name"] = name
            return self

        def master(self, master: str) -> "Session.Builder":
            self._conf["spark.master"] = master
            return self

        def config(self, key: str | None = None, value: Any = None, *, map: dict | None = None) -> "Session.Builder":
            if map is not None:
                self._conf.update({k: str(v) for k, v in map.items()})
            if key is not None:
                self._conf[key] = str(value)
            return self

        # snake_case aliases for non-Spark users
        app_name = appName

        def getOrCreate(self) -> "Session":
            from distributeddeeplearningspark_tpu.cli import conf_from_env

            with _LOCK:
                if Session._active is not None and not Session._active._stopped:
                    Session._active.conf.update(self._conf)
                    return Session._active
                # dlsubmit launch flags arrive via env and lose to explicit
                # .config()/.master() calls in the driver script.
                conf = {**conf_from_env(), **self._conf}
                with spans.span("dls.start/session", anatomy.STARTUP.sink()):
                    sess = _create_session(conf)
                Session._active = sess
                return sess

        get_or_create = getOrCreate

    # ``Session.builder`` must yield a fresh Builder per access, like pyspark.
    class _BuilderDescriptor:
        def __get__(self, obj, objtype=None) -> "Session.Builder":
            return Session.Builder()

    builder = _BuilderDescriptor()

    @classmethod
    def active(cls) -> "Session":
        if cls._active is None or cls._active._stopped:
            raise RuntimeError("no active Session; use Session.builder.getOrCreate()")
        return cls._active

    @classmethod
    def get_or_default(cls) -> "Session":
        """Active session, or a default all-device DP session."""
        if cls._active is not None and not cls._active._stopped:
            return cls._active
        return cls.Builder().getOrCreate()

    # -- data plane ---------------------------------------------------------

    @property
    def sparkContext(self) -> "Session":
        """The reference reaches ``parallelize`` via ``spark.sparkContext``;
        session and context are one object here, so this returns ``self``."""
        return self

    spark_context = sparkContext

    def parallelize(self, data: Sequence | Iterable, numSlices: int | None = None) -> PartitionedDataset:
        n = numSlices if numSlices is not None else self.default_parallelism
        return PartitionedDataset.parallelize(data, n)

    def range(self, n: int, numSlices: int | None = None) -> PartitionedDataset:
        return self.parallelize(range(n), numSlices)

    @property
    def read(self):
        """``spark.read`` — the DataFrame reader surface (config 4's
        feature-engineering entry point): ``spark.read.option("sep", "\\t")
        .schema([...]).csv(path)``."""
        from .data.dataframe import DataFrameReader

        return DataFrameReader(default_parallelism=self.default_parallelism)

    def createDataFrame(self, rows, numSlices: int | None = None):
        """Columnarize driver-side rows into a :class:`DataFrame`."""
        from .data.dataframe import from_rows

        n = numSlices if numSlices is not None else self.default_parallelism
        return from_rows(rows, num_partitions=n)

    create_dataframe = createDataFrame

    @property
    def default_parallelism(self) -> int:
        return num_data_shards(self.mesh)

    defaultParallelism = default_parallelism

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    # -- lifecycle ----------------------------------------------------------

    def stop(self) -> None:
        self._stopped = True
        if Session._active is self:
            Session._active = None

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def __repr__(self) -> str:
        return (
            f"Session(app={self.app_name!r}, devices={self.num_devices}, "
            f"mesh={dict(self.mesh.shape)})"
        )

    # -- multi-host ---------------------------------------------------------

    _distributed_initialized = False

    @classmethod
    def initialize_distributed(
        cls,
        coordinator_address: str | None = None,
        num_processes: int | None = None,
        process_id: int | None = None,
    ) -> None:
        """Join the multi-host coordination service (Spark driver↔executor RPC
        control plane ≙ jax.distributed's coordinator; SURVEY.md §5)."""
        if cls._distributed_initialized:
            return
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        cls._distributed_initialized = True
        atexit.register(jax.distributed.shutdown)


def _local_n(master: str | None) -> int | None:
    """N from 'local[N]' master URLs; None for wildcard/other forms."""
    if master and master.startswith("local[") and master.endswith("]"):
        inner = master[len("local["):-1]
        if inner.isdigit():
            return int(inner)
    return None


def _parse_master(master: str | None, conf: dict[str, str]) -> tuple[list[jax.Device] | None, MeshSpec]:
    """Resolve a master URL + conf into (device subset, MeshSpec)."""
    fsdp = int(conf.get("mesh.fsdp", 1))
    pipe = int(conf.get("mesh.pipe", 1))
    tensor = int(conf.get("mesh.tensor", 1))
    seq = int(conf.get("mesh.seq", 1))
    expert = int(conf.get("mesh.expert", 1))
    executors = conf.get("spark.executor.instances")

    devices: list[jax.Device] | None = None
    data: int = -1

    if master is None or master in ("auto", "local[*]", "local"):
        pass
    elif master == "tpu":
        platform = jax.devices()[0].platform
        if platform != "tpu":
            raise ValueError(
                f"master 'tpu' but jax found no TPU: the platform is "
                f"{platform!r} (JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS', '')!r}) — use 'auto' or "
                f"'local[N]' for a host rehearsal")
    elif _local_n(master) is not None:
        n = _local_n(master)
        # a -1 (wildcard) axis contributes ×1 here: local[N] then means "N
        # workers total", and the wildcard axis absorbs them in MeshSpec
        n_dev = (n * max(fsdp, 1) * max(pipe, 1) * max(tensor, 1)
                 * max(seq, 1) * max(expert, 1))
        all_dev = jax.devices()
        if n_dev > len(all_dev):
            raise ValueError(
                f"master {master!r} needs {n_dev} devices, only {len(all_dev)} available"
            )
        devices = all_dev[:n_dev]
        data = n
    else:
        raise ValueError(f"unrecognized master URL: {master!r}")

    if "mesh.data" in conf:
        # explicit data-axis size; lets another axis (e.g. mesh.fsdp=-1) be
        # the wildcard for FSDP-dominant layouts like config 5
        data = int(conf["mesh.data"])
    if executors is not None:
        data = int(executors)
        if devices is None:
            n_dev = (data * max(fsdp, 1) * max(pipe, 1) * max(tensor, 1)
                     * max(seq, 1) * max(expert, 1))
            all_dev = jax.devices()
            if n_dev > len(all_dev):
                raise ValueError(
                    f"spark.executor.instances={data} needs {n_dev} devices, "
                    f"only {len(all_dev)} available"
                )
            devices = all_dev[:n_dev]

    spec = MeshSpec(data=data, fsdp=fsdp, pipe=pipe, tensor=tensor, seq=seq, expert=expert)
    return devices, spec


def _create_session(conf: dict[str, str]) -> Session:
    from distributeddeeplearningspark_tpu.utils.env import (
        configure_compile_cache,
        ensure_cpu_devices,
    )

    ensure_cpu_devices(_local_n(conf.get("spark.master")))
    configure_compile_cache()
    # Auto-join a pod if the driver environment provides coordination info.
    if os.environ.get("DLS_COORDINATOR") and not Session._distributed_initialized:
        Session.initialize_distributed(
            coordinator_address=os.environ["DLS_COORDINATOR"],
            num_processes=int(os.environ.get("DLS_NUM_PROCESSES", "1")),
            process_id=int(os.environ.get("DLS_PROCESS_ID", "0")),
        )
    # the session's first look at the devices, ahead of `_parse_master` and
    # `MeshSpec.build`, which ask again: the runtime comes up here
    with spans.span("dls.start/backend", anatomy.STARTUP.sink()):
        jax.devices()
    master = conf.get("spark.master")
    devices, spec = _parse_master(master, conf)
    mesh = spec.build(devices)
    app = conf.get("spark.app.name", "dls-tpu")
    sess = Session(app, conf, mesh, spec)
    logger.info("session %s: mesh %s over %d %s device(s)", app, dict(mesh.shape),
                mesh.devices.size, mesh.devices.flat[0].platform)
    return sess
