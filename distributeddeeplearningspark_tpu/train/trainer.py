"""Trainer — the driver-side loop that replaces Spark's round orchestration.

The reference driver (SURVEY.md §3.1) loops: broadcast params → dispatch
``mapPartitions(train_fn)`` tasks → aggregate grads → update. Here the loop
body is one async-dispatched jitted SPMD step; the Python loop's only jobs are
feeding prefetched sharded batches, periodic metrics, and checkpoint hooks.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
from typing import Any, Callable, Iterator, Sequence

import jax
import optax

from distributeddeeplearningspark_tpu.data.feed import (
    host_batches,
    process_shard_range,
    put_global,
    stack_examples,
)
from distributeddeeplearningspark_tpu.data.prefetch import (
    StarvationProbe,
    prefetch_to_device,
)
from distributeddeeplearningspark_tpu import faults
from distributeddeeplearningspark_tpu import telemetry as telemetry_lib
from distributeddeeplearningspark_tpu.telemetry import anatomy as anatomy_lib
from distributeddeeplearningspark_tpu.telemetry import spans
from distributeddeeplearningspark_tpu.metrics import (
    Meter,
    MetricLogger,
    compiled_flops_per_step,
)
from distributeddeeplearningspark_tpu.parallel import collectives
from distributeddeeplearningspark_tpu.parallel import plan as plan_lib
from distributeddeeplearningspark_tpu.parallel.mesh import num_data_shards
from distributeddeeplearningspark_tpu.parallel.sharding import REPLICATED, ShardingRules
from distributeddeeplearningspark_tpu.rdd import PartitionedDataset
from distributeddeeplearningspark_tpu.session import Session
from distributeddeeplearningspark_tpu.train import step as step_lib
from distributeddeeplearningspark_tpu.train.state import TrainState
from distributeddeeplearningspark_tpu.utils import profiling, sanitize

logger = logging.getLogger("distributeddeeplearningspark_tpu.trainer")


def _touch_heartbeat() -> None:
    """Stamp the supervisor's liveness file (DLS_HEARTBEAT_FILE, set by
    :class:`~..supervisor.Supervisor`): progress between checkpoints is then
    visible to the hang watchdog, so a long checkpoint_every doesn't read as
    a hung gang (and a spinning-but-stuck worker genuinely stops stamping)."""
    path = os.environ.get("DLS_HEARTBEAT_FILE")
    if not path:
        return
    try:
        with open(path, "w") as f:
            f.write(str(os.getpid()))
    except OSError:  # heartbeats are best-effort, never fail training
        pass


class Trainer:
    """Bind (session, model, loss, optimizer, sharding rules) into a train loop.

    ``model`` is a flax Module whose ``__call__(batch, *, train)`` returns the
    outputs consumed by ``loss_fn(outputs, batch) → (loss, metrics)``.
    """

    def __init__(
        self,
        session: Session | None,
        model,
        loss_fn: Callable,
        optimizer: optax.GradientTransformation,
        *,
        rules: ShardingRules = REPLICATED,
        plan: "plan_lib.Plan | None" = None,
        mutable_keys: Sequence[str] = (),
        rng_names: Sequence[str] = ("dropout",),
        seed: int = 0,
        checkpointer=None,
        context_parallel: bool = False,
        accum_steps: int = 1,
        pipeline_microbatches: int | None = None,
        sparse_embed: Sequence[Any] = (),
        trainable: Callable[[str], bool] | None = None,
    ):
        self.session = session or Session.get_or_default()
        self.mesh = self.session.mesh
        self.model = model
        self.loss_fn = loss_fn
        # every trainer compiles through ONE Plan (parallel/plan.py): an
        # explicit `plan=` wins (a sweep winner pinned via Plan.load, a
        # ZeRO layout, a composed ulysses×fsdp); otherwise the legacy
        # (rules, context_parallel) knobs are wrapped into an equivalent
        # plan so the unified compile path serves both call styles
        if plan is not None:
            if plan.style != "jit":
                # Trainer's step bodies are GSPMD-style (no explicit
                # collective calls — the grad all-reduce is inserted by
                # the partitioner). Wrapping them in shard_map would
                # silently skip the gradient reduction: each shard would
                # train on its own rows. shard_map plans are for bodies
                # built on the explicit collectives verbs.
                raise plan_lib.PlanValidationError(
                    f"Trainer requires a style='jit' plan; plan "
                    f"{plan.name!r} has style={plan.style!r} (shard_map "
                    f"plans need step bodies with explicit collectives — "
                    f"compile those via compile_step_with_plan directly)")
            self.plan = plan
            rules = plan.rules
            context_parallel = context_parallel or plan.seq_sharded
            if plan.model_hints:
                # the plan layer cannot rebuild the caller's model — a
                # pinned sweep winner measured WITH these hints applied
                # (e.g. attention_impl=ulysses), so silently training
                # without them would not reproduce the ranked number
                logger.warning(
                    "plan %r carries model hints %s: apply them to the "
                    "model config yourself (e.g. dataclasses.replace(cfg, "
                    "...)) — the sweep measured with them in effect",
                    plan.name, plan.hints())
        else:
            self.plan = plan_lib.plan_for_rules(
                rules, context_parallel=context_parallel)
        # typed spec validation up front: a bad pinned plan fails HERE
        # with PlanValidationError, not as an opaque jax error deep in
        # init_state (tensor>1 meshes warn per the ROADMAP skew guard)
        self.plan.validate(self.mesh)
        self.sparse_embed = tuple(sparse_embed)
        if self.sparse_embed and accum_steps != 1:
            raise ValueError("accum_steps is not supported with sparse_embed")
        if self.sparse_embed and trainable is not None:
            raise ValueError(
                "trainable is not supported with sparse_embed: the sparse "
                "step already keeps tables out of autodiff, and silently "
                "ignoring the predicate for other params would skip the "
                "frozen-weight exclusion the caller asked for")
        if self.sparse_embed:
            # tables train through the row-sparse path (train/embed.py); the
            # main optimizer must be masked off them or its dense "no-op"
            # updates re-introduce the full-table traffic
            from distributeddeeplearningspark_tpu.train import optim
            from distributeddeeplearningspark_tpu.train.embed import dense_trainable

            optimizer = optim.masked(optimizer, dense_trainable(self.sparse_embed))
        # the unwrapped (post-masking) optimizer is kept so apply_plan can
        # re-wrap it under a NEW plan's ZeRO layout without asking the
        # caller to re-thread it
        self._optimizer = optimizer
        # ZeRO plans pin the gradient layout replicated inside tx.update
        # (bitwise parity with the replicated optimizer — see
        # Plan.wrap_optimizer); a no-op for plans without zero_axes
        self.tx = self.plan.wrap_optimizer(optimizer, self.mesh)
        self.rules = rules
        self.mutable_keys = tuple(mutable_keys)
        self.rng_names = tuple(rng_names)
        self.seed = seed
        self.checkpointer = checkpointer
        # context parallelism: shard batch dim 1 (sequence) over the mesh
        # `seq` axis; pair with a model whose attention_impl is "ring"
        self.context_parallel = context_parallel
        self.accum_steps = accum_steps
        self.pipeline_microbatches = pipeline_microbatches
        # path predicate for partial training (LoRA): frozen params are
        # stop_gradient'ed out of autodiff — pass the SAME predicate used
        # to mask the optimizer (step.py `trainable` docstring)
        self.trainable = trainable

        self.state: TrainState | None = None
        self.state_shardings = None
        self._train_step = None
        self._eval_step = None
        self._predict_step = None
        # device-side skip guard (fit(on_nonfinite="skip")) — set before
        # init() builds the jitted step, or fit() rebuilds it on change
        self._guard_nonfinite = False
        # step at which a graceful preemption drain ended fit() early (the
        # worker script keys its exit path off this — a drained run must
        # not write DONE or a final checkpoint)
        self.preempted_at: int | None = None

    # -- setup --------------------------------------------------------------

    def init(self, sample_batch: dict[str, Any]) -> TrainState:
        """Initialize sharded state from one host example batch."""
        with spans.span("dls.start/init_state", anatomy_lib.STARTUP.sink()):
            self.state, self.state_shardings = step_lib.init_state(
                self.model, self.tx, sample_batch, self.mesh, self.rules,
                seed=self.seed, sparse_embed=self.sparse_embed, plan=self.plan,
            )
        if self.mutable_keys == () and self.state.mutable:
            self.mutable_keys = tuple(self.state.mutable.keys())
        self._build_train_step()
        self._build_aux_steps()
        logger.info("initialized %s params over mesh %s",
                    f"{self.state.num_params:,}", dict(self.mesh.shape))
        return self.state

    def _build_aux_steps(self) -> None:
        """(Re)compile the eval/predict steps against the CURRENT
        (shardings, plan) — shared by init() and apply_plan()."""
        ev = step_lib.make_eval_step(self._apply_fn(), self.loss_fn)
        self._eval_step = step_lib.jit_eval_step(
            ev, self.mesh, self.state_shardings,
            seq_sharded=self.context_parallel, plan=self.plan,
        )
        self._predict_step = step_lib.jit_predict_step(
            step_lib.make_predict_step(self._apply_fn()),
            self.mesh, self.state_shardings,
        )

    def _build_train_step(self) -> None:
        """(Re)compile the jitted train step from the current trainer config
        — the ONE place the (accum_steps, guard_nonfinite, trainable, ...)
        knobs meet make_train_step, shared by init() and fit()'s rebuilds."""
        if self.sparse_embed:
            from distributeddeeplearningspark_tpu.train.embed import (
                make_sparse_embed_train_step,
            )

            train = make_sparse_embed_train_step(
                self._apply_fn(), self.tx, self.loss_fn, self.sparse_embed,
                rng_names=self.rng_names,
            )
        else:
            train = step_lib.make_train_step(
                self._apply_fn(), self.tx, self.loss_fn,
                mutable_keys=self.mutable_keys, rng_names=self.rng_names,
                accum_steps=self.accum_steps, trainable=self.trainable,
                guard_nonfinite=self._guard_nonfinite,
            )
        # ONE compile path for every strategy (parallel/plan.py): the plan
        # centralizes donation + spec validation, and the compile ledger
        # owns the lower→compile path — every executable this step ever
        # builds becomes a timed, cost-analyzed `compile` telemetry event
        # TAGGED with the plan's name/signature, and a second signature
        # through a shape-stable train step (expected_signatures=1) flags
        # as a recompile (docs/OBSERVABILITY.md "Device anatomy")
        self._train_step = plan_lib.compile_step_with_plan(
            train, self.plan, self.mesh,
            state_shardings=self.state_shardings,
            kind="train", name="train_step",
        )

    def _apply_fn(self):
        """The forward used by train/eval steps — the model's own apply, or
        its pipeline-parallel variant when the mesh has a ``pipe`` axis > 1.

        (A plain-function dispatch, NOT a Module method: flax wraps module
        methods in scope machinery that breaks standalone submodule
        construction inside them.)"""
        if self.mesh.shape.get("pipe", 1) <= 1:
            return self.model.apply
        from distributeddeeplearningspark_tpu.models.llama import LlamaForCausalLM

        if isinstance(self.model, LlamaForCausalLM):
            from distributeddeeplearningspark_tpu.models.llama_pp import make_pp_apply

            return make_pp_apply(self.model.cfg, self.mesh,
                                 self.pipeline_microbatches)
        raise NotImplementedError(
            f"mesh has pipe={self.mesh.shape['pipe']} but "
            f"{type(self.model).__name__} has no pipeline-parallel forward — "
            f"use a pipe=1 mesh or a pipeline-capable model (Llama)")

    def load_pretrained(self, params, *, batch_stats=None, strict: bool = False,
                        allow_uncovered: Sequence[str] = ("lora_",)) -> TrainState:
        """Overlay imported weights (e.g. a HF Llama safetensors tree) on state.

        The rebuild of the reference's "load base checkpoint, then attach
        adapters" flow: leaves present in ``params`` replace the fresh-init
        values. Staging stays host-side (numpy) until ``device_put`` with the
        state's sharding, so each chip receives only its FSDP/TP slice and no
        device ever holds a full unsharded tensor. Leaves absent from
        ``params`` keep their initialized values; with ``strict``, both extra
        overlay keys and model params NOT covered by the overlay (except paths
        matching ``allow_uncovered``, by default LoRA adapters) raise.
        """
        assert self.state is not None, "call init() before load_pretrained()"
        import re

        import numpy as np

        from distributeddeeplearningspark_tpu.parallel.sharding import path_str

        flat_new = {path_str(p): x for p, x in
                    jax.tree_util.tree_flatten_with_path(params)[0]}
        seen = set()

        def overlay(path, current, sharding):
            key = path_str(path)
            if key in flat_new:
                seen.add(key)
                new = flat_new[key]
                if tuple(new.shape) != tuple(current.shape):
                    raise ValueError(
                        f"pretrained {key}: shape {new.shape} != model {current.shape}")
                return jax.device_put(np.asarray(new, current.dtype), sharding)
            return current

        new_params = jax.tree_util.tree_map_with_path(
            overlay, self.state.params, self.state_shardings.params)
        extra = set(flat_new) - seen
        model_keys = {path_str(p) for p, _ in
                      jax.tree_util.tree_flatten_with_path(self.state.params)[0]}
        uncovered = {k for k in model_keys - seen
                     if not any(re.search(pat, k) for pat in allow_uncovered)}
        if strict and (extra or uncovered):
            raise ValueError(
                f"pretrained overlay mismatch: extra keys {sorted(extra)[:4]}, "
                f"uncovered model params {sorted(uncovered)[:4]}")
        if extra:
            logger.warning("ignored %d pretrained keys not in model", len(extra))
        if uncovered:
            logger.warning("%d model params not covered by pretrained overlay "
                           "(e.g. %s)", len(uncovered), sorted(uncovered)[:3])
        self.state = self.state.replace(params=new_params)
        if batch_stats is not None:
            # pretrained running statistics (e.g. a torchvision ResNet's BN
            # means/vars — resnet_io returns them alongside the params)
            cur = self.state.mutable.get("batch_stats")
            if cur is None:
                raise ValueError(
                    "batch_stats given but the model has no batch_stats "
                    "collection")
            stats_sh = self.state_shardings.mutable["batch_stats"]

            def place(path, current, sharding):
                node = batch_stats
                try:
                    for p in path:
                        node = node[getattr(p, "key", getattr(p, "idx", None))]
                except (KeyError, TypeError):
                    return current
                if tuple(np.shape(node)) != tuple(current.shape):
                    raise ValueError(
                        f"batch_stats {path_str(path)}: shape "
                        f"{np.shape(node)} != model {current.shape}")
                return jax.device_put(np.asarray(node, current.dtype), sharding)

            new_stats = jax.tree_util.tree_map_with_path(place, cur, stats_sh)
            self.state = self.state.replace(
                mutable={**self.state.mutable, "batch_stats": new_stats})
        return self.state

    def restore(self, checkpointer=None, *, step: int | None = None):
        """Restore (state, data_state) from a checkpoint onto THIS mesh.

        The reference resumes by driver-side ``torch.load`` + re-broadcast
        (SURVEY.md §3.4); here restore reshards: the checkpoint may have been
        written on any topology, and each chip reads only its slice as
        dictated by this trainer's shardings. Call after ``init()``.
        """
        ckpt = checkpointer or self.checkpointer
        # bind the run's telemetry before the restore so checkpoint.py's
        # restore/verify phase spans land in the event stream even when
        # restore() is called ahead of fit() (the resume path) — resolved
        # against THIS restore's checkpointer, which may be the explicit
        # argument rather than the constructor's
        self._telemetry(ckpt)
        # real exceptions, not asserts: restore is the recovery path, and a
        # python -O relaunch silently skipping these guards would turn a
        # wiring mistake into an undiagnosable crash deep inside orbax
        if ckpt is None:
            raise RuntimeError(
                "Trainer.restore: no checkpointer configured — pass one to "
                "the constructor or to restore()")
        if self.state is None:
            raise RuntimeError(
                "Trainer.restore: state is uninitialized — call init() "
                "(with a sample batch) before restore()")
        self.state, data_state = ckpt.restore(
            self.state, step=step, shardings=self.state_shardings
        )
        logger.info("resumed at step %d", int(jax.device_get(self.state.step)))
        return self.state, data_state

    def restore_live_handoff(self, checkpointer=None):
        """Resume from a graceful drain's live handoff — the CURRENT step,
        not the last checkpoint (no walk-back).

        Ingests the digest-verified raw blocks a draining gang left beside
        the checkpoints (:func:`..parallel.live_reshard.save_handoff`)
        directly onto THIS trainer's shardings, consumes the handoff, and
        returns ``(state, data_state)`` exactly like :meth:`restore`.
        Raises :class:`..parallel.live_reshard.HandoffError` on any
        digest/structure mismatch — the caller falls back to the
        checkpoint. Call after ``init()``.
        """
        import time

        from distributeddeeplearningspark_tpu.parallel import live_reshard

        ckpt = checkpointer or self.checkpointer
        self._telemetry(ckpt)
        if ckpt is None:
            raise RuntimeError(
                "Trainer.restore_live_handoff: no checkpointer configured — "
                "the handoff lives in its directory")
        if self.state is None:
            raise RuntimeError(
                "Trainer.restore_live_handoff: state is uninitialized — "
                "call init() (with a sample batch) before restoring")
        t0 = time.perf_counter()
        self.state, manifest = live_reshard.load_handoff(
            ckpt.directory, self.state, self.state_shardings)
        step = int(manifest["step"])
        stats = live_reshard.TransferStats(
            leaves=len(manifest["leaves"]),
            leaves_moved=len(manifest["leaves"]),
            bytes_moved=sum(int(x.nbytes) for x in
                            jax.tree_util.tree_leaves(self.state)),
            mem_budget_bytes=live_reshard.memory_budget_bytes(),
            wall_s=time.perf_counter() - t0, verified=True)
        stats.bytes_total = stats.bytes_moved
        live_reshard.emit_reshard_event(
            stats, step=step, transport="handoff", walk_back=False,
            reason="preemption-resume")
        live_reshard.clear_handoff(ckpt.directory)
        logger.info("resumed from live handoff at step %d (checkpoint-free, "
                    "no walk-back)", step)
        return self.state, manifest.get("data_state")

    def apply_plan(self, plan: "plan_lib.Plan", *,
                   verify: bool = True):
        """Apply a plan (e.g. a serialized ``plan_sweep`` winner) LIVE
        between steps — no restart, no checkpoint round-trip.

        The state is re-projected onto the new plan's shardings by the
        bounded live-reshard engine (:mod:`..parallel.live_reshard`,
        blake2b-verified when ``verify``), the optimizer re-wrapped under
        the new plan's ZeRO layout, and train/eval/predict recompiled
        through the same ``compile_step_with_plan`` path ``init()`` uses —
        so the trajectory thereafter is bitwise identical to a restart
        pinned to the same plan. Returns the engine's
        :class:`~..parallel.live_reshard.TransferStats`.
        """
        if self.state is None:
            raise RuntimeError("init() the trainer before apply_plan() — "
                               "there is no live state to re-project yet")
        if plan.style != "jit":
            raise plan_lib.PlanValidationError(
                f"Trainer requires a style='jit' plan; plan {plan.name!r} "
                f"has style={plan.style!r} (shard_map plans need step "
                f"bodies with explicit collectives — compile those via "
                f"compile_step_with_plan directly)")
        plan.validate(self.mesh)
        if plan.model_hints:
            logger.warning(
                "plan %r carries model hints %s: apply_plan cannot rebuild "
                "the model — the live trajectory only matches the sweep's "
                "ranked number if the model was built with them",
                plan.name, plan.hints())
        from distributeddeeplearningspark_tpu import checkpoint as ckpt_lib
        from distributeddeeplearningspark_tpu.parallel import live_reshard

        old = self.plan
        targets = plan.state_shardings(ckpt_lib.abstract_like(self.state),
                                       self.mesh)
        self.state, stats = live_reshard.redistribute(
            self.state, targets, verify=verify)
        self.state_shardings = targets
        self.plan = plan
        self.rules = plan.rules
        self.tx = plan.wrap_optimizer(self._optimizer, self.mesh)
        self._build_train_step()
        self._build_aux_steps()
        self._telemetry()
        live_reshard.emit_reshard_event(
            stats, step=int(jax.device_get(self.state.step)),
            transport="collectives", walk_back=False, reason="apply-plan",
            from_plan=old.name, to_plan=plan.name,
            from_signature=old.signature(), to_signature=plan.signature())
        logger.info(
            "applied plan %r live (was %r): moved %d/%d leaves, %.1f MiB in "
            "%d bounded round(s), %.3fs — steps recompiled, no restart",
            plan.name, old.name, stats.leaves_moved, stats.leaves,
            stats.bytes_moved / 2**20, stats.rounds, stats.wall_s)
        return stats

    def _graceful_drain(self, step: int, *, examples_seen: int,
                        batch_size: int, doomed: int | None = None) -> None:
        """Honor a preemption notice (``DLS_FAULT=sigterm@N``, or a
        scheduler-delivered runtime notice naming ``doomed``): the
        in-flight step is drained, the doomed host's live shards are
        re-gathered onto the survivors-hold-everything layout (every leaf
        replicated) by the bounded engine, the state is committed as a
        digest-verified live handoff beside the checkpoints, and the DRAIN
        evidence file is written LAST so the supervisor only ever sees
        evidence backed by an ingestible handoff. Hard kills (die_host)
        never reach here — they still walk back through the checkpoint."""
        from jax.sharding import NamedSharding, PartitionSpec

        from distributeddeeplearningspark_tpu import supervisor as sup_lib
        from distributeddeeplearningspark_tpu.parallel import live_reshard

        if self.checkpointer is None:
            raise RuntimeError(
                "graceful preemption drain needs a checkpointer: its "
                "directory carries the live handoff the shrunk gang "
                "resumes from")
        doomed = faults.fault_host() if doomed is None else doomed
        jax.block_until_ready(self.state.params)  # drain the in-flight step
        targets = jax.tree.map(
            lambda _: NamedSharding(self.mesh, PartitionSpec()),
            self.state_shardings)
        self.state, stats = live_reshard.redistribute(self.state, targets)
        self.state_shardings = targets
        live_reshard.emit_reshard_event(
            stats, step=step, transport="collectives", walk_back=False,
            reason="preemption-drain", dead_host=doomed)
        live_reshard.save_handoff(
            self.checkpointer.directory, step, self.state,
            data_state={"examples_seen": examples_seen,
                        "batch_size": batch_size},
            stats=stats)
        sup_lib.write_drain_evidence(
            self.checkpointer.directory, host=doomed, step=step)
        self.preempted_at = step
        logger.warning(
            "graceful drain at step %d: host %d preempted — live handoff "
            "committed (%d leaves, %.1f MiB gathered in %d round(s)); "
            "exiting clean for the supervisor to shrink without walk-back",
            step, doomed, stats.leaves, stats.bytes_moved / 2**20,
            stats.rounds)

    def _telemetry(self, checkpointer=None) -> "telemetry_lib.EventWriter | None":
        """The run's event writer, or None when no workdir is resolvable.

        Workdir resolution: ``DLS_TELEMETRY_DIR`` (exported by the
        supervisor so the gang and its overseer share one stream) wins;
        otherwise the checkpointer directory (``checkpointer`` argument
        first — restore() may be handed one explicitly — then the
        constructor's) serves as the run's workdir — the place an operator
        already points recovery tooling at. Binds the process-wide writer
        so writer-less layers (checkpoint.py, profiling.py) emit into the
        same stream.
        """
        workdir = os.environ.get(telemetry_lib.WORKDIR_ENV)
        ckpt = checkpointer or self.checkpointer
        if not workdir and ckpt is not None:
            workdir = getattr(ckpt, "directory", None)
        if not workdir:
            return None
        return telemetry_lib.configure(workdir)

    def _feed(self, dataset: PartitionedDataset, batch_size: int, *,
              skip_batches: int = 0, probe: StarvationProbe | None = None):
        nshards = num_data_shards(self.mesh)
        # Multi-process: each host stacks only its own devices' rows (its
        # "executor partitions"); put_global assembles the global batch.
        hb = host_batches(dataset, batch_size, num_shards=nshards,
                          shard_range=process_shard_range(nshards))
        if skip_batches:
            # Resume fast-forward: burn host batches (no device transfer) so a
            # deterministic pipeline continues from where the checkpoint left
            # off — the analogue of Spark resuming at a partition boundary.
            import itertools

            hb = itertools.islice(hb, skip_batches, None)
        put = functools.partial(put_global, seq_sharded=self.context_parallel)
        return prefetch_to_device(hb, self.mesh, put=put, probe=probe)

    # -- training -----------------------------------------------------------

    def fit(
        self,
        dataset: PartitionedDataset,
        *,
        batch_size: int,
        steps: int | None = None,
        epochs: int | None = None,
        tokens_per_example: int = 0,
        log_every: int = 10,
        checkpoint_every: int | None = None,
        eval_dataset: PartitionedDataset | None = None,
        eval_every: int | None = None,
        callbacks: Sequence[Callable[[int, dict], None]] = (),
        data_state: dict | None = None,
        sanitize_every: int | None = None,
        profile: "profiling.ProfileSpec | None" = None,
        measure_flops: bool = False,
        tensorboard_dir: str | None = None,
        accum_steps: int | None = None,
        on_nonfinite: str = "raise",
        nonfinite_budget: int = 10,
        max_rollbacks: int = 2,
    ) -> tuple[TrainState, dict[str, float]]:
        """Train until ``steps`` (or dataset exhaustion × ``epochs``).

        ``accum_steps``: gradient-accumulation micro-steps per optimizer step
        (``batch_size`` stays the GLOBAL batch; it is split into this many
        micro-batches inside the jitted step). Overrides the constructor value.

        ``on_nonfinite`` — the divergence-recovery policy for NaN/Inf losses:

        - ``"raise"`` (default): fail fast at the next log boundary — the
          historical ``assert_all_finite`` behavior.
        - ``"skip"``: the jitted step itself withholds the optimizer update
          on non-finite gradients (params/opt-state/mutables keep their
          previous values; the poisoned batch is consumed) — a transient
          NaN spike costs one batch, not the gang. At most
          ``nonfinite_budget`` steps may be skipped before the run fails
          (persistent divergence must not masquerade as progress). The
          summary reports ``skipped_steps``.
        - ``"rollback"``: on a non-finite loss at a log boundary, reload the
          newest *verified* checkpoint and keep consuming the data stream
          from the current position — the model rewinds, the feed does not,
          so the poisonous batch window is fast-forwarded past. Requires a
          ``checkpointer`` with at least one saved step; bounded by
          ``max_rollbacks``. The summary reports ``rollbacks``.

        Recovery events surface through :class:`~..metrics.MetricLogger`
        WARNING lines (and ``recovery/*`` TensorBoard scalars).

        Returns (final state, summary metrics). The loop never blocks on the
        device except at metric log points — steps dispatch asynchronously.
        """
        # the process's start ends where this fit's first lap closes
        # (telemetry/anatomy.StartupLedger). `dls.start/fit` holds every
        # section up to there; it is left by hand at that boundary, and by
        # this `with` if fit ends or raises sooner
        start = anatomy_lib.STARTUP.sink()
        with contextlib.ExitStack() as start_fit:
            if start is not None:
                start_fit.enter_context(spans.span("dls.start/fit", start))
            if on_nonfinite not in ("raise", "skip", "rollback"):
                raise ValueError(
                    f"on_nonfinite must be 'raise'|'skip'|'rollback', got "
                    f"{on_nonfinite!r}")
            if on_nonfinite == "skip" and self.sparse_embed:
                raise ValueError(
                    "on_nonfinite='skip' is not supported with sparse_embed "
                    "tables (the row-sparse step has no update guard); use "
                    "'rollback' or 'raise'")
            rebuild = False
            need_guard = on_nonfinite == "skip"
            if need_guard != self._guard_nonfinite:
                self._guard_nonfinite = need_guard
                rebuild = True
            if accum_steps is not None and accum_steps != self.accum_steps:
                if self.sparse_embed:
                    raise ValueError(
                        "accum_steps is not supported with sparse_embed tables "
                        "(train/embed.py) — recommender batches are already large; "
                        "scale batch_size instead")
                self.accum_steps = accum_steps
                rebuild = True
            if rebuild and self.state is not None:
                # recompile once with the settled (guard, accum) combination
                self._build_train_step()
            if self.state is None:
                with spans.span("dls.start/sample", start):
                    sample = self._sample_batch(dataset, batch_size)
                self.init(sample)
            assert self._train_step is not None
            if batch_size % self.accum_steps:
                raise ValueError(
                    f"batch_size {batch_size} must divide by accum_steps "
                    f"{self.accum_steps}")

            if epochs is not None:
                dataset = dataset.repeat(epochs)

            meter = Meter(
                examples_per_step=batch_size,
                tokens_per_step=batch_size * tokens_per_example,
                num_chips=self.mesh.devices.size,
            )
            # run telemetry: per-lap step_metrics + phase spans + heartbeats into
            # the workdir's JSONL stream (docs/OBSERVABILITY.md). None when no
            # workdir is resolvable — then fit costs nothing extra.
            tele = self._telemetry()
            probe = StarvationProbe() if tele is not None else None
            # per-lap device/host/input anatomy (docs/OBSERVABILITY.md "Device
            # anatomy"): the instrumented step adds each dispatch and compile
            # to it, every `spans.span(name, anat)` below adds its section, and
            # the closed lap's split rides the step_metrics record. Telemetry
            # off: no accumulator, and each span is a bare TraceAnnotation.
            anat = anatomy_lib.StepAnatomy() if tele is not None else None
            if isinstance(self._train_step, anatomy_lib.InstrumentedFunction):
                self._train_step.attach_anatomy(anat)
            step_compiles = getattr(self._train_step, "records", [])
            compiled_before = len(step_compiles)
            attempt = int(os.environ.get("DLS_RESTART", "0") or 0)

            def tele_phase(name: str):
                return (tele.phase(name) if tele is not None
                        else spans.span(spans.PHASE_PREFIX + name))

            mlog = MetricLogger(log_every=log_every, tensorboard_dir=tensorboard_dir,
                                telemetry=tele)
            step_i = int(jax.device_get(self.state.step))
            if tele is not None:
                tele.emit("phase", name="run", edge="begin", step=step_i,
                          attempt=attempt)
                # baseline heartbeat BEFORE the first (long) compile: a host
                # that stalls during startup is then localizable by heartbeat
                # age, not only by its phase-begin record
                tele.heartbeat(step=step_i)
            # opt-in gang-barrier latency sample per metrics lap (a replicated
            # scalar psum timed host-side): in a straggling gang every healthy
            # host's sample grows by the straggler's lag, which is the fleet
            # table's comms-wait column (DLS_COMMS_PROBE=1, docs/OBSERVABILITY)
            comms_probe = (tele is not None
                           and collectives.collective_probes_enabled())
            # trace window is relative to THIS loop's first step, and stop must
            # sync on the live state or async dispatch truncates the capture
            profiler = profiling.StepProfiler(
                profile, start_offset=step_i,
                sync=lambda: jax.block_until_ready(self.state.params),
            )
            flops_pending = measure_flops
            meter.start()
            if anat is not None:
                # start the anatomy lap clock at the SAME instant as the meter:
                # the two walls are measured independently and must agree
                anat.reset()

            lap_start = step_i
            last_metrics: dict[str, float] = {}
            skip = 0
            if data_state and data_state.get("examples_seen"):
                stored_bs = data_state.get("batch_size")
                if stored_bs is not None and int(stored_bs) != batch_size:
                    raise ValueError(
                        f"resume batch_size mismatch: checkpoint was written with "
                        f"batch_size={int(stored_bs)}, fit() called with "
                        f"{batch_size} — the examples_seen fast-forward would "
                        f"land mid-batch; resume with the original batch size")
                skip = int(data_state["examples_seen"]) // batch_size
            got_batch = False
            # fallback gate for drivers not launched through the test workers
            # (those already died pre-rendezvous): on a relaunch, a die_host
            # target must not train — the machine it stands in for is gone
            faults.die_if_dead_host_on_relaunch()
            fault = faults.get()
            # the graceful-preemption notice is scoped out of get(): every rank
            # consults it (the trainer coordinates the drain no matter which
            # host is doomed — survivors are the ones re-gathering shards)
            preempt = faults.sigterm_fault()
            # the scheduler's runtime notice channel: a file path in the env
            # (scheduler-launched jobs only — unset keeps the poll at zero
            # cost). Polled at step boundaries; the notice's step floor is how
            # every rank lands on the same drain step despite observing the
            # file at slightly different wall-clock times.
            notice_path = faults.preempt_notice_path()
            skipped_dev = None  # device-side cumulative skip count (stays async)
            n_skipped = 0
            rollbacks = 0
            # extra batches the feed consumed beyond step_i (rollback rewinds the
            # model, never the stream) — folded into examples_seen so a resume
            # fast-forwards to the TRUE stream position, not step_i's. A resumed
            # run inherits the previous run's offset (skip beyond state.step IS
            # that drift) so re-checkpointing doesn't quietly drop it.
            rolled_back_batches = max(0, skip - step_i)
            try:
                for batch in self._feed(dataset, batch_size, skip_batches=skip,
                                        probe=probe):
                    got_batch = True
                    if steps is not None and step_i >= steps:
                        break
                    if flops_pending:
                        # lower+compile for cost analysis blocks like the first
                        # step's compile does — same goodput category
                        with tele_phase("compile"):
                            meter.set_flops(self.compiled_cost(batch))
                        flops_pending = False
                    if fault is not None and step_i + 1 == fault.step \
                            and fault.kind in ("nan", "crash", "hang", "die_host"):
                        kind = fault.kind
                        # one-shot: a rollback rewinds step_i past the trigger,
                        # and re-poisoning the retrained window would turn one
                        # injected spike into an unrecoverable loop
                        fault = None
                        if kind == "nan":
                            batch = faults.nan_batch(batch)
                        elif kind in ("crash", "die_host"):
                            faults.crash()
                        else:
                            faults.hang()
                    profiler.observe(step_i)
                    # the step marker is always written: the profiler may be the
                    # caller's (utils/profiling.trace, a benchmark), not
                    # `profile=`'s
                    with profiling.step_annotation(step_i):
                        # compiles (the first dispatch AND any mid-run shape
                        # change) are spanned, timed, and cost-analyzed by the
                        # instrumented step itself (telemetry/anatomy.py), so
                        # no first-dispatch phase wrap is needed here
                        self.state, metrics = self._train_step(self.state, batch)
                    metrics = dict(metrics)
                    metrics.pop("weight", None)  # eval-aggregation detail, not a log line
                    step_i += 1
                    if self._guard_nonfinite and "skipped" in metrics:
                        # eager device-side add per step — no host sync; fetched
                        # only at log boundaries
                        s = metrics["skipped"]
                        skipped_dev = s if skipped_dev is None else skipped_dev + s
                    if step_i % log_every == 0 or (steps is not None and step_i >= steps):
                        if (meter.flops_per_step is None
                                and getattr(self._train_step, "flops_per_step",
                                            None)):
                            # the ledger already cost-analyzed the compiled step,
                            # so MFU comes free — no measure_flops double compile
                            meter.set_flops(self._train_step.flops_per_step)
                        # device_get blocks until this step's metrics exist, so the
                        # lap boundary is a true device-sync point — timing is honest.
                        with spans.span("dls.fit/sync", anat):
                            fetched = jax.device_get(metrics)
                        last_metrics = meter.lap(step_i - lap_start, fetched)
                        lap_start = step_i
                        # close the anatomy lap at the SAME sync point the
                        # meter lapped at — the log rendering below belongs to
                        # the next lap on both clocks, or the two walls drift
                        lap_s, lap_n = meter.last_lap or (0.0, 0)
                        lap_close = anat.now() if anat is not None else None
                        if start is not None:
                            # the process's first lap has closed, and its start
                            start_fit.close()
                            start_close = start.clock()
                        # opened after the boundary and closed after lap(): the
                        # emits are the NEXT lap's on the anatomy's clock too
                        with spans.span("dls.fit/emit", anat):
                            snap = probe.snapshot() if probe is not None else {}
                            anat_rec: dict = {}
                            if anat is not None:
                                anat_rec = anat.lap(
                                    steps=lap_n,
                                    input_wait_s=snap.get("input_wait_s", 0.0),
                                    input_put_s=snap.get("input_put_s", 0.0),
                                    flops_per_step=getattr(
                                        self._train_step, "flops_per_step",
                                        None),
                                    num_chips=self.mesh.devices.size,
                                    now=lap_close,
                                )
                            if start is not None:
                                # (no writer: no lap record and no snapshot,
                                # and the lap's feed wait, dispatches and
                                # drain stay in `fit_unaccounted_s`)
                                startup = start.first_lap(
                                    steps=lap_n, lap=anat_rec, feed=snap,
                                    compiles=step_compiles[compiled_before:],
                                    attempt=attempt, now=start_close)
                                start = None
                                if tele is not None:
                                    # once a process, ahead of its first lap
                                    tele.emit("startup", **startup)
                            mlog.log(step_i, {**last_metrics, **meter.summary()})
                            _touch_heartbeat()
                            if tele is not None:
                                tele.step_metrics(
                                    step_i, steps=lap_n, lap_s=lap_s,
                                    metrics=last_metrics, **snap, **anat_rec)
                                tele.emit("memory",
                                          **anatomy_lib.memory_watermarks())
                                tele.heartbeat(step=step_i)
                                if comms_probe:
                                    collectives.barrier_probe(self.mesh)
                        if on_nonfinite == "raise":
                            sanitize.assert_all_finite(last_metrics, step=step_i)
                        elif on_nonfinite == "skip":
                            if skipped_dev is not None:
                                new_skipped = int(jax.device_get(skipped_dev))
                                if new_skipped > n_skipped:
                                    mlog.event(
                                        step_i, "skip",
                                        skipped_steps=new_skipped,
                                        nonfinite=sanitize.nonfinite_metrics(last_metrics))
                                n_skipped = new_skipped
                                if n_skipped > nonfinite_budget:
                                    raise FloatingPointError(
                                        f"skipped {n_skipped} non-finite steps, "
                                        f"over nonfinite_budget={nonfinite_budget} "
                                        f"— this divergence is persistent, not a "
                                        f"transient spike; last metrics: "
                                        f"{last_metrics}")
                        else:  # rollback
                            bad = sanitize.nonfinite_metrics(last_metrics)
                            if bad:
                                rollbacks += 1
                                if rollbacks > max_rollbacks:
                                    raise FloatingPointError(
                                        f"non-finite metrics at step {step_i} "
                                        f"after exhausting max_rollbacks="
                                        f"{max_rollbacks}: {bad}")
                                if self.checkpointer is None:
                                    raise FloatingPointError(
                                        f"on_nonfinite='rollback' needs a "
                                        f"checkpointer with a saved step; "
                                        f"non-finite at step {step_i}: {bad}")
                                try:
                                    last_bad = None
                                    while True:
                                        self.restore()
                                        if sanitize.tree_all_finite(
                                                self.state.params):
                                            break
                                        # byte-intact but numerically poisoned
                                        # (divergence was checkpointed before a
                                        # log boundary could see it): discard
                                        # and walk back further
                                        ckpt_step = int(
                                            jax.device_get(self.state.step))
                                        if ckpt_step == last_bad:
                                            # quarantine didn't take (read-only
                                            # fs, non-0 process): refuse to spin
                                            raise RuntimeError(
                                                f"could not quarantine poisoned "
                                                f"checkpoint step {ckpt_step}")
                                        last_bad = ckpt_step
                                        logger.warning(
                                            "rollback target step %d holds "
                                            "non-finite params; quarantining "
                                            "and walking back further",
                                            ckpt_step)
                                        self.checkpointer.quarantine(ckpt_step)
                                except Exception as e:
                                    raise FloatingPointError(
                                        f"rollback from non-finite metrics at "
                                        f"step {step_i} failed ({e}); bad "
                                        f"metrics: {bad}") from e
                                rolled_to = int(jax.device_get(self.state.step))
                                mlog.event(step_i, "rollback", to_step=rolled_to,
                                           window=step_i - rolled_to, nonfinite=bad)
                                rolled_back_batches += step_i - rolled_to
                                step_i = rolled_to
                                lap_start = step_i
                                last_metrics = {}
                                # the feed keeps streaming forward — the model
                                # rewound, the poisonous batch window did not
                                continue
                    if sanitize_every and step_i % sanitize_every == 0:
                        sanitize.assert_replicas_in_sync(self.state.params)
                    if callbacks:
                        # the callers' time (a benchmark's window, its
                        # start_trace / stop_trace), not the program's
                        with spans.span("dls.fit/callbacks", anat):
                            for cb in callbacks:
                                cb(step_i, last_metrics)
                    doomed_now: int | None = None
                    if preempt is not None and step_i >= preempt.step:
                        doomed_now = faults.fault_host()
                    elif notice_path is not None:
                        notice = faults.read_preempt_notice(notice_path)
                        if notice is not None and step_i >= notice.step:
                            doomed_now = notice.host
                    if doomed_now is not None:
                        # preemption notice: drain (the step above completed),
                        # hand off live state, exit BEFORE any further
                        # checkpoint write — the resume point is THIS step
                        self._graceful_drain(
                            step_i,
                            examples_seen=(step_i + rolled_back_batches)
                            * batch_size,
                            batch_size=batch_size, doomed=doomed_now)
                        break
                    if checkpoint_every and self.checkpointer and step_i % checkpoint_every == 0:
                        with spans.span("dls.fit/checkpoint", anat):
                            self.checkpointer.save(
                                step_i, self.state,
                                data_state={"examples_seen":
                                            (step_i + rolled_back_batches)
                                            * batch_size,
                                            "batch_size": batch_size},
                            )
                        if (fault is not None and fault.kind == "truncate_ckpt"
                                and step_i >= fault.step):
                            # kill-mid-finalize drill: make the save durable +
                            # manifested, tear its bytes, die without warning
                            self.checkpointer.wait()
                            faults.truncate_latest_checkpoint(
                                self.checkpointer.directory)
                            faults.crash()
                    if eval_every and eval_dataset is not None and step_i % eval_every == 0:
                        with spans.span("dls.fit/eval", anat), tele_phase("eval"):
                            emetrics = self.evaluate(eval_dataset, batch_size=batch_size)
                        mlog.log(step_i, {f"eval_{k}": v for k, v in emetrics.items()})
            finally:
                # flush the trace and tensorboard even when a step/sanitizer blows
                # up mid-window — a crashed run's trace is the one you want most
                profiler.stop()
                if isinstance(self._train_step, anatomy_lib.InstrumentedFunction):
                    # detach so a later fit() on this trainer gets a fresh lap
                    # accumulator, not this run's dangling one
                    self._train_step.attach_anatomy(None)
                if tele is not None:
                    # close the run span on every exit the interpreter survives;
                    # a SIGKILL'd run leaves the stream open-ended, which is the
                    # signal dlstatus reads as "died mid-run"
                    tele.emit("phase", name="run", edge="end", step=step_i)
                mlog.close()

            if skip and not got_batch:
                raise RuntimeError(
                    f"resume fast-forward consumed the whole dataset: skipping "
                    f"{skip} batches (examples_seen="
                    f"{int(data_state['examples_seen'])}) exhausted the feed "
                    f"before the first post-resume step — pass a .repeat() "
                    f"dataset or fewer epochs-already-trained")
            jax.block_until_ready(self.state.params)
            summary = {**meter.summary(), **last_metrics}
            if on_nonfinite == "skip":
                if skipped_dev is not None:
                    n_skipped = int(jax.device_get(skipped_dev))
                summary["skipped_steps"] = float(n_skipped)
                if n_skipped:
                    logger.warning("run skipped %d non-finite step(s) "
                                   "(on_nonfinite='skip')", n_skipped)
            elif on_nonfinite == "rollback":
                summary["rollbacks"] = float(rollbacks)
            if (self.checkpointer and checkpoint_every
                    and self.preempted_at is None):
                # a drained run already committed its live handoff; a final
                # checkpoint here would advance the walk-back point past the
                # handoff and muddy the "no walk-back" resume invariant
                self.checkpointer.save(
                    step_i, self.state,
                    data_state={"examples_seen":
                                (step_i + rolled_back_batches) * batch_size,
                                "batch_size": batch_size},
                )
                self.checkpointer.wait()
            # timing laps are closed — safe to wait for the async device-time
            # budget log so short jobs still surface it before returning
            profiler.join_breakdown()
            return self.state, summary

    def evaluate(self, dataset: PartitionedDataset, *, batch_size: int) -> dict[str, float]:
        """Weighted-mean metrics over the full dataset, tail batch included.

        The remainder batch is processed at its natural (smaller) size — one
        extra compile of the eval step, no silent under-count (VERDICT r1
        weak-#3) — and per-batch means are combined weighted by example count
        (or by the loss's own ``"weight"`` metric when it reports one, e.g.
        token-weighted LM losses), so the result equals a single full-dataset
        pass. A tail that cannot fill every data shard equally (< one row per
        shard, multi-process tails) is padded with ``eval_mask == 0`` rows
        that every contract loss downweights to exactly zero (VERDICT r3
        missing-#5) — no row is ever dropped, at any shard count.
        """
        assert self._eval_step is not None and self.state is not None
        nshards = num_data_shards(self.mesh)
        hb = host_batches(
            dataset, batch_size, num_shards=nshards, drop_remainder=False,
            shard_range=process_shard_range(nshards), pad_remainder=True,
        )
        put = functools.partial(put_global, seq_sharded=self.context_parallel)
        totals: dict[str, float] = {}
        wsum = 0.0
        for batch in prefetch_to_device(hb, self.mesh, put=put):
            rows = next(iter(batch.values())).shape[0]
            m = dict(jax.device_get(self._eval_step(self.state, batch)))
            if "eval_mask" in batch and "weight" not in m:
                raise RuntimeError(
                    "the loss ignored the padded tail's eval_mask (no "
                    "'weight' metric reported) — padding rows would "
                    "contaminate the mean. Weight per-row metrics by "
                    "batch['eval_mask'] and report weight=mask.sum() "
                    "(see train/losses.py _row_mask).")
            w = float(m.pop("weight", rows))
            for k, v in m.items():
                totals[k] = totals.get(k, 0.0) + float(v) * w
            wsum += w
        return {k: v / max(wsum, 1e-9) for k, v in totals.items()}

    def predict(
        self,
        dataset: PartitionedDataset,
        *,
        batch_size: int,
        output_fn: Callable[[Any], Any] | None = None,
        with_inputs: bool = False,
    ) -> Iterator[Any]:
        """Yield per-example model outputs over ``dataset`` (host numpy).

        The reference's inference path (SURVEY.md §3.3): params broadcast →
        ``rdd.mapPartitions(predict_fn)`` → collect. The jitted forward runs
        batch-sharded over the mesh; the tail batch is processed at its
        natural size (same GSPMD divisibility rule as :meth:`evaluate`).

        **Ordering:** rows stream in *feed order* — shard-interleaved
        (partition *i* → data shard ``i % num_shards``), which is NOT
        ``dataset.collect()`` order when there are multiple partitions. To
        attach predictions to their examples, pass ``with_inputs=True`` and
        receive ``(example, output)`` pairs — never zip against a separately
        iterated dataset.

        ``output_fn`` post-processes each device batch BEFORE the host fetch
        (e.g. ``lambda logits: jnp.argmax(logits, -1)`` to ship class ids,
        not [B, 1000] logit matrices). Multi-process: outputs replicate
        (all-gather) so every host yields the full global row stream —
        except with ``with_inputs``, where each host yields only the rows
        whose inputs it holds (its own data shards).
        """
        assert self._predict_step is not None and self.state is not None
        nshards = num_data_shards(self.mesh)
        srange = process_shard_range(nshards)
        hb = host_batches(
            dataset, batch_size, num_shards=nshards, drop_remainder=False,
            shard_range=srange,
        )
        put = functools.partial(put_global, mesh=self.mesh,
                                seq_sharded=self.context_parallel)
        for host_batch in hb:
            out = self._predict_step(self.state, put(host_batch))
            if output_fn is not None:
                out = output_fn(out)
            host = jax.device_get(out)
            leaves = jax.tree.leaves(host)
            rows = leaves[0].shape[0] if leaves else 0
            local_rows = next(iter(host_batch.values())).shape[0]
            # multi-process: the replicated output is GLOBAL; this host's
            # input rows sit at [lo, lo + local_rows) of it
            lo = 0 if srange is None else srange[0] * (rows // nshards)
            for r in range(rows):
                row_out = jax.tree.map(lambda a: a[r], host)
                if with_inputs:
                    if not (lo <= r < lo + local_rows):
                        continue
                    yield ({k: v[r - lo] for k, v in host_batch.items()},
                           row_out)
                else:
                    yield row_out

    def startup_summary(self) -> dict[str, Any] | None:
        """The process's ``startup`` record (:class:`~..telemetry.anatomy.
        StartupLedger`: the ``dls.start/*`` sections and the first lap's
        parts, in seconds, which sum to ``to_first_lap_s``), with or without
        a telemetry writer (without one that lap's feed wait, dispatches and
        drain are not split out of ``fit_unaccounted_s``); ``None`` until the
        first lap of the process's first ``fit`` has closed."""
        return anatomy_lib.STARTUP.summary()

    def compiled_cost(self, batch: dict[str, Any]) -> float | None:
        """FLOPs per step from XLA cost analysis (for MFU reporting).

        Routed through the compile ledger when the train step is
        instrumented: "get the FLOPs" and "warm the executable" are then
        ONE compile (the old path lower+compiled a throwaway twin of the
        program the first dispatch would compile again)."""
        assert self._train_step is not None and self.state is not None
        if isinstance(self._train_step, anatomy_lib.InstrumentedFunction):
            self._train_step.prepare(self.state, batch)
            if self._train_step.flops_per_step is not None:
                return self._train_step.flops_per_step
        lowered = self._train_step.lower(self.state, batch)
        return compiled_flops_per_step(lowered.compile())

    def _sample_batch(self, dataset: PartitionedDataset, batch_size: int):
        examples = dataset.take(max(2, min(batch_size, 8)))
        sample = stack_examples(examples)
        # init only needs shapes/dtypes; small batch keeps init cheap, but we
        # place it like a real batch so sharding propagation sees the layout.
        return sample
