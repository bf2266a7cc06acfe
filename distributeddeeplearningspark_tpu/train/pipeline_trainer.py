"""MPMD pipeline trainer — each stage an independent program on its own gang.

``models/llama_pp.py`` runs GPipe inside ONE program: every stage shares one
mesh, one failure domain, and one HBM pool. This module is the production
shape from PAPERS.md 2412.14374 (MPMD pipeline parallelism): stage *k* is a
separate OS process with its OWN mesh and strategy — a wide-fsdp gang for the
embedding-heavy first stage, a tensor-heavy gang for MLP-bound middle stages
— exchanging activations and gradients over the async authkey'd socket
transport of :mod:`..parallel.mpmd`, double-buffered so stage *k* computes
microbatch *i* while *i+1* is already in flight. Because stages never join a
collective, this also runs on jax builds whose CPU backend cannot do
cross-process collectives — the stage boundary is a socket, not a psum.

**Numerics.** Two compute modes per stage:

- ``mode="exact"`` (data/fsdp-row-sharded stages, ``shard_map``): grad
  reductions are kept as per-device *partials* ([D, ...] stacked) and
  summed ONCE at the optimizer step in the same association order as the
  single-program GPipe scan (per-device accumulate over microbatches in
  reverse order, then one cross-device sum), the first stage embeds the
  FULL batch once (one scatter-add backward, like the baseline), and the
  last stage computes the loss over the FULL concatenated logits with the
  baseline's exact expression — loss value and its backward in ONE
  program, which turned out to be load-bearing for parity, not just for
  speed: XLA fuses a grad-program's loss region differently from a
  forward-only one (measured ±2 f32 ulp on the same bits), so a separate
  loss-stats pass can never match the baseline's value_and_grad. With all
  of the above, a 2-stage MPMD run matches the single-program ``llama_pp``
  Trainer step **bitwise** — per-step losses AND updated params —
  pinned by tests/test_mpmd.py and asserted in CI by ``tools/ci.sh mpmd``.
  Requires ``loss_mode="full_batch"``.
- ``mode="sharded"`` (any per-stage mesh via :class:`..parallel.sharding
  .ShardingRules`): stage params/grads lay out by rules (fsdp, tensor, …)
  under GSPMD jit; grads reduce per microbatch and accumulate in arrival
  order — float-exact association is traded for per-stage layout freedom.

**Scheduling.** 1F1B: middle stages prefer a waiting gradient over the next
forward (backward-as-soon-as-possible), and with
``loss_mode="per_microbatch"`` the last stage backwards each microbatch
right after its forward, holding at most one activation; warmup/cooldown
give the textbook bubble (P−1)/(M+P−1), which the trace spans measure
(``dlstatus --traces`` pipeline block). ``loss_mode="full_batch"`` computes
loss after all M forwards (GPipe at the last stage) — the bitwise-parity
mode, same bubble bound.

**Recovery.** Each stage checkpoints its own shard of the model
(``<workdir>/stage<k>/ckpt``) through the ordinary :class:`..checkpoint
.Checkpointer` — including reshard-on-restore, so a stage can come back on
a DIFFERENT mesh. When a stage dies, its peers' transport raises a typed
error; they re-listen/re-dial (blocking on the transport) while the
:class:`..supervisor.PipelineSupervisor` restarts only the dead stage, then
all stages agree on the resume step (:meth:`..parallel.mpmd
.PipelineTransport.sync_step` — min over committed checkpoints), roll back
to it, and continue (docs/POD_PLAYBOOK.md "A pipeline stage died").
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import os
import time
from typing import Any, Callable

import numpy as np

from distributeddeeplearningspark_tpu import faults
from distributeddeeplearningspark_tpu import telemetry as telemetry_lib
from distributeddeeplearningspark_tpu.parallel import mpmd
from distributeddeeplearningspark_tpu.telemetry import trace as trace_lib

logger = logging.getLogger("distributeddeeplearningspark_tpu.pipeline")

#: span names the pipeline emits; telemetry/fleet.pipeline_anatomy folds
#: busy vs wait into the measured bubble fraction.
BUSY_SPANS = ("pipe-fwd", "pipe-bwd", "pipe-loss", "pipe-embed",
              "pipe-embed-bwd", "pipe-opt")
WAIT_SPANS = ("pipe-recv-wait", "pipe-send-wait")
STEP_SPAN = "pipe-step"


def theoretical_bubble(m: int, p: int) -> float:
    """The GPipe/1F1B pipeline-fill bound: (P−1)/(M+P−1)."""
    return (p - 1) / float(m + p - 1)


# -- per-stage Llama program --------------------------------------------------


class LlamaStageProgram:
    """The jitted compute owned by ONE pipeline stage of a Llama model.

    Stage 0 holds ``token_embed`` + its layer slice; the last stage holds
    its slice + ``final_norm`` + ``lm_head`` (and the loss). Parameter
    VALUES are the full model's own init (every stage runs the identical
    deterministic init and keeps its slice), so N stages reassemble to the
    exact single-program parameter tree.
    """

    def __init__(self, cfg, stage: int, num_stages: int, mesh, tx, *,
                 mode: str = "exact", loss_mode: str = "full_batch",
                 rules=None, plan=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributeddeeplearningspark_tpu.models.llama_pp import (
            build_stage_modules,
            check_pp_config,
        )
        from distributeddeeplearningspark_tpu.parallel import plan as plan_lib
        from distributeddeeplearningspark_tpu.parallel.mesh import BATCH_AXES

        if mode not in ("exact", "sharded"):
            raise ValueError(f"mode must be 'exact'|'sharded', got {mode!r}")
        if loss_mode not in ("full_batch", "per_microbatch"):
            raise ValueError(
                f"loss_mode must be 'full_batch'|'per_microbatch', got "
                f"{loss_mode!r}")
        if mode == "exact" and loss_mode != "full_batch":
            raise ValueError(
                "mode='exact' requires loss_mode='full_batch': bitwise "
                "parity with the single-program baseline needs the loss "
                "computed over the full concatenated logits")
        check_pp_config(cfg, num_stages)
        if mode == "exact":
            extra = {a: s for a, s in mesh.shape.items()
                     if a not in BATCH_AXES and s > 1}
            if extra:
                raise ValueError(
                    f"mode='exact' shards rows over (data, fsdp) only; this "
                    f"stage mesh also has {extra} — use mode='sharded'")
        self.cfg = cfg
        self.stage = stage
        self.num_stages = num_stages
        self.mesh = mesh
        self.tx = tx
        self.mode = mode
        self.loss_mode = loss_mode
        self.first = stage == 0
        self.last = stage == num_stages - 1
        self.stage_len = cfg.num_layers // num_stages
        mods = build_stage_modules(cfg, self.stage_len)
        self._stage_mod, self._embed_mod, self._norm_mod, self._head_mod = mods
        self._jax = jax
        self._row_spec = P(BATCH_AXES)
        self._row_sh = NamedSharding(mesh, self._row_spec)
        # mode='sharded' stages lay out by a first-class Plan — an explicit
        # `plan=` (e.g. a per-stage DLS_PIPE_SPEC entry or a pinned sweep
        # winner) wins; a bare `rules=` is wrapped into an equivalent plan
        # so both call styles compile identically. The plan's spec
        # validation runs against THIS stage's mesh (the tensor-axis skew
        # guard warns here — the per-stage tensor layout is pinned green at
        # data=1 in tests, the refusal is the sweep's job).
        if plan is None and rules is not None:
            plan = plan_lib.Plan(name=f"stage{stage}-rules", rules=rules)
        if plan is not None:
            plan.validate(mesh)
            rules = plan.rules
            tx = plan.wrap_optimizer(tx, mesh)
            self.tx = tx
        self._plan = plan
        self._rules = rules
        self._acc: dict[str, Any] = {}
        self._split_cache: dict[int, Any] = {}
        self._build()

    # -- jitted functions ----------------------------------------------------

    def _stage_apply(self, sp, x):
        out, _ = self._stage_mod.apply({"params": sp}, x, None, None)
        return out

    def _build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributeddeeplearningspark_tpu.parallel.mesh import BATCH_AXES

        mesh, row = self.mesh, self._row_spec
        part = P(BATCH_AXES)  # leading [1]-per-device partial axis

        def stack1(tree):
            return jax.tree.map(lambda g: g[None], tree)

        def ce_local(norm_p, head_p, acts, labels, mask, denom):
            """The baseline loss expression on this device's rows: RMSNorm
            → head → next-token CE → mask-weighted sum / global denom
            (replicated). Bitwise the same chain losses.causal_lm builds."""
            h = self._norm_mod.apply({"params": norm_p}, acts)
            logits = self._head_mod.apply({"params": head_p}, h)
            logits = logits.astype(jnp.float32)
            per_tok = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], labels[:, 1:])
            m = mask[:, 1:].astype(jnp.float32)
            return (per_tok * m).sum() / denom, m.sum()

        if self.mode == "exact":
            def sm(f, in_specs, out_specs):
                return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                             out_specs=out_specs,
                                             check_vma=False))

            self._fwd = sm(self._stage_apply, (P(), row), row)

            def stage_bwd(sp, x, dy):
                _, vjp = jax.vjp(self._stage_apply, sp, x)
                dp, dx = vjp(dy)
                return stack1(dp), dx

            self._bwd = sm(stage_bwd, (P(), row, row), (part, row))
            if self.first:
                def embed_apply(ep, ids):
                    return self._embed_mod.apply({"params": ep}, ids)

                self._embed = sm(embed_apply, (P(), row), row)

                def embed_bwd(ep, ids, dx):
                    _, vjp = jax.vjp(lambda p: embed_apply(p, ids), ep)
                    return stack1(vjp(dx)[0])

                self._embed_bwd = sm(embed_bwd, (P(), row, row), part)
            if self.last:
                # loss value AND its backward in ONE program (separate
                # fwd/bwd jits would recompute the head matmul)
                def loss_grad(norm_p, head_p, acts, labels, mask, denom):
                    def f(np_, hp_, a_):
                        s, w = ce_local(np_, hp_, a_, labels, mask,
                                        jnp.float32(1.0))
                        return s / denom, (s, w)

                    _, vjp, (s, w) = jax.vjp(f, norm_p, head_p, acts,
                                             has_aux=True)
                    dn, dh, da = vjp(jnp.float32(1.0))
                    return (jnp.stack([s, w])[None], stack1(dn), stack1(dh),
                            da)

                self._loss_grad = sm(loss_grad,
                                     (P(), P(), row, row, row, P()),
                                     (part, part, part, row))
            self._collect = lambda tree: jax.tree.map(
                lambda g: g.sum(axis=0), tree)
        else:  # sharded: GSPMD jit, per-stage layout from the rules
            from distributeddeeplearningspark_tpu.parallel.sharding import (
                ShardingRules,
            )

            self._rules = self._rules or ShardingRules()
            self._fwd = jax.jit(self._stage_apply,
                                out_shardings=self._row_sh)

            def stage_bwd(sp, x, dy):
                _, vjp = jax.vjp(self._stage_apply, sp, x)
                return vjp(dy)  # (dparams, dx) — GSPMD reduces dparams

            self._bwd = jax.jit(stage_bwd)
            if self.first:
                def embed_apply(ep, ids):
                    return self._embed_mod.apply({"params": ep}, ids)

                self._embed = jax.jit(embed_apply,
                                      out_shardings=self._row_sh)

                def embed_bwd(ep, ids, dx):
                    _, vjp = jax.vjp(lambda p: embed_apply(p, ids), ep)
                    return vjp(dx)[0]

                self._embed_bwd = jax.jit(embed_bwd)
            if self.last:
                def loss_grad(norm_p, head_p, acts, labels, mask, denom):
                    def f(np_, hp_, a_):
                        s, w = ce_local(np_, hp_, a_, labels, mask,
                                        jnp.float32(1.0))
                        return s / denom, (s, w)

                    _, vjp, (s, w) = jax.vjp(f, norm_p, head_p, acts,
                                             has_aux=True)
                    dn, dh, da = vjp(jnp.float32(1.0))
                    return jnp.stack([s, w]), dn, dh, da

                self._loss_grad = jax.jit(loss_grad)
            self._collect = lambda tree: tree
            self._state_rules = self._rules

        def apply_fn(params, opt_state, *grad_trees):
            import optax as _optax

            grads = {}
            for t in grad_trees:
                grads.update(t)
            grads = self._collect(grads)
            updates, new_opt = self.tx.update(grads, opt_state, params)
            return _optax.apply_updates(params, updates), new_opt

        self._apply = jax.jit(apply_fn)
        # mask-weight (the loss denominator) over the SAME shifted mask the
        # loss uses — one full-batch reduction, computed by whichever stage
        # holds the batch (stage 0) and shipped in the step META frame
        self._mask_weight = jax.jit(
            lambda mask: mask[:, 1:].astype(jnp.float32).sum(),
            out_shardings=NamedSharding(mesh, P()))
        self._concat = jax.jit(
            lambda parts: jnp.concatenate(parts, axis=0),
            out_shardings=self._row_sh)

    # -- state ---------------------------------------------------------------

    def slice_params(self, full_params: dict) -> dict:
        jax = self._jax
        lo, hi = self.stage * self.stage_len, (self.stage + 1) * self.stage_len
        sub = {"layers": jax.tree.map(lambda a: a[lo:hi],
                                      full_params["layers"])}
        if self.first:
            sub["token_embed"] = full_params["token_embed"]
        if self.last:
            sub["final_norm"] = full_params["final_norm"]
            sub["lm_head"] = full_params["lm_head"]
        return sub

    def init_state(self, sample_batch: dict, seed: int):
        """Deterministic full-model init (identical to the single-program
        ``step_lib.init_state`` values), sliced to this stage and placed
        with the stage's shardings."""
        import jax

        from distributeddeeplearningspark_tpu.models.llama import (
            LlamaForCausalLM,
        )
        from distributeddeeplearningspark_tpu.train.state import TrainState

        model = LlamaForCausalLM(self.cfg)

        def init_fn(rng):
            model_rng, state_rng = jax.random.split(rng)
            variables = model.init({"params": model_rng, "dropout": model_rng},
                                   sample_batch, train=False)
            return variables["params"], state_rng

        full_params, state_rng = jax.jit(init_fn)(jax.random.PRNGKey(seed))
        sub = self.slice_params(full_params)
        del full_params
        state = TrainState.create(params=sub, opt_state=self.tx.init(sub),
                                  mutable={}, rng=state_rng, embed_state={})
        self.state_shardings = self._shardings_for(state)
        return jax.device_put(state, self.state_shardings)

    def _shardings_for(self, state):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.mode == "exact":
            rep = NamedSharding(self.mesh, P())
            return jax.tree.map(lambda _: rep, state)
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        if self._plan is not None:
            # the stage's Plan owns the layout (incl. any ZeRO opt-state
            # sharding over the stage's replica axes)
            return self._plan.state_shardings(abstract, self.mesh)
        from distributeddeeplearningspark_tpu.parallel.sharding import (
            state_shardings,
        )

        return state_shardings(abstract, self.mesh, self._rules)

    # -- per-step compute (called by the runner) -----------------------------

    def start_step(self) -> None:
        self._acc = {}

    def _accumulate(self, key: str, grads: Any) -> None:
        jax = self._jax
        cur = self._acc.get(key)
        self._acc[key] = grads if cur is None else jax.tree.map(
            jax.numpy.add, cur, grads)

    def put_rows(self, arr: np.ndarray):
        return self._jax.device_put(arr, self._row_sh)

    def split_rows(self, x, m: int) -> list:
        """[B, ...] → M row-contiguous microbatch slices, each re-sharded
        over the stage's (data, fsdp) rows — an eager slice of a sharded
        array would land whole on one device and silently serialize the
        stage."""
        fn = self._split_cache.get(m)
        if fn is None:
            import jax

            def split(a):
                r = a.shape[0] // m
                return tuple(a[i * r:(i + 1) * r] for i in range(m))

            fn = jax.jit(split, out_shardings=(self._row_sh,) * m)
            self._split_cache[m] = fn
        return list(fn(x))

    def embed(self, state, ids_dev):
        return self._embed(state.params["token_embed"], ids_dev)

    def embed_backward(self, state, ids_dev, d_x_full) -> None:
        self._accumulate("token_embed", {
            "token_embed": self._embed_bwd(state.params["token_embed"],
                                           ids_dev, d_x_full)})

    def fwd(self, state, x_mb):
        return self._fwd(state.params["layers"], x_mb)

    def bwd(self, state, x_mb, dy_mb):
        dp, dx = self._bwd(state.params["layers"], x_mb, dy_mb)
        self._accumulate("layers", {"layers": dp})
        return dx

    def mask_weight(self, mask_dev) -> float:
        return float(self._jax.device_get(self._mask_weight(mask_dev)))

    def concat_rows(self, parts: list):
        return self._concat(list(parts))

    def loss_backward(self, state, acts, labels_dev, mask_dev, denom: float
                      ) -> tuple[dict, Any]:
        """(metrics, d_acts) for ``acts`` (full batch or one microbatch);
        accumulates the norm/head grads. ``denom`` is the GLOBAL mask
        weight (max(W, 1) — the baseline's loss denominator)."""
        import jax.numpy as jnp

        jax = self._jax
        p = state.params
        stats, dn, dh, da = self._loss_grad(
            p["final_norm"], p["lm_head"], acts, labels_dev, mask_dev,
            jnp.float32(denom))
        stats = np.asarray(jax.device_get(stats), np.float32)
        if stats.ndim == 2:  # exact mode: per-device partials, sum once
            stats = stats.sum(axis=0, dtype=np.float32)
        loss_sum = np.float32(stats[0])
        self._accumulate("head", {"final_norm": dn, "lm_head": dh})
        loss = np.float32(loss_sum / np.float32(denom))
        return {"loss": float(loss), "loss_sum": float(loss_sum),
                "weight": float(stats[1])}, da

    def apply_grads(self, state):
        """One optimizer step from the accumulated grads (exact mode sums
        the per-device partials here — ONE cross-device reduction per step,
        matching the single-program scan's association order)."""
        trees = [self._acc[k] for k in ("token_embed", "layers", "head")
                 if k in self._acc]
        new_params, new_opt = self._apply(state.params, state.opt_state,
                                          *trees)
        self._acc = {}
        return state.replace(step=state.step + 1, params=new_params,
                             opt_state=new_opt)


# -- span bookkeeping ---------------------------------------------------------


class _StepSpans:
    """Per-step span collector for one stage: a stage-local ``pipe-step``
    tree (bubble accounting) plus per-microbatch spans that join the
    cross-stage trace minted by stage 0 (the PR 7 context carried in the
    transport frames)."""

    def __init__(self, stage: int, step: int, m: int, p: int, schedule: str):
        self.stage, self.step, self.m, self.p = stage, step, m, p
        self.schedule = schedule
        self.trace_id = f"pipe-{os.urandom(4).hex()}"
        self.root_id = trace_lib.new_span_id()
        self.t0 = time.time()
        self.records: list[dict] = []

    def add(self, name: str, t0: float, t1: float, *,
            trace_id: str | None = None, parent_id: str | None = None,
            span_id: str | None = None, **attrs) -> str:
        sid = span_id or trace_lib.new_span_id()
        rec = trace_lib.span(
            trace_id or self.trace_id, sid, name, t0, t1,
            parent_id=(parent_id if trace_id else
                       (parent_id or self.root_id)),
            stage=self.stage, step=self.step, **attrs)
        self.records.append(rec)
        return sid

    @contextlib.contextmanager
    def span(self, name: str, **kw):
        t0 = time.time()
        try:
            yield
        finally:
            self.add(name, t0, time.time(), **kw)

    def flush(self, writer) -> None:
        self.records.append(trace_lib.span(
            self.trace_id, self.root_id, STEP_SPAN, self.t0, time.time(),
            stage=self.stage, step=self.step, m=self.m, p=self.p,
            schedule=self.schedule))
        if writer is not None:
            writer.emit_many(trace_lib.SPAN_KIND, self.records)
        self.records = []


# -- the stage runner ---------------------------------------------------------


@dataclasses.dataclass
class StageRunConfig:
    steps: int
    batch_size: int
    microbatches: int
    checkpoint_every: int | None = None
    seed: int = 0
    recv_timeout_s: float = 300.0
    connect_timeout_s: float = 300.0
    #: total wall budget for surviving a dead peer (reconnect + resync);
    #: past it the stage exits nonzero and the supervisor restarts it too.
    resync_budget_s: float = 600.0


class PipelineStageRunner:
    """Drive ONE stage program against the transport for ``steps`` steps.

    ``batch_fn(step) -> {"input_ids", "loss_mask"}`` (stage 0 only) must be
    a pure function of the step index — that is what makes rollback-resync
    trivial (no stream state to rewind). The runner owns scheduling,
    checkpointing, telemetry (spans + step_metrics + heartbeats), fault
    injection hooks, and peer-death resync.
    """

    def __init__(self, program: LlamaStageProgram,
                 transport: mpmd.PipelineTransport, run: StageRunConfig, *,
                 batch_fn: Callable[[int], dict] | None = None,
                 checkpointer=None):
        self.program = program
        self.transport = transport
        self.run_cfg = run
        self.batch_fn = batch_fn
        self.ckpt = checkpointer
        if program.first and batch_fn is None:
            raise ValueError("stage 0 needs a batch_fn (it owns the feed)")
        if run.batch_size % run.microbatches:
            raise ValueError(
                f"batch_size {run.batch_size} must divide by microbatches "
                f"{run.microbatches}")
        from distributeddeeplearningspark_tpu.parallel.mesh import (
            num_data_shards,
        )

        rows = run.batch_size // run.microbatches
        shards = num_data_shards(program.mesh)
        if rows % shards:
            raise ValueError(
                f"microbatch of {rows} row(s) (batch {run.batch_size} / "
                f"{run.microbatches} microbatches) cannot shard over this "
                f"stage's {shards} (data x fsdp) device(s) — use fewer "
                f"microbatches, a bigger batch, or a narrower stage mesh")
        self._tele = telemetry_lib.get()
        self._losses: list[float] = []

    # -- lifecycle -----------------------------------------------------------

    def _sample_batch(self) -> dict:
        b = max(2, min(self.run_cfg.batch_size, 8))
        return {"input_ids": np.zeros((b, 8), np.int32),
                "loss_mask": np.ones((b, 8), np.float32)}

    def _committed_step(self) -> int:
        if self.ckpt is None:
            return 0
        return self.ckpt.latest_verified_step() or 0

    def _restore(self, state, step: int):
        assert self.ckpt is not None
        restored, data_state = self.ckpt.restore(
            state, step=step, shardings=self.program.state_shardings)
        saved = (data_state or {}).get("losses")
        if saved is not None:
            self._losses = [float(x) for x in saved][:step]
        return restored

    def run(self) -> dict:
        import jax

        cfg = self.run_cfg
        state = self.program.init_state(self._sample_batch(), cfg.seed)
        committed = self._committed_step()
        if committed > 0:
            state = self._restore(state, committed)
        step = int(jax.device_get(state.step))
        self.transport.connect(hello={"step": committed},
                               timeout=cfg.connect_timeout_s)
        agreed = self.transport.sync_step(committed)
        if agreed != step:
            state = self._reposition(state, agreed)
            step = agreed
        if self._tele is not None:
            self._tele.emit("phase", name="run", edge="begin", step=step)
            self._tele.heartbeat(step=step)
        fault = faults.get()
        resync_t0: float | None = None
        try:
            while step < cfg.steps:
                if fault is not None and step + 1 == fault.step and \
                        fault.kind in ("crash", "die_host", "hang"):
                    kind, fault = fault.kind, None
                    if kind == "hang":
                        faults.hang()
                    else:
                        faults.crash()
                lap_t0 = time.time()
                try:
                    state, metrics = self._run_step(state, step)
                except mpmd.TransportError as e:
                    now = time.monotonic()
                    if resync_t0 is None:
                        resync_t0 = now
                    if now - resync_t0 > cfg.resync_budget_s:
                        raise
                    state = self._resync(state, e)
                    step = int(jax.device_get(state.step))
                    continue
                resync_t0 = None
                step += 1
                self._losses.append(metrics.get("loss", float("nan")))
                if self._tele is not None:
                    self._tele.step_metrics(
                        step, steps=1, lap_s=time.time() - lap_t0,
                        metrics=metrics, stage=self.program.stage)
                    self._tele.heartbeat(step=step)
                self._touch_heartbeat()
                if (cfg.checkpoint_every and self.ckpt is not None
                        and step % cfg.checkpoint_every == 0):
                    self._save(state, step)
            if self.ckpt is not None:
                self._save(state, step)
            self.transport.close()
            return {"step": step, "losses": self._losses,
                    "stage": self.program.stage, "state": state}
        except BaseException:
            # dying of a NON-transport error (shape bug, OOM, SIGTERM
            # unwinding): tear the sockets now so peers get a typed
            # PeerDiedError immediately instead of burning their full
            # recv timeout discovering it
            self.transport.reset()
            raise
        finally:
            if self._tele is not None:
                self._tele.emit("phase", name="run", edge="end", step=step)

    def _save(self, state, step: int) -> None:
        assert self.ckpt is not None
        # the loss trajectory rides the checkpoint: a restarted stage-0
        # process must report the WHOLE run's losses in its summary/DONE,
        # not just the steps since its own restore
        self.ckpt.save(step, state, data_state={
            "examples_seen": step * self.run_cfg.batch_size,
            "batch_size": self.run_cfg.batch_size,
            "losses": list(self._losses[:step])})
        self.ckpt.wait()

    @staticmethod
    def _touch_heartbeat() -> None:
        path = os.environ.get("DLS_HEARTBEAT_FILE")
        if not path:
            return
        try:
            with open(path, "w") as f:
                f.write(str(os.getpid()))
        except OSError:
            pass

    def _reposition(self, state, step: int):
        """Move this stage's state to ``step``: restore the per-stage
        checkpoint, or re-init deterministically when the pipeline agreed
        on step 0 (no checkpoint anywhere)."""
        import jax

        # rollback rewinds the loss trajectory too — the steps past the
        # resume point will re-run and re-append
        del self._losses[step:]
        if step == 0:
            self.program.start_step()
            return self.program.init_state(self._sample_batch(),
                                           self.run_cfg.seed)
        if int(jax.device_get(state.step)) == step:
            return state
        return self._restore(state, step)

    def _resync(self, state, err: mpmd.TransportError):
        """A peer died mid-step: drop partial step state, block on the
        transport until the supervisor brings the stage back, agree on the
        resume step, roll back to it."""
        cfg = self.run_cfg
        committed = self._committed_step()
        logger.warning(
            "stage %d: peer failure (%s: %s) — reconnecting and resyncing "
            "from checkpoint step %d",
            self.program.stage, type(err).__name__, err, committed)
        if self._tele is not None:
            self._tele.recovery(committed or None, "pipeline-resync",
                                stage=self.program.stage,
                                error=type(err).__name__,
                                detail=str(err)[:200])
        self.program.start_step()
        self.transport.reset()
        self.transport.connect(hello={"step": committed},
                               timeout=cfg.connect_timeout_s)
        agreed = self.transport.sync_step(committed)
        return self._reposition(state, agreed)

    # -- one training step ---------------------------------------------------

    def _run_step(self, state, step: int):
        cfg = self.run_cfg
        prog = self.program
        spans = _StepSpans(prog.stage, step, cfg.microbatches,
                           prog.num_stages,
                           "gpipe" if prog.loss_mode == "full_batch"
                           else "1f1b")
        prog.start_step()
        try:
            if prog.first:
                metrics = self._step_first(state, step, spans)
            elif prog.last:
                metrics = self._step_last(state, step, spans)
            else:
                metrics = self._step_mid(state, step, spans)
            with spans.span("pipe-opt"):
                state = prog.apply_grads(state)
                self._block(state.params)
        finally:
            spans.flush(self._tele)
        return state, metrics

    def _block(self, x):
        import jax

        return jax.block_until_ready(x)

    def _recv(self, link: mpmd.StageLink, kind: int, spans: _StepSpans,
              pending: "list | None" = None):
        """Blocking receive, booked as recv-wait only when it actually
        blocks (a buffered frame is free — that is the double-buffering
        paying off, not a bubble). ``pending`` frames (drained while a
        send was blocked) are consumed first."""
        if pending:
            return pending.pop(0)
        got = link.try_recv(kind)
        if got is not None:
            return got
        with spans.span("pipe-recv-wait",
                        kind=mpmd._KIND_NAMES.get(kind, kind)):
            return link.recv(kind, timeout=self.run_cfg.recv_timeout_s)

    def _send(self, link: mpmd.StageLink, kind: int, obj: Any, mb: int,
              spans: _StepSpans, *, drain=None) -> None:
        """Bounded send that never deadlocks the bidirectional flow: while
        the send queue is full, incoming frames are drained into a local
        pending list (``drain``), so the opposite direction keeps moving.
        Booked as send-wait only when it actually blocked."""
        t0 = time.time()
        blocked = False
        deadline = time.monotonic() + self.run_cfg.recv_timeout_s
        while True:
            try:
                link.send(kind, obj, mb=mb, timeout=0.02)
                break
            except mpmd.TransportTimeout:
                blocked = True
                if drain is not None:
                    drain()
                if time.monotonic() > deadline:
                    raise
        if blocked:
            spans.add("pipe-send-wait", t0, time.time(), mb=mb)

    @staticmethod
    def _drainer(link: mpmd.StageLink | None, kind: int, pending: list):
        """A drain callback: move any available ``kind`` frame off the
        link's bounded inbox into ``pending`` (no compute — just free the
        inbox so the peer's sender unblocks)."""
        def drain():
            if link is None:
                return
            try:
                item = link.try_recv(kind)
            except mpmd.TransportError:
                return  # surfaced by the next blocking call, typed
            if item is not None:
                pending.append(item)
        return drain

    # stage 0 — owns the batch, the embedding, and the microbatch traces.
    def _step_first(self, state, step: int, spans: _StepSpans) -> dict:
        cfg, prog = self.run_cfg, self.program
        m = cfg.microbatches
        rows = cfg.batch_size // m
        down = self.transport.down
        assert down is not None
        batch = self.batch_fn(step)
        ids = np.ascontiguousarray(batch["input_ids"], np.int32)
        mask = np.ascontiguousarray(
            batch.get("loss_mask",
                      np.ones(ids.shape, np.float32)), np.float32)
        if ids.shape[0] != cfg.batch_size:
            raise ValueError(
                f"batch_fn returned {ids.shape[0]} rows, expected "
                f"{cfg.batch_size}")
        with spans.span("pipe-embed"):
            ids_dev = prog.put_rows(ids)
            x_full = self._block(prog.embed(state, ids_dev))
            weight = prog.mask_weight(prog.put_rows(mask))
        pending: list = []
        drain = self._drainer(down, mpmd.GRAD, pending)
        self._send(down, mpmd.META, {
            "step": step, "m": m, "p": prog.num_stages,
            "weight": weight, "loss_mode": prog.loss_mode}, -1, spans)
        x_mbs = prog.split_rows(x_full, m)
        traces: list[tuple[str, str, float]] = []
        for i in range(m):
            tid = trace_lib.new_trace_id()
            root = trace_lib.new_span_id()
            mb_t0 = time.time()
            fwd_sid = trace_lib.new_span_id()
            with spans.span("pipe-fwd", trace_id=tid, parent_id=root,
                            span_id=fwd_sid, mb=i):
                act = np.asarray(self._block(prog.fwd(state, x_mbs[i])))
            self._send(down, mpmd.ACT, {
                "step": step, "act": act,
                "labels": ids[i * rows:(i + 1) * rows],
                "mask": mask[i * rows:(i + 1) * rows],
                "trace": {"trace_id": tid, "parent_id": fwd_sid},
            }, i, spans, drain=drain)
            traces.append((tid, root, mb_t0))
        d_x: list = [None] * m
        for _ in range(m):
            mb, payload = self._recv(down, mpmd.GRAD, spans, pending)
            tid, root, mb_t0 = traces[mb]
            ctx = payload.get("trace") or {}
            with spans.span("pipe-bwd", trace_id=tid,
                            parent_id=ctx.get("parent_id") or root, mb=mb):
                dy = prog.put_rows(np.asarray(payload["grad"]))
                d_x[mb] = self._block(prog.bwd(state, x_mbs[mb], dy))
            # close the cross-stage microbatch root: fwd → transit →
            # downstream stages → grad return → local bwd, end to end
            spans.add("microbatch", mb_t0, time.time(), trace_id=tid,
                      span_id=root, parent_id=None, mb=mb, m=m,
                      p=prog.num_stages)
        with spans.span("pipe-embed-bwd"):
            self._block(prog.embed_backward(state, ids_dev,
                                            prog.concat_rows(d_x)))
        _, payload = self._recv(down, mpmd.METRICS, spans)
        return dict(payload.get("metrics") or {})

    # middle stages — pure relay compute: 1F1B (prefer a waiting gradient
    # over the next forward).
    def _step_mid(self, state, step: int, spans: _StepSpans) -> dict:
        cfg, prog = self.run_cfg, self.program
        m = cfg.microbatches
        up, down = self.transport.up, self.transport.down
        assert up is not None and down is not None
        pending_g: list = []
        drain_g = self._drainer(down, mpmd.GRAD, pending_g)
        _, meta = self._recv(up, mpmd.META, spans)
        self._send(down, mpmd.META, meta, -1, spans, drain=drain_g)
        x_in: dict[int, Any] = {}
        tids: dict[int, str | None] = {}
        done_f = done_b = 0
        while done_b < m:
            item = pending_g.pop(0) if pending_g else down.try_recv(mpmd.GRAD)
            if item is None and done_f < m:
                mb, payload = self._recv(up, mpmd.ACT, spans)
                ctx = payload.get("trace") or {}
                fwd_sid = trace_lib.new_span_id()
                with spans.span(
                        "pipe-fwd",
                        trace_id=ctx.get("trace_id") or spans.trace_id,
                        parent_id=ctx.get("parent_id"),
                        span_id=fwd_sid, mb=mb):
                    x = prog.put_rows(np.asarray(payload["act"]))
                    y = self._block(prog.fwd(state, x))
                x_in[mb] = x
                tids[mb] = ctx.get("trace_id")
                self._send(down, mpmd.ACT, {
                    "step": step, "act": np.asarray(y),
                    "labels": payload["labels"], "mask": payload["mask"],
                    "trace": {"trace_id": ctx.get("trace_id"),
                              "parent_id": fwd_sid},
                }, mb, spans, drain=drain_g)
                done_f += 1
                continue
            if item is None:
                item = self._recv(down, mpmd.GRAD, spans)
            mb, payload = item
            ctx = payload.get("trace") or {}
            bwd_sid = trace_lib.new_span_id()
            tid = tids.get(mb) or spans.trace_id
            with spans.span("pipe-bwd", trace_id=tid,
                            parent_id=ctx.get("parent_id"),
                            span_id=bwd_sid, mb=mb):
                dy = prog.put_rows(np.asarray(payload["grad"]))
                dx = self._block(prog.bwd(state, x_in.pop(mb), dy))
            self._send(up, mpmd.GRAD, {
                "step": step, "grad": np.asarray(dx),
                "trace": {"trace_id": tid, "parent_id": bwd_sid},
            }, mb, spans, drain=drain_g)
            done_b += 1
        _, payload = self._recv(down, mpmd.METRICS, spans)
        self._send(up, mpmd.METRICS, payload, -1, spans)
        return dict(payload.get("metrics") or {})

    # last stage — the loss. full_batch: all forwards, one baseline-exact
    # full-batch loss, backwards in reverse (the scan's accumulation
    # order). per_microbatch: loss+backward per arrival (1F1B memory).
    def _step_last(self, state, step: int, spans: _StepSpans) -> dict:
        cfg, prog = self.run_cfg, self.program
        m = cfg.microbatches
        up = self.transport.up
        assert up is not None
        _, meta = self._recv(up, mpmd.META, spans)
        denom = max(float(meta["weight"]), 1.0)
        if prog.loss_mode == "full_batch":
            metrics = self._last_full_batch(state, step, spans, m, denom)
        else:
            metrics = self._last_per_microbatch(state, step, spans, m, denom)
        self._send(up, mpmd.METRICS, {"step": step, "metrics": metrics},
                   -1, spans)
        return metrics

    def _last_full_batch(self, state, step, spans, m, denom) -> dict:
        prog = self.program
        up = self.transport.up
        pending_a: list = []
        drain_a = self._drainer(up, mpmd.ACT, pending_a)
        x_in, h_out, labels, masks, ctxs = {}, {}, {}, {}, {}
        for _ in range(m):
            mb, payload = self._recv(up, mpmd.ACT, spans, pending_a)
            ctx = payload.get("trace") or {}
            fwd_sid = trace_lib.new_span_id()
            with spans.span("pipe-fwd",
                            trace_id=ctx.get("trace_id") or spans.trace_id,
                            parent_id=ctx.get("parent_id"),
                            span_id=fwd_sid, mb=mb):
                x = prog.put_rows(np.asarray(payload["act"]))
                h_out[mb] = self._block(prog.fwd(state, x))
            x_in[mb] = x
            labels[mb] = np.asarray(payload["labels"], np.int32)
            masks[mb] = np.asarray(payload["mask"], np.float32)
            ctxs[mb] = {"trace_id": ctx.get("trace_id"), "fwd": fwd_sid}
        with spans.span("pipe-loss"):
            acts = prog.concat_rows([h_out[i] for i in range(m)])
            lab_dev = prog.put_rows(np.concatenate(
                [labels[i] for i in range(m)], axis=0))
            mask_dev = prog.put_rows(np.concatenate(
                [masks[i] for i in range(m)], axis=0))
            metrics, d_acts = prog.loss_backward(state, acts, lab_dev,
                                                 mask_dev, denom)
            d_mbs = prog.split_rows(self._block(d_acts), m)
        # reverse microbatch order — the single-program scan's backward
        # accumulation order, which the bitwise parity contract pins
        for mb in reversed(range(m)):
            bwd_sid = trace_lib.new_span_id()
            tid = ctxs[mb]["trace_id"] or spans.trace_id
            with spans.span("pipe-bwd", trace_id=tid,
                            parent_id=ctxs[mb]["fwd"], span_id=bwd_sid,
                            mb=mb):
                dx = self._block(prog.bwd(state, x_in[mb], d_mbs[mb]))
            self._send(up, mpmd.GRAD, {
                "step": step, "grad": np.asarray(dx),
                "trace": {"trace_id": tid, "parent_id": bwd_sid},
            }, mb, spans, drain=drain_a)
        metrics["perplexity"] = float(np.exp(np.float32(metrics["loss"])))
        return metrics

    def _last_per_microbatch(self, state, step, spans, m, denom) -> dict:
        prog = self.program
        up = self.transport.up
        pending_a: list = []
        drain_a = self._drainer(up, mpmd.ACT, pending_a)
        loss_sum = weight = 0.0
        for _ in range(m):
            mb, payload = self._recv(up, mpmd.ACT, spans, pending_a)
            ctx = payload.get("trace") or {}
            tid = ctx.get("trace_id") or spans.trace_id
            fwd_sid = trace_lib.new_span_id()
            with spans.span("pipe-fwd", trace_id=tid,
                            parent_id=ctx.get("parent_id"),
                            span_id=fwd_sid, mb=mb):
                x = prog.put_rows(np.asarray(payload["act"]))
                h = self._block(prog.fwd(state, x))
            with spans.span("pipe-loss", trace_id=tid, parent_id=fwd_sid,
                            mb=mb):
                mrec, d_h = prog.loss_backward(
                    state, h,
                    prog.put_rows(np.asarray(payload["labels"], np.int32)),
                    prog.put_rows(np.asarray(payload["mask"], np.float32)),
                    denom)
                loss_sum += mrec["loss_sum"]
                weight += mrec["weight"]
            bwd_sid = trace_lib.new_span_id()
            with spans.span("pipe-bwd", trace_id=tid, parent_id=fwd_sid,
                            span_id=bwd_sid, mb=mb):
                dx = self._block(prog.bwd(state, x, self._block(d_h)))
            self._send(up, mpmd.GRAD, {
                "step": step, "grad": np.asarray(dx),
                "trace": {"trace_id": tid, "parent_id": bwd_sid},
            }, mb, spans, drain=drain_a)
        loss = float(np.float32(np.float32(loss_sum) / np.float32(denom)))
        return {"loss": loss, "weight": weight,
                "perplexity": float(np.exp(np.float32(loss)))}


# -- env-configured stage entry point -----------------------------------------
#
# ``python -m distributeddeeplearningspark_tpu.train.pipeline_trainer`` runs
# one stage, entirely env-configured — the worker half of the
# PipelineSupervisor contract, exactly how serve/fleet.py's replica_main
# boots. DLS_PIPE_SPEC carries the run recipe; DLS_STAGE_ID / DLS_NUM_STAGES
# / DLS_PIPE_PORTS / DLS_PIPE_AUTHKEY the topology; DLS_TELEMETRY_DIR the
# shared run directory (per-stage checkpoints live under
# ``<workdir>/stage<k>/ckpt``).


def _tiny_cfg(spec: dict):
    """The built-in CPU-trainable Llama geometry for drills/CI (mirrors
    serve/fleet's _tiny_llama_cfg idiom); ``spec["cfg"]`` overrides."""
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models.llama import LlamaConfig

    base = dict(vocab_size=512, hidden_size=128, num_layers=4, num_heads=4,
                num_kv_heads=2, intermediate_size=256, max_position=128,
                dtype=jnp.float32)
    base.update(spec.get("cfg") or {})
    return LlamaConfig(**base)


def _optimizer(spec: dict):
    import optax

    opt = dict(spec.get("optimizer") or {})
    name = opt.get("name", "adamw")
    lr = float(opt.get("lr", 1e-3))
    if name == "adamw":
        return optax.adamw(lr)
    if name == "sgd":
        return optax.sgd(lr, momentum=float(opt.get("momentum", 0.0)))
    raise ValueError(f"unknown optimizer {name!r} in DLS_PIPE_SPEC")


def _stage_mesh(spec: dict, stage: int):
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    per_stage = (spec.get("stage_meshes") or {}).get(str(stage))
    axes = dict(per_stage or spec.get("mesh") or {"data": -1})
    return MeshSpec(**{k: int(v) for k, v in axes.items()}).build()


def _stage_plan(spec: dict, stage: int, cfg):
    """Per-stage layout for mode='sharded' as a first-class compile Plan
    (parallel/plan.py): 'fsdp' (wide sharded storage — the embedding-heavy
    first stage), 'tensor' (Megatron splits — MLP-heavy middle/last
    stages), 'zero' (replicated params, replica-sharded optimizer state),
    or 'replicated'. ``stage_plans`` (preferred) and the legacy
    ``stage_rules`` spec keys are synonyms; a per-stage entry may also be
    a full serialized plan record (e.g. a pinned ``plan_sweep`` winner)."""
    from distributeddeeplearningspark_tpu.parallel import plan as plan_lib

    name = (spec.get("stage_plans") or spec.get("stage_rules") or {}).get(
        str(stage), spec.get("plan", spec.get("rules", "replicated")))
    if isinstance(name, dict):  # inline serialized plan record
        return plan_lib.Plan.from_record(name)
    try:
        return plan_lib.stage_plan(
            name, cfg, fsdp_min_size=int(spec.get("fsdp_min_size", 2 ** 10)))
    except plan_lib.PlanError as e:
        raise ValueError(f"DLS_PIPE_SPEC stage {stage}: {e}") from e


def synthetic_batch_fn(spec: dict):
    """Deterministic pure-function-of-step batch stream: the property that
    makes resync rollback trivial (re-running step *s* reproduces its
    batch bit-for-bit at any attempt, on any stage geometry)."""
    b = int(spec.get("batch_size", 8))
    t = int(spec.get("seq", 32))
    vocab = int((spec.get("cfg") or {}).get("vocab_size", 512))
    data_seed = int(spec.get("data_seed", 1234))

    def batch_fn(step: int) -> dict:
        rng = np.random.default_rng(data_seed + step)
        return {
            "input_ids": rng.integers(0, vocab, (b, t)).astype(np.int32),
            "loss_mask": np.ones((b, t), np.float32),
        }

    return batch_fn


def stage_main() -> int:
    from distributeddeeplearningspark_tpu.utils.env import (
        configure_compile_cache,
    )

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    configure_compile_cache()
    spec = json.loads(os.environ[mpmd.ENV_SPEC])
    stage = int(os.environ[mpmd.ENV_STAGE])
    num_stages = int(os.environ[mpmd.ENV_NUM_STAGES])
    workdir = os.environ.get(telemetry_lib.WORKDIR_ENV)
    if workdir:
        telemetry_lib.configure(workdir)
    cfg = _tiny_cfg(spec)
    mesh = _stage_mesh(spec, stage)
    mode = spec.get("mode", "exact")
    program = LlamaStageProgram(
        cfg, stage, num_stages, mesh, _optimizer(spec), mode=mode,
        loss_mode=spec.get("loss_mode",
                           "full_batch" if mode == "exact"
                           else "per_microbatch"),
        plan=_stage_plan(spec, stage, cfg) if mode == "sharded" else None)
    transport = mpmd.PipelineTransport.from_env(
        depth=int(spec.get("depth", 2)))
    ckpt = None
    if workdir and spec.get("checkpoint_every"):
        from distributeddeeplearningspark_tpu.checkpoint import Checkpointer

        ckpt = Checkpointer(os.path.join(workdir, f"stage{stage}", "ckpt"),
                            async_save=False)
    run = StageRunConfig(
        steps=int(spec["steps"]),
        batch_size=int(spec.get("batch_size", 8)),
        microbatches=int(spec.get("microbatches", 4)),
        checkpoint_every=spec.get("checkpoint_every"),
        seed=int(spec.get("seed", 0)),
    )
    runner = PipelineStageRunner(
        program, transport, run,
        batch_fn=synthetic_batch_fn(spec) if stage == 0 else None,
        checkpointer=ckpt)
    logger.info("stage %d/%d: mesh %s mode=%s serving pipeline",
                stage, num_stages, dict(mesh.shape), mode)
    try:
        summary = runner.run()
    finally:
        if ckpt is not None:
            ckpt.close()
        transport.close()
    if stage == 0 and workdir:
        with open(os.path.join(workdir, "DONE"), "w") as f:
            json.dump({"step": summary["step"], "losses": summary["losses"],
                       "attempt": int(os.environ.get("DLS_RESTART", "0")
                                      or 0)}, f)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(stage_main())
