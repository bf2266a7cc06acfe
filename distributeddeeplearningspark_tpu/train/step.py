"""The jitted SPMD train step — the rebuild's entire hot loop.

The reference's hot loop is the per-partition closure dispatched by
``rdd.mapPartitions(train_fn)``: rebuild model from broadcast weights, then
``for batch: forward → backward → optimizer.step → (NCCL all-reduce)``
(SURVEY.md §3.1/§3.2). Here all of that — including gradient synchronization —
is ONE ``jax.jit``-compiled function of ``(TrainState, batch) → (TrainState,
metrics)``:

- the batch arrives sharded over the (data, fsdp) mesh axes, so each chip
  computes gradients on its shard;
- params are laid out by :class:`..parallel.sharding.ShardingRules`
  (replicated for DP ≙ driver broadcast; 'fsdp'-sharded for ZeRO);
- GSPMD inserts the gradient all-reduce (or reduce-scatter under FSDP) that
  the reference issues manually via Horovod/NCCL — no collective calls appear
  in this file, by design;
- the state is donated, so parameter memory is updated in place in HBM.

No Python control flow depends on data; shapes are static; the step compiles
once per (shapes, mesh) and is dispatched asynchronously so host-side input
prep overlaps device compute.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributeddeeplearningspark_tpu.parallel.mesh import BATCH_AXES
from distributeddeeplearningspark_tpu.parallel.sharding import ShardingRules, state_shardings
from distributeddeeplearningspark_tpu.train.state import TrainState

LossFn = Callable[[Any, dict[str, Any]], tuple[jax.Array, dict[str, Any]]]


def make_train_step(
    apply_fn: Callable,
    tx: optax.GradientTransformation,
    loss_fn: LossFn,
    *,
    mutable_keys: Sequence[str] = (),
    rng_names: Sequence[str] = ("dropout",),
    compute_dtype: Any = None,
    accum_steps: int = 1,
    trainable: Callable[[str], bool] | None = None,
    guard_nonfinite: bool = False,
) -> Callable[[TrainState, dict[str, Any]], tuple[TrainState, dict[str, Any]]]:
    """Build the (state, batch) → (state, metrics) function (un-jitted).

    ``apply_fn`` is a flax ``Module.apply``-shaped callable taking
    ``(variables, batch, train=...)``; models in
    :mod:`distributeddeeplearningspark_tpu.models` all follow this convention.
    ``compute_dtype`` (e.g. jnp.bfloat16) casts inputs for the forward pass —
    params stay in their stored dtype; MXU-bound matmuls pick up bf16 via the
    models' own ``dtype`` attributes, so this only affects raw inputs.

    ``accum_steps > 1`` — gradient accumulation (microbatching): the batch is
    split into ``accum_steps`` equal micro-batches scanned sequentially, their
    gradients averaged, and ONE optimizer update applied. This is the HBM
    lever when the per-chip batch doesn't fit (7B LoRA on small meshes): peak
    activation memory drops ×accum while arithmetic intensity per micro-step
    stays MXU-friendly. The reference gets the same effect for free from its
    round loop (multiple batches per aggregation round, SURVEY.md §3.1); here
    it is a ``lax.scan`` *inside* the jitted step so the optimizer/collective
    cost stays once-per-step.

    ``trainable`` — path predicate marking which params receive gradients
    (same signature as ``optim.masked``'s; pass the SAME predicate to both).
    Frozen params enter the loss under ``stop_gradient``, so autodiff never
    emits their weight-gradient matmuls or materializes their gradient
    buffers. This is a pure-waste cut for LoRA-style fine-tuning: without
    it, ``value_and_grad`` computes every frozen base weight's dW = Xᵀ dY
    (≈⅓ of backward FLOPs) and stacks [L, ...] f32 grad buffers that the
    masked optimizer then throws away — measured 394 → 304 ms/step (+30%
    tokens/s) on the config-5 bench shape (op_breakdown: the
    dynamic-update-slice grad-stacking fusions were 15% of device time
    alone).

    ``guard_nonfinite`` — divergence containment inside the graph: when the
    step's gradients are non-finite (NaN/Inf loss or blowup), params,
    optimizer state, and mutable collections keep their previous values and
    the step reports ``skipped = 1`` in its metrics; the step counter still
    advances (the poisoned batch is consumed, keeping the deterministic
    data-stream position honest for checkpoint fast-forward). This is the
    device-side half of ``Trainer.fit(on_nonfinite="skip")`` — a
    ``jnp.where`` select per leaf, free of host syncs, so async dispatch
    (and throughput) is untouched on the healthy path.
    """
    mutable_keys = tuple(mutable_keys)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    freeze = None
    if trainable is not None:
        from distributeddeeplearningspark_tpu.parallel.sharding import path_str

        def freeze(params):  # noqa: F811 — bound once, used in loss_of
            return jax.tree_util.tree_map_with_path(
                lambda path, p: p if trainable(path_str(path))
                else jax.lax.stop_gradient(p),
                params,
            )

    def train_step(state: TrainState, batch: dict[str, Any]):
        next_rng, step_rng = jax.random.split(jax.random.fold_in(state.rng, state.step))
        rngs = {name: jax.random.fold_in(step_rng, i) for i, name in enumerate(rng_names)}

        if compute_dtype is not None:
            batch = jax.tree.map(
                lambda x: x.astype(compute_dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
                batch,
            )

        def loss_of(params, mutable, mb, mb_rngs):
            if freeze is not None:
                params = freeze(params)
            variables = {"params": params, **mutable}
            if mutable_keys:
                outputs, updated = apply_fn(
                    variables, mb, train=True, mutable=list(mutable_keys), rngs=mb_rngs
                )
            else:
                outputs = apply_fn(variables, mb, train=True, rngs=mb_rngs)
                updated = {}
            loss, metrics = loss_fn(outputs, mb)
            return loss, (metrics, updated)

        # allow_int: int8 frozen-base leaves (LlamaConfig.base_quant)
        # are valid params that can never receive a real gradient — jax
        # hands back float0 for them, normalized to typed zeros below so
        # optax transforms and the accumulation scan stay dtype-stable
        grad_fn = jax.value_and_grad(loss_of, has_aux=True, allow_int=True)

        def detyped(grads):
            return jax.tree.map(
                lambda g, p: jnp.zeros_like(p)
                if g.dtype == jax.dtypes.float0 else g,
                grads, state.params)

        if accum_steps == 1:
            (_, (metrics, updated)), grads = grad_fn(
                state.params, state.mutable, batch, rngs
            )
            grads = detyped(grads)
            metrics = dict(metrics)
        else:
            def split_leaf(x):
                if x.shape[0] % accum_steps:
                    raise ValueError(
                        f"global batch {x.shape[0]} must divide by "
                        f"accum_steps {accum_steps}")
                return x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:])

            micro = jax.tree.map(split_leaf, batch)
            zero_grads = jax.tree.map(jnp.zeros_like, state.params)

            def body(carry, xs):
                mutable, gsum = carry
                mb, idx = xs
                mb_rngs = {n: jax.random.fold_in(r, idx) for n, r in rngs.items()}
                (_, (m, updated)), g = grad_fn(state.params, mutable, mb, mb_rngs)
                g = detyped(g)
                mutable = {**mutable, **updated} if mutable_keys else mutable
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (mutable, gsum), m

            (updated, grads), stacked_metrics = jax.lax.scan(
                body, (state.mutable, zero_grads),
                (micro, jnp.arange(accum_steps)),
            )
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            metrics = {k: jnp.mean(v, axis=0) for k, v in dict(stacked_metrics).items()}

        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_mutable = {**state.mutable, **updated} if mutable_keys else state.mutable
        grad_norm = optax.global_norm(grads)
        if guard_nonfinite:
            # a NaN/Inf anywhere in the gradients poisons their global norm,
            # so one scalar predicate covers loss blowup and grad blowup;
            # selecting OLD values (not zero updates) also shields stateful
            # optimizers (Adam moments) and BatchNorm stats from the event
            ok = jnp.isfinite(grad_norm)

            def keep_old(new_tree, old_tree):
                return jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                    new_tree, old_tree)

            new_params = keep_old(new_params, state.params)
            new_opt_state = keep_old(new_opt_state, state.opt_state)
            new_mutable = keep_old(new_mutable, state.mutable)
            metrics["skipped"] = 1.0 - ok.astype(jnp.float32)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            mutable=new_mutable,
            rng=next_rng,
        )
        metrics["grad_norm"] = grad_norm
        return new_state, metrics

    return train_step


def make_eval_step(apply_fn: Callable, loss_fn: LossFn) -> Callable:
    """(state, batch) → metrics, no grads, model in inference mode."""

    def eval_step(state: TrainState, batch: dict[str, Any]):
        variables = {"params": state.params, **state.mutable}
        outputs = apply_fn(variables, batch, train=False)
        _, metrics = loss_fn(outputs, batch)
        return metrics

    return eval_step


def make_predict_step(apply_fn: Callable) -> Callable:
    """(state, batch) → raw model outputs, inference mode (no loss).

    The reference's inference stack is ``broadcast(params)`` →
    ``rdd.mapPartitions(predict_fn)`` → collect (SURVEY.md §3.3); this is
    the jitted per-batch body of that ``predict_fn``.
    """

    def predict_step(state: TrainState, batch: dict[str, Any]):
        from distributeddeeplearningspark_tpu.train.fused_ce import (
            is_fused_output,
            materialize_logits,
        )

        variables = {"params": state.params, **state.mutable}
        out = apply_fn(variables, batch, train=False)
        if is_fused_output(out):
            return materialize_logits(out)
        return out

    return predict_step


def jit_predict_step(predict_step: Callable, mesh: Mesh, state_sh: Any) -> Callable:
    # outputs replicate (all-gather) like eval metrics: device_get cannot
    # fetch shards living on other hosts' devices, so batch-sharded outputs
    # would crash any multi-process run
    from distributeddeeplearningspark_tpu.parallel import plan as plan_lib

    return plan_lib.compile_step_with_plan(
        predict_step, plan_lib.DP, mesh, state_shardings=state_sh,
        kind="predict", instrument=False)


def batch_shardings_like(batch: Any, mesh: Mesh) -> Any:
    """Per-leaf NamedSharding: leading axis over (data, fsdp), rest replicated.

    A PartitionSpec shorter than the array rank leaves trailing dims
    replicated, so one spec covers every leaf rank.
    """
    sh = NamedSharding(mesh, P(BATCH_AXES))
    return jax.tree.map(lambda _: sh, batch)


def jit_train_step(
    train_step: Callable,
    mesh: Mesh,
    state_sh: Any,
    *,
    seq_sharded: bool = False,
    plan=None,
) -> Callable:
    """Compile with explicit state shardings and state donation — routed
    through the unified plan layer (:func:`..parallel.plan
    .compile_step_with_plan`), which owns donation and spec validation
    for every strategy.

    Batch shardings are inherited from the arrays themselves (``in_shardings
    = None``): :func:`..data.feed.put_global` is the single source of truth
    for the input layout — batch rows over (data, fsdp) and, under context
    parallelism, sequence over ``seq`` for rank≥2 leaves only. Declaring a
    uniform spec here instead would reject rank-1 leaves (sample weights,
    labels) that put_global correctly leaves batch-only.
    """
    from distributeddeeplearningspark_tpu.parallel import plan as plan_lib

    if plan is None:
        plan = plan_lib.plan_for_rules(
            plan_lib.REPLICATED, context_parallel=seq_sharded)
    return plan_lib.compile_step_with_plan(
        train_step, plan, mesh, state_shardings=state_sh, kind="train",
        instrument=False)


def jit_eval_step(
    eval_step: Callable, mesh: Mesh, state_sh: Any, *,
    seq_sharded: bool = False, plan=None,
) -> Callable:
    from distributeddeeplearningspark_tpu.parallel import plan as plan_lib

    if plan is None:
        plan = plan_lib.plan_for_rules(
            plan_lib.REPLICATED, context_parallel=seq_sharded)
    return plan_lib.compile_step_with_plan(
        eval_step, plan, mesh, state_shardings=state_sh, kind="eval",
        instrument=False)


def init_state(
    model,
    tx: optax.GradientTransformation,
    sample_batch: dict[str, Any],
    mesh: Mesh,
    rules: ShardingRules,
    *,
    seed: int = 0,
    sparse_embed: Sequence[Any] = (),
    plan=None,
) -> tuple[TrainState, Any]:
    """Initialize a sharded TrainState directly on the mesh.

    The init function is jitted with ``out_shardings`` derived from the rules,
    so a 7B-param FSDP state materializes already sharded — each chip only
    ever holds its slice (no host-side full copy, unlike the reference's
    driver-held ``state_dict``). Returns (state, sharding pytree).

    ``sparse_embed``: row-sparse table specs (train/embed.py) — allocates
    their per-row accumulators in ``embed_state`` (sharded by the rules).

    ``plan``: a :class:`..parallel.plan.Plan` — shardings then come from
    ``plan.state_shardings`` (its rules plus the ZeRO weight-update pass
    over the replica axes) instead of ``rules`` alone.
    """
    init_rng = jax.random.PRNGKey(seed)

    def init_fn(rng):
        model_rng, state_rng = jax.random.split(rng)
        variables = model.init({"params": model_rng, "dropout": model_rng}, sample_batch, train=False)
        variables = dict(variables)
        params = variables.pop("params")
        mutable = {k: v for k, v in variables.items()}
        opt_state = tx.init(params)
        embed_state = {}
        if sparse_embed:
            from distributeddeeplearningspark_tpu.train.embed import init_embed_state

            embed_state = init_embed_state(sparse_embed, params)
        return TrainState.create(params=params, opt_state=opt_state, mutable=mutable,
                                 rng=state_rng, embed_state=embed_state)

    from distributeddeeplearningspark_tpu.parallel.plan import traced_on

    init_fn = traced_on(init_fn, mesh)  # model.init traces the ops too
    abstract = jax.eval_shape(init_fn, init_rng)
    if plan is not None:
        shardings = plan.state_shardings(abstract, mesh)
    else:
        shardings = state_shardings(abstract, mesh, rules)
    state = jax.jit(init_fn, out_shardings=shardings)(init_rng)
    return state, shardings
