"""Row-sparse embedding training — touched-rows-only table updates.

Why this exists: with the generic train step nearly all of DLRM's device
time is full-table work — autodiff's dense scatter-add gradient over the
[2.6M, 64] fused table, full-table optimizer reads and writes, and XLA
layout copies of the whole table — while the batch's own compute is a
sliver. A Criteo step touches at most ``batch × 26`` rows (~8% of the
table), so updating every row every step is wasted HBM bandwidth. The reference's
parameter-server-style table distribution gets row sparsity implicitly (only
gathered rows ship gradients, SURVEY.md §2 'Wide&Deep/DLRM'); this module is
the TPU-native equivalent, and the same trick torchrec fuses into its
sharded embedding bags.

Scheme (all static-shaped, fully jittable, GSPMD-shardable):

1. **Gather outside autodiff**: rows are looked up *before* the forward pass
   and injected into the model through its ``overrides`` kwarg, so autodiff
   produces gradients w.r.t. the *gathered vectors* [K, D] — never a dense
   [V, D] table gradient. The table leaves handed to the loss are poisoned
   with NaN so a model that ignores the injection (wrong spec name, missing
   plumbing) fails loudly on its first step instead of silently reverting to
   dense-gradient traffic with an untrained table.
2. **Row-wise AdaGrad** (the torchrec ROWWISE_ADAGRAD): one accumulator
   scalar per row; ``unique``(size=K) + ``segment_sum`` fold duplicate ids
   within the batch into one deterministic per-row gradient, then a
   ``scatter-add`` applies the update to touched rows only. Unused `unique`
   padding slots carry the out-of-bounds sentinel ``V`` and are dropped by
   the scatter.

Traffic per step: O(K·D + K) instead of O(V·D) — on the bench shape ~54 MB
of row traffic vs ~2.6 GB of full-table traffic (plus the layout copies it
provokes). Composes with the ``expert``-axis row sharding: gather/scatter on
a row-sharded table lower to the same index/result exchange as the forward
lookup.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import optax

from distributeddeeplearningspark_tpu.train.state import TrainState

#: embed_state leaf name; dlrm_rules ships a rank-1 sharding rule for it.
ROW_ACCUM = "row_accum"


@dataclasses.dataclass(frozen=True)
class SparseEmbedSpec:
    """One sparsely-trained embedding table.

    ``name`` keys the model's ``overrides`` dict and the state's
    ``embed_state`` entry; ``param_path`` is the '/'.joined params path of
    the table array; ``ids_fn(batch)`` returns the integer row ids the step
    will gather (any shape; vectors come back as ``ids.shape + (D,)``).
    """

    name: str
    param_path: str
    ids_fn: Callable[[dict[str, Any]], jax.Array]
    lr: float = 1e-2
    eps: float = 1e-8

    def path_tuple(self) -> tuple[str, ...]:
        return tuple(self.param_path.split("/"))


def _get_path(tree: Any, path: tuple[str, ...]) -> Any:
    for k in path:
        tree = tree[k]
    return tree


def _set_path(tree: Any, path: tuple[str, ...], value: Any) -> Any:
    if not path:
        return value
    return {**tree, path[0]: _set_path(tree[path[0]], path[1:], value)}


def dense_trainable(specs: Sequence[SparseEmbedSpec]) -> Callable[[str], bool]:
    """Predicate for ``optim.masked``: everything but the sparse tables.

    The main optimizer must not touch the tables — a dense AdaGrad "no-op"
    update still reads and writes the full [V, D] table and its moments,
    which is exactly the traffic this module exists to eliminate.
    """
    paths = {s.param_path for s in specs}
    return lambda path: path not in paths


def rowwise_adagrad_update(
    table: jax.Array,
    accum: jax.Array,
    ids: jax.Array,
    d_vecs: jax.Array,
    *,
    lr: float,
    eps: float = 1e-8,
) -> tuple[jax.Array, jax.Array]:
    """Apply row-wise AdaGrad to the rows named by ``ids`` only.

    ``accum`` is [V] f32 (one scalar per row: the running mean-square of that
    row's gradient — torchrec's ROWWISE_ADAGRAD, 1/D the state of full
    AdaGrad). Duplicate ids are first combined by ``segment_sum``, so the
    result is deterministic and equals the dense update that a full gradient
    with those row sums would produce.
    """
    v, d = table.shape
    flat = ids.reshape(-1)
    k = flat.size
    g = d_vecs.reshape(k, d).astype(jnp.float32)
    # sorted unique ids padded with the OOB sentinel `v`; inverse indices
    # fold duplicates into one segment per distinct row
    uniq, inv = jnp.unique(flat, return_inverse=True, size=k, fill_value=v)
    # The pad slots all carry the same sentinel, but `unique_indices=True`
    # below promises XLA collision-free indices — duplicate indices under
    # that hint are documented UB, and relying on mode="drop" to discard
    # them before the hint matters is backend-dependent (ADVICE r2). Spread
    # the pads over v+0, v+1, ... : still OOB (every pad ≥ v), still sorted
    # (pads are the trailing run and arange increases), now genuinely unique.
    uniq = jnp.where(uniq == v, v + jnp.arange(k, dtype=uniq.dtype), uniq)
    row_g = jax.ops.segment_sum(g, inv.reshape(-1), num_segments=k)  # [K, D]
    acc_rows = jnp.take(accum, uniq, axis=0, mode="fill", fill_value=0.0)
    new_acc_rows = acc_rows + jnp.mean(row_g * row_g, axis=1)
    upd = (-lr * row_g / jnp.sqrt(new_acc_rows + eps)[:, None]).astype(table.dtype)
    # sentinel rows: row_g == 0 → upd == 0, and mode="drop" discards them.
    # unique() guarantees sorted, collision-free indices — assert both to XLA
    # so the TPU scatter emitter parallelizes instead of serializing updates
    # under collision-safety assumptions.
    new_table = table.at[uniq].add(
        upd, mode="drop", unique_indices=True, indices_are_sorted=True)
    new_accum = accum.at[uniq].set(
        new_acc_rows, mode="drop", unique_indices=True, indices_are_sorted=True)
    return new_table, new_accum


def init_embed_state(
    specs: Sequence[SparseEmbedSpec], params: Any
) -> dict[str, Any]:
    """Zero row accumulators, shaped/keyed for TrainState.embed_state."""
    out: dict[str, Any] = {}
    for s in specs:
        table = _get_path(params, s.path_tuple())
        out[s.name] = {ROW_ACCUM: jnp.zeros((table.shape[0],), jnp.float32)}
    return out


def make_sparse_embed_train_step(
    apply_fn: Callable,
    tx: optax.GradientTransformation,
    loss_fn: Callable,
    specs: Sequence[SparseEmbedSpec],
    *,
    rng_names: Sequence[str] = ("dropout",),
) -> Callable[[TrainState, dict[str, Any]], tuple[TrainState, dict[str, Any]]]:
    """Variant of :func:`..step.make_train_step` with sparse table updates.

    ``tx`` MUST be masked off the table paths (wrap with ``optim.masked(tx,
    dense_trainable(specs))``) — :class:`..trainer.Trainer` does this when
    given ``sparse_embed`` specs. The model must accept an ``overrides``
    kwarg routing gathered vectors to its embedding modules (see
    ``models/dlrm.py``). Mutable collections and accum_steps are not
    supported here (recommender models use neither).
    """
    specs = tuple(specs)

    def train_step(state: TrainState, batch: dict[str, Any]):
        next_rng, step_rng = jax.random.split(jax.random.fold_in(state.rng, state.step))
        rngs = {name: jax.random.fold_in(step_rng, i) for i, name in enumerate(rng_names)}

        tables = {s.name: _get_path(state.params, s.path_tuple()) for s in specs}
        ids = {s.name: s.ids_fn(batch) for s in specs}
        vecs = {n: jnp.take(tables[n], ids[n], axis=0) for n in tables}

        # The loss must see the table rows ONLY through `vecs` (injected via
        # `overrides`), or autodiff materializes the dense [V, D] table grad
        # this module exists to avoid. That cannot be guaranteed passively —
        # a spec name the model does not consume would silently fall back to
        # the in-model lookup — so the table leaves handed to the loss are
        # poisoned with NaN: a model that reads them NaNs its loss/grad_norm
        # on step one (fail-loud), while a correctly-wired model never
        # touches them (their gradient is zero and the masked optimizer
        # ignores it).
        params_sg = state.params
        for s in specs:
            params_sg = _set_path(
                params_sg, s.path_tuple(), jnp.full_like(tables[s.name], jnp.nan)
            )

        def loss_of(params, vec_args):
            outputs = apply_fn(
                {"params": params}, batch, train=True, rngs=rngs, overrides=vec_args
            )
            loss, metrics = loss_fn(outputs, batch)
            return loss, metrics

        (_, metrics), (g_dense, g_vecs) = jax.value_and_grad(
            loss_of, argnums=(0, 1), has_aux=True
        )(params_sg, vecs)
        metrics = dict(metrics)

        # real (unpoisoned) params: optimizers read param values (weight
        # decay), and only the loss needed the poisoned view
        updates, new_opt_state = tx.update(g_dense, state.opt_state, state.params)
        # the masked tx emits zero updates for table leaves; XLA dead-code-
        # eliminates the table+0 adds because the scatter below overwrites them
        new_params = optax.apply_updates(state.params, updates)
        new_embed: dict[str, Any] = {}
        for s in specs:
            new_table, new_accum = rowwise_adagrad_update(
                tables[s.name],
                state.embed_state[s.name][ROW_ACCUM],
                ids[s.name],
                g_vecs[s.name],
                lr=s.lr,
                eps=s.eps,
            )
            new_params = _set_path(new_params, s.path_tuple(), new_table)
            new_embed[s.name] = {ROW_ACCUM: new_accum}

        metrics["grad_norm"] = optax.global_norm((g_dense, g_vecs))
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            rng=next_rng,
            embed_state=new_embed,
        )
        return new_state, metrics

    return train_step
