"""The verdicts behind ``correct`` that need the trainer: compile count and
path, placement (both copied from ``chip_smoke.check_run``), and agreement
with the configuration's plain reference."""

from __future__ import annotations

import math

MOSAIC_CALL = "tpu_custom_call"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def compile_failures(ledger: dict, late_compiles: int) -> list[str]:
    failures = []
    if (ledger.get("compiles") != 1 or ledger.get("flagged_recompiles")
            or not ledger.get("aot")):
        failures.append(f"the train step must compile once on the AOT path "
                        f"and stay there: {ledger}")
    if late_compiles:
        failures.append(f"{late_compiles} backend compile(s) after the "
                        f"window opened")
    return failures


def placement(step_fn, *, batch_size: int, devices: list
              ) -> tuple[list[str], dict]:
    """Every device of the session's mesh holds part of the state and an equal share of the
    rows of every batch array, read from the executable's own dispatch key
    (the shapes and shardings of the very arrays ``fit`` passed in)."""
    import jax

    failures: list[str] = []
    if step_fn is None or not step_fn.executables():
        return ["no compiled train step to inspect"], {}
    n_local = len(devices)
    (key, compiled), = step_fn.executables()
    treedef, sigs, shardings = key
    _, batch_sigs = jax.tree_util.tree_unflatten(treedef, list(sigs))
    placed = jax.tree_util.tree_unflatten(treedef, list(shardings))
    for path, sh in jax.tree_util.tree_leaves_with_path(placed):
        if len(sh.device_set) != n_local:
            failures.append(f"{jax.tree_util.keystr(path)} lives on "
                            f"{len(sh.device_set)} of {n_local} devices")
    for name, (shape, _) in batch_sigs.items():
        rows = placed[1][name].shard_shape(shape)[0]
        if rows * n_local != batch_size:
            failures.append(f"batch[{name!r}] holds {rows} rows a device, "
                            f"want {batch_size // n_local}")
    for d in devices:
        s = d.memory_stats()
        if s is not None and not s.get("bytes_in_use", 0) > 0:
            failures.append(f"{d} holds no bytes: {s}")
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    facts = {"memory_analysis_bytes": {
        k: int(getattr(ma, k + "_size_in_bytes", 0))
        for k in ("temp", "argument", "output", "alias", "generated_code")},
             "mosaic_custom_calls": text.count(MOSAIC_CALL),
             "collectives": {c: text.count(f" {c}(") + text.count(f" {c}-start(")
                             for c in COLLECTIVES},
             "batch_arrays": {k: list(v[0]) for k, v in batch_sigs.items()}}
    return failures, facts


def reference(config_mod, reference_mod, cfg: dict, built: dict, state,
              sample: dict) -> tuple[list[str], dict]:
    """Loss and flattened gradient of the program's model against the plain
    reference's, same parameters, same seeded sample, on one device."""
    import jax
    import numpy as np

    dev = jax.local_devices()[0]
    params = jax.device_put(jax.device_get(state.params), dev)
    mutable = jax.device_put(jax.device_get(dict(state.mutable or {})), dev)
    batch = jax.device_put(sample, dev)

    # mutable state and batch go in as arguments, not as constants of the
    # traced program: the executables then depend on shapes alone, and every
    # run after a checkout's first finds them in the compile cache
    def got_fn(p, m, b):
        return config_mod.program_loss(built["model"], built["loss"], p, m, b)

    def want_fn(p, m, b):
        return reference_mod.loss(p, m, b, cfg)

    got_loss, got_grad = jax.jit(jax.value_and_grad(got_fn))(
        params, mutable, batch)
    want_loss, want_grad = jax.jit(jax.value_and_grad(want_fn))(
        params, mutable, batch)
    diff = sum(float(np.sum((np.asarray(a, np.float64)
                             - np.asarray(b, np.float64)) ** 2))
               for a, b in zip(jax.tree.leaves(got_grad),
                               jax.tree.leaves(want_grad)))
    norm = sum(float(np.sum(np.asarray(b, np.float64) ** 2))
               for b in jax.tree.leaves(want_grad))
    rel = math.sqrt(diff / norm) if norm > 0 else math.inf
    got_loss, want_loss = float(got_loss), float(want_loss)
    tol = cfg["check"]
    facts = {"loss_program": got_loss, "loss_reference": want_loss,
             "grad_rel_err": rel, "grad_norm_reference": math.sqrt(norm),
             "examples": int(next(iter(sample.values())).shape[0])}
    failures = []
    if not math.isfinite(got_loss) or \
            abs(got_loss - want_loss) > tol["loss_abs_tol"]:
        failures.append(f"loss {got_loss} against the reference's "
                        f"{want_loss}: tolerance {tol['loss_abs_tol']}")
    if not rel <= tol["grad_rel_tol"]:
        failures.append(f"gradient differs from the reference's by {rel:.4f} "
                        f"of its norm: tolerance {tol['grad_rel_tol']}")
    return failures, facts
