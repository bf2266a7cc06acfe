"""Planted faults for the comparison that decides ``correct`` in the cells of
``keye_vl2_30b_a3b``: what the limits of ``check`` in the configuration's
file were read against, kept so that they can be read again.

    python3 benchmark/controls/keye_vl2_30b_a3b.py --seed <n> --seconds 40

runs ``keye_vl2_30b_a3b.fit_s8k`` exactly as ``run.py`` does (same runner,
same window, same last line) and, where the runner compares the trained
state with the reference (``harness/checks.reference``), repeats THAT
comparison once for every fault below, each time with the fault standing in
for the configuration's ``program_loss``: same trained parameters, same
window of the feed, same limits. One ``# control:`` line a fault, and
``benchmark/out/controls/seed<n>.json``. A fault that comes out ``correct``
is one the comparison cannot see.

A fault is a function with ``program_loss``'s signature, and for one of
them a change to the program that stays in place while it is compared:

``drop_assignments``   the program with ONE IN SIXTEEN of the assignments to
                       held experts dropped (the last in expert order: their
                       rows are zeroed where the layer zeroes the rows past
                       the ones in use, forward and backward). What a
                       capacity would do at this traffic's imbalance is ten
                       times that
``e4m3_weights``       the reference with every weight matrix rounded to an
                       8-bit float's 3 bits of mantissa (e4m3; weights only,
                       so a lower bound on 8-bit products): the nearest
                       precision below the configuration's bf16
``e4m3_experts``       the program with only the experts' kernels so rounded
``dense_prefix``       the reference attending to the whole causal prefix
                       instead of the indexer's selection
``wq_gradient_lost``   the program, no gradient reaching ``wq``: what a wrong
                       ``dsa_attend_bwd_dq`` would do to that leaf, at the least

``--per-leaf 1`` adds, for the sound program and every fault, each leaf's
squared gradient norm and squared difference as the harness sums them (a leaf
named in the configuration's ``check.grad_leaf_weights`` times its weight
squared; the weights were chosen from these readings), and ``--flips 1`` the
share of selected (query, key) pairs on which the kernels' selection, from
bf16 operands, differs from the reference's on the same input, layer by layer.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "keye_vl2_30b_a3b.fit_s8k"
DROPPED_SHARE = 16   # ``drop_assignments`` drops one in this many


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _map_named(fn, params):
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, x: fn(_path(path), x), params)


def _round_e4m3(x):
    """``x`` with 3 bits of mantissa, as an 8-bit float (e4m3) scaled to the
    leaf's range would hold it (its 4 bits of exponent reach 2**-15 of the
    largest entry, far below any entry that matters), straight through for
    the gradient, which is then the gradient AT the rounded weights. In
    arithmetic: the TPU's compiler widens a float8 it has no unit for, and a
    cast there and back rounds nothing (PR 26's first reading of this
    control was of the unrounded reference)."""
    import jax
    import jax.numpy as jnp

    mantissa, exponent = jnp.frexp(x)            # |mantissa| in [0.5, 1)
    rounded = jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent)
    return x + jax.lax.stop_gradient(rounded.astype(x.dtype) - x)


def _is_matrix(name: str) -> bool:
    return "norm" not in name


def faults(config_mod, reference_mod, cfg: dict, seq_len: int) -> dict:
    import jax

    def program(change):
        def fn(model, loss_fn, params, mutable, batch):
            return config_mod.program_loss(model, loss_fn, change(params),
                                           mutable, batch)
        return fn

    def reference(change, cfg=cfg):
        def fn(model, loss_fn, params, mutable, batch):
            del model, loss_fn
            return reference_mod.loss(change(params), mutable, batch, cfg)
        return fn

    @contextlib.contextmanager
    def assignments_dropped():
        from distributeddeeplearningspark_tpu.models import moe

        sound = moe._zero_past
        moe._zero_past = lambda a, used: sound(
            a, used - used // DROPPED_SHARE)
        try:
            yield
        finally:
            moe._zero_past = sound

    dense_cfg = {**cfg, "sa_config": {**cfg["sa_config"], "topk": seq_len}}
    same = lambda p: p
    return {
        "drop_assignments": (program(same), assignments_dropped),
        "e4m3_weights": (reference(lambda p: _map_named(
            lambda n, x: _round_e4m3(x) if _is_matrix(n) else x, p)),
            contextlib.nullcontext),
        "e4m3_experts": (program(lambda p: _map_named(
            lambda n, x: _round_e4m3(x) if "/moe/w_" in n else x, p)),
            contextlib.nullcontext),
        "dense_prefix": (reference(same, dense_cfg), contextlib.nullcontext),
        "wq_gradient_lost": (program(lambda p: _map_named(
            lambda n, x: jax.lax.stop_gradient(x)
            if n == "layers/wq/kernel" else x, p)), contextlib.nullcontext),
    }


def per_leaf(loss_fn, want, params, mutable, batch) -> dict:
    """``loss_fn``'s loss, and per leaf ``[squared difference from want,
    want's squared norm]`` in float64, as ``checks.reference`` sums them."""
    import jax
    import numpy as np

    loss, grad = jax.jit(jax.value_and_grad(loss_fn))(params, mutable, batch)
    out = {}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grad),
                            jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        out[_path(path)] = [float(np.sum((g - w) ** 2)), float(np.sum(w ** 2))]
    return {"loss": float(loss), "leaves": out}


def selection_flips(reference_mod, cfg: dict, params, ids) -> list[dict]:
    """Layer by layer, on the reference's own float32 input to that layer:
    selected pairs on which the kernels' selection (index scores from bf16
    operands, the exact select) differs from the reference's ``lax.top_k``."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.ops import indexed_attention as ia

    r = reference_mod
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    sa = cfg["sa_config"]
    topk = sa["topk"]

    @jax.jit
    def one_layer(x, lp):
        with jax.default_matmul_precision("highest"):
            row = x[0]
            h = r._rms(row, lp["attention_norm"]["scale"], eps)
            qi = r._rotary(jnp.einsum("sh,hnd->snd", h,
                                      lp["index_wq"]["kernel"]), theta)
            ki = r._layer_norm(h @ lp["index_wk"]["kernel"],
                               lp["index_k_norm"], eps)
            ki = r._rotary(ki[:, None, :], theta)[:, 0, :]
            wi = (h @ lp["index_w"]["kernel"]) * (
                sa["indexer_num_heads"] ** -0.5 * sa["indexer_head_dim"] ** -0.5)
            s = row.shape[0]
            n = min(r.BLOCK, s)
            want = jax.lax.map(
                lambda t0: r.selection(
                    jax.lax.dynamic_slice_in_dim(qi, t0, n), ki,
                    jax.lax.dynamic_slice_in_dim(wi, t0, n), t0, topk)[1],
                jnp.arange(0, s, n)).reshape(s, s)
            x_next = r.layer(x, lp, cfg)[0]
        bf = jnp.bfloat16
        scores = ia._index_scores_kernel(
            qi.astype(bf).transpose(1, 0, 2)[None], ki.astype(bf)[None],
            wi[None], block=min(ia.DEFAULT_BLOCK, s),
            interpret=jax.default_backend() != "tpu")
        got = ia.select_topk(scores, topk)[0][0] != 0
        return x_next, (jnp.sum(got != want), jnp.sum(want))

    p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x = p["token_embed"]["embedding"][ids]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        x, (differ, selected) = one_layer(
            x, jax.tree.map(lambda t: t[i], p["layers"]))
        out.append({"layer": i, "differ_pairs": int(differ),
                    "selected_pairs": int(selected),
                    "share_of_selected": int(differ) / int(selected)})
    return out


def wrap_reference(checks, *, names, leaves: bool, flips: bool,
                   seq_len: int, out: dict):
    """``checks.reference`` with the controls behind it; the sound program's
    verdict is what the runner gets, unchanged."""
    sound_reference = checks.reference

    def reference(config_mod, reference_mod, cfg, built, state, sample):
        import jax

        sound = sound_reference(config_mod, reference_mod, cfg, built, state,
                                sample)
        out["sound"] = {"failures": sound[0], **sound[1]}
        print(f"# control: {json.dumps({'sound': out['sound']})}", flush=True)
        todo = faults(config_mod, reference_mod, cfg, seq_len)
        todo = {k: todo[k] for k in names}
        for name, (fn, planted) in todo.items():
            t0 = time.perf_counter()
            with planted():
                fails, facts = sound_reference(
                    types.SimpleNamespace(program_loss=fn), reference_mod,
                    cfg, built, state, sample)
            out[name] = {"correct": not fails, "failures": fails, **facts,
                         "loss_abs_err": abs(facts["loss_program"]
                                             - facts["loss_reference"]),
                         "seconds": time.perf_counter() - t0}
            print(f"# control: {json.dumps({name: out[name]})}", flush=True)
        if leaves or flips:
            dev = jax.local_devices()[0]
            params = jax.device_put(jax.device_get(state.params), dev)
            batch = jax.device_put(sample, dev)
        if flips:
            out["selection_flips"] = selection_flips(
                reference_mod, cfg, params, batch["input_ids"])
            print(f"# control: {json.dumps(out['selection_flips'])}",
                  flush=True)
        if leaves:
            import numpy as np

            def want_fn(p, m, b):
                return reference_mod.loss(p, m, b, cfg)

            want_loss, want = jax.jit(jax.value_and_grad(want_fn))(
                params, {}, batch)
            want = jax.tree.map(np.asarray, want)   # off the device
            out["per_leaf"] = {"loss_reference": float(want_loss)}
            sound_fn = (config_mod.program_loss, contextlib.nullcontext)
            for name, (fn, planted) in {"sound": sound_fn, **todo}.items():
                with planted():
                    out["per_leaf"][name] = per_leaf(
                        lambda p, m, b, fn=fn: fn(
                            built["model"], built["loss"], p, m, b),
                        want, params, {}, batch)
                print(f"# per_leaf: {json.dumps({name: out['per_leaf'][name]})}",
                      flush=True)
        return sound

    checks.reference = reference


ALL = ("drop_assignments", "e4m3_weights", "e4m3_experts", "dense_prefix",
       "wq_gradient_lost")


def run(seed: int, seconds: float, names=ALL, *, leaves: bool = False,
        flips: bool = False, master: str = "tpu") -> dict:
    """One run of the cell with the controls behind its comparison."""
    from benchmark.harness import checks, runner

    traffic = runner.resolve_cell(ROOT, CELL)["traffic"]
    out: dict = {"seed": seed, "seconds": seconds}
    sound_reference = checks.reference
    wrap_reference(checks, names=names, leaves=leaves, flips=flips,
                   seq_len=traffic["seq_len"], out=out)
    try:
        result = runner.measure(ROOT, CELL, seed=seed, seconds=seconds,
                                trace=False, t_process=T_PROCESS,
                                master=master)
    finally:
        checks.reference = sound_reference
    out["trained_to_step"] = result["facts"]["window"].get("last_step")
    out["result"] = {k: result[k] for k in ("correct", "attempted", "failed",
                                            "metrics", "device")}
    path = os.path.join(ROOT, "benchmark", "out", "controls",
                        f"seed{seed}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--faults", default=",".join(ALL))
    p.add_argument("--per-leaf", type=int, choices=(0, 1), default=0)
    p.add_argument("--flips", type=int, choices=(0, 1), default=0)
    args = p.parse_args()

    sys.path.insert(0, ROOT)
    from benchmark.harness import runner

    names = [n for n in args.faults.split(",") if n]
    unknown = set(names) - set(ALL)
    if unknown:
        p.error(f"no fault named {sorted(unknown)}; there are {ALL}")
    try:
        out = run(args.seed, args.seconds, names, leaves=bool(args.per_leaf),
                  flips=bool(args.flips))
    except runner.Refused as e:
        for reason in e.args[0]:
            print(f"controls: refused: {reason}", file=sys.stderr)
        return 1
    seen = {n: out[n]["correct"] for n in names if n in out}
    print(f"# controls: {json.dumps({'came_out_correct': seen})}", flush=True)
    print(json.dumps(out["result"]), flush=True)
    return 0 if math.isfinite(out["sound"]["grad_rel_err"]) else 1


if __name__ == "__main__":
    sys.exit(main())
