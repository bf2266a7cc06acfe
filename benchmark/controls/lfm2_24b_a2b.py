"""Planted faults for the comparison that decides ``correct`` in the cell of
``lfm2_24b_a2b``: what the limits of ``check`` in the configuration's file
were read against, kept so that they can be read again.

    python3 benchmark/controls/lfm2_24b_a2b.py --seed <n> --seconds 40

runs ``lfm2_24b_a2b.fit_seg32k`` exactly as ``run.py`` does (same runner,
same window, same last line) and, where the runner compares the trained
state with the reference (``harness/checks.reference``, whose verdict on the
sound program the runner gets unchanged), makes THAT comparison again for
the sound program and once for every fault below, each time with the fault
standing in for the configuration's ``program_loss``: same trained
parameters and bias, same window of the feed, same limits, the reference
computed once. One ``# control:`` line a fault, with every TERM of the
compared scalar on both sides (``terms``: program, reference, the difference
of their logarithms), and ``benchmark/out/controls/lfm2-seed<n>.json``. A
fault that comes out ``correct`` is one the comparison cannot see.

``taps_cross_documents``        the program's short convolution given no
                                segment ids: its taps read across boundaries
``attention_crosses_documents`` the program's attention given none: every
                                query reads its whole causal prefix
``positions_not_restarted``     the program with rotary positions counted
                                from the window's start. NOT a fault of the
                                function: a rotary product depends on the
                                difference of two positions, and inside a
                                document that is the same either way; kept to
                                show that the comparison says so
``selection_without_bias``      the program's router choosing a token's
                                experts on the score alone where the bias is
                                not zero (in the model and in the probe)
``drop_assignments``            the program with ONE IN SIXTEEN of the
                                assignments to held experts dropped (the last
                                in expert order, forward and backward)
``taps_gradient_lost``          the program, no gradient reaching the taps
``e4m3_weights``                the reference with every weight matrix
                                rounded to an 8-bit float's 3 bits of mantissa
                                (weights only, a lower bound on 8-bit
                                products): the nearest precision below bf16

``--per-leaf 1`` adds, for the sound program and every fault, each leaf's
squared difference and the reference's squared norm as the harness sums them.
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

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm2_24b_a2b.fit_seg32k"
DROPPED_SHARE = 16   # ``drop_assignments`` drops one in this many
ALL = ("taps_cross_documents", "attention_crosses_documents",
       "positions_not_restarted", "selection_without_bias",
       "drop_assignments", "taps_gradient_lost", "e4m3_weights")


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _map_named(fn, params):
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, x: fn(_path(path), x), params)


def _round_e4m3(x):
    """``x`` with 3 bits of mantissa, as an 8-bit float (e4m3) scaled to the
    leaf's range would hold it, straight through for the gradient. In
    arithmetic: the TPU's compiler widens a float8 it has no unit for, and a
    cast there and back rounds nothing (PR 26)."""
    import jax
    import jax.numpy as jnp

    mantissa, exponent = jnp.frexp(x)            # |mantissa| in [0.5, 1)
    rounded = jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent)
    return x + jax.lax.stop_gradient(rounded.astype(x.dtype) - x)


def faults(config_mod, reference_mod, cfg: dict) -> dict:
    """name -> (a function ``(model, loss_fn, params, mutable, batch) -> (the
    compared scalar, its terms)``, a context that keeps a change to the
    program in place while it is traced)."""
    import jax
    import jax.numpy as jnp

    def program(change):
        def fn(model, loss_fn, params, mutable, batch):
            terms = config_mod.parts(model, loss_fn, change(params), mutable,
                                     batch)
            return config_mod.compared(terms), terms
        return fn

    def reference(change):
        def fn(model, loss_fn, params, mutable, batch):
            del model, loss_fn
            terms = reference_mod.parts(change(params), mutable, batch, cfg)
            return reference_mod.compared(terms, cfg), terms
        return fn

    def swapped(module, name, make):
        """A context in which ``module.name`` is ``make(the sound one)``; the
        program looks the name up whenever it is traced."""
        @contextlib.contextmanager
        def planted():
            sound = getattr(module, name)
            setattr(module, name, make(sound))
            try:
                yield
            finally:
                setattr(module, name, sound)
        return planted

    from distributeddeeplearningspark_tpu.models import hybrid_decoder, moe

    in_the_program = {
        "taps_cross_documents": swapped(
            hybrid_decoder, "gated_short_conv",
            lambda sound: lambda bcx, taps, seg: sound(bcx, taps, None)),
        "attention_crosses_documents": swapped(
            hybrid_decoder, "dot_product_attention",
            lambda sound: lambda q, k, v, **kw: sound(
                q, k, v, **{**kw, "segment_ids": None})),
        "positions_not_restarted": swapped(
            hybrid_decoder, "document_positions",
            lambda sound: lambda seg: jnp.broadcast_to(
                jnp.arange(seg.shape[1], dtype=jnp.int32), seg.shape)),
        "selection_without_bias": swapped(
            moe, "_held_experts",
            lambda sound: lambda xf, router, w_gate, w_up, w_down, first,
            bias=None, **kw: sound(xf, router, w_gate, w_up, w_down, first,
                                   None, **kw)),
        "drop_assignments": swapped(
            moe, "_zero_past",
            lambda sound: lambda a, used: sound(
                a, used - used // DROPPED_SHARE)),
    }
    same = lambda p: p
    out = {"sound": (program(same), contextlib.nullcontext),
           "reference": (reference(same), contextlib.nullcontext)}
    out.update({name: (program(same), planted)
                for name, planted in in_the_program.items()})
    out["taps_gradient_lost"] = (program(lambda p: _map_named(
        lambda n, x: jax.lax.stop_gradient(x) if n.endswith("/taps") else x,
        p)), contextlib.nullcontext)
    out["e4m3_weights"] = (reference(lambda p: _map_named(
        lambda n, x: _round_e4m3(x) if "norm" not in n else x, p)),
        contextlib.nullcontext)
    return out


def compare(got, want, tol: dict, *, leaves: bool = False) -> dict:
    """``checks.reference``'s verdict from ``(scalar, terms, gradient)`` of
    both sides: the same sums in float64, the same two limits, the same
    words."""
    import jax
    import numpy as np

    (got_loss, got_terms, got_grad), (want_loss, want_terms, want_grad) = (
        got, want)
    per_leaf = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_grad),
                            jax.tree.leaves(want_grad)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        per_leaf[_path(path)] = [float(np.sum((a - b) ** 2)),
                                 float(np.sum(b ** 2))]
    diff = sum(d for d, _ in per_leaf.values())
    norm = sum(n for _, n in per_leaf.values())
    rel = math.sqrt(diff / norm) if norm > 0 else math.inf
    failures = []
    if not math.isfinite(got_loss) or \
            abs(got_loss - want_loss) > tol["loss_abs_tol"]:
        failures.append(f"loss {got_loss} against the reference's "
                        f"{want_loss}: tolerance {tol['loss_abs_tol']}")
    if not rel <= tol["grad_rel_tol"]:
        failures.append(f"gradient differs from the reference's by {rel:.4f} "
                        f"of its norm: tolerance {tol['grad_rel_tol']}")
    out = {"correct": not failures, "failures": failures,
           "loss_program": got_loss, "loss_reference": want_loss,
           "loss_abs_err": abs(got_loss - want_loss), "grad_rel_err": rel,
           "terms": {k: [v, want_terms.get(k)] + (
               [math.log(v / want_terms[k])]
               if want_terms.get(k, 0) > 0 and v > 0 else [])
               for k, v in got_terms.items()}}
    if leaves:
        out["per_leaf"] = per_leaf
    return out


def wrap_reference(checks, *, names, leaves: bool, out: dict):
    """``checks.reference`` with the controls behind it; the sound program's
    verdict is what the runner gets, unchanged."""
    sound_reference = checks.reference

    def reference(config_mod, reference_mod, cfg, built, state, sample):
        import jax
        import numpy as np

        verdict = sound_reference(config_mod, reference_mod, cfg, built,
                                  state, sample)
        out["harness"] = {"failures": verdict[0], **verdict[1]}
        print(f"# control: {json.dumps({'harness': out['harness']})}",
              flush=True)
        bias = jax.tree.leaves(dict(state.mutable or {}))
        out["router_bias_abs_max"] = max(
            (float(abs(jax.device_get(b)).max()) for b in bias), default=0.0)
        dev = jax.local_devices()[0]
        params = jax.device_put(jax.device_get(state.params), dev)
        mutable = jax.device_put(
            jax.device_get(dict(state.mutable or {})), dev)
        batch = jax.device_put(sample, dev)
        todo = faults(config_mod, reference_mod, cfg)

        def evaluate(name):
            """``(scalar, terms, gradient)`` of ``name``, off the device."""
            fn, planted = todo[name]
            with planted():
                (value, terms), grad = jax.jit(jax.value_and_grad(
                    lambda p, m, b: fn(built["model"], built["loss"], p, m,
                                       b), has_aux=True))(
                    params, mutable, batch)
                # (np.array copies: no device buffer stays alive behind a
                # view while the next fault is computed)
                return (float(value),
                        {k: float(v) for k, v in terms.items()},
                        jax.tree.map(np.array, grad))

        want = evaluate("reference")
        for name in ("sound", *names):
            t0 = time.perf_counter()
            out[name] = {**compare(evaluate(name), want, cfg["check"],
                                   leaves=leaves),
                         "seconds": time.perf_counter() - t0}
            print(f"# control: {json.dumps({name: out[name]})}", flush=True)
        return verdict

    checks.reference = reference


def run(seed: int, seconds: float, names=ALL, *, leaves: bool = False,
        master: str = "tpu", root: str = ROOT) -> dict:
    """One run of the cell with the controls behind its comparison."""
    from benchmark.harness import checks, runner

    out: dict = {"seed": seed, "seconds": seconds}
    sound_reference = checks.reference
    wrap_reference(checks, names=names, leaves=leaves, out=out)
    try:
        result = runner.measure(root, CELL, seed=seed, seconds=seconds,
                                trace=False, t_process=T_PROCESS,
                                master=master)
    finally:
        checks.reference = sound_reference
    out["trained_to_step"] = result["facts"]["window"].get("last_step")
    # the step's counters lap by lap, warm-up included: is the held share of
    # the assignments where balanced routing puts it, and does it stay there
    from distributeddeeplearningspark_tpu import telemetry
    out["laps"] = [
        {"step": e["step"], **{k: e["metrics"].get(k) for k in (
            "loss", "moe_rows_held_share", "moe_load_max_over_mean",
            "router_bias_abs_max", "attn_pairs_share")}}
        for e in telemetry.read_events(os.path.join(
            result["facts"]["out_dir"], "telemetry"))
        if e.get("kind") == "step_metrics"]
    print(f"# controls: {json.dumps({'laps': out['laps']})}", flush=True)
    out["result"] = {k: result[k] for k in ("correct", "attempted", "failed",
                                            "metrics", "device")}
    path = os.path.join(root, "benchmark", "out", "controls",
                        f"lfm2-seed{seed}.json")
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
    args = p.parse_args()

    sys.path.insert(0, ROOT)
    from benchmark.harness import runner

    names = [n for n in args.faults.split(",") if n]
    unknown = set(names) - set(ALL)
    if unknown:
        p.error(f"no fault named {sorted(unknown)}; there are {ALL}")
    try:
        out = run(args.seed, args.seconds, names, leaves=bool(args.per_leaf))
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
