"""Planted faults for the comparison that decides ``correct`` in the cell of
``laguna_xs2``: what the limits of ``check`` in the configuration's file
were read against, kept so that they can be read again.

    python3 benchmark/controls/laguna_xs2.py --seed <n> --seconds 40

runs ``laguna_xs2.fit_swa16k`` exactly as ``run.py`` does (same runner, same
window, same last line) and, where the runner compares the trained state
with the reference (``harness/checks.reference``, whose verdict on the sound
program the runner gets unchanged), makes THAT comparison again for the
sound program and once for every fault below, each time with the fault
standing in for the configuration's ``program_loss``: same trained
parameters, same window of the feed, same limits, the reference computed
once. One ``# control:`` line a fault, with every TERM of the compared
scalar on both sides (``terms``: program, reference, the difference of
their logarithms), and ``benchmark/out/controls/laguna-seed<n>.json``. A
fault that comes out ``correct`` is one the comparison cannot see. The
comparison itself (``compare``) and the 8-bit rounding are those of
``controls/lfm2_24b_a2b.py``, loaded from that file.

``window_513``                  the sliding layers' band one key wider
``window_511``                  ... one key narrower
``no_window_on_sliding``        the sliding layers causal over the whole row
``window_on_full``              the full layers inside the band of 512
``plain_rotary_on_full``        the full layers' rotary at theta 5e5 without
                                YaRN's frequencies
``no_attention_factor``         cos and sin of the full layers not scaled
``rotary_whole_head_on_full``   the full layers rotating all 128 dimensions
``theta_5e5_on_sliding``        the sliding layers' rotary at the full
                                layers' theta
``no_gate``                     no per-head output gate
``band_crosses_documents``      the sliding layers handed no segment ids
``positions_not_restarting``    positions counted from the window's start
``no_topk_renorm``              the weights of a token's 8 experts their
                                softmax probabilities, not renormalised
``routed_scale_1``              the routed part without the factor 2.5
``shared_expert_left_out``      the expert layers without their shared expert
``e4m3_weights``                the reference with every weight matrix
                                rounded to an 8-bit float's 3 bits of
                                mantissa (weights only, a lower bound on
                                8-bit products): the nearest precision below
                                bf16

Each fault but the last is planted in the PROGRAM, by a name the model (and
the probes) look up when they are traced, so it reaches the timed pass and
the probes alike. ``--per-leaf 1`` adds, for the sound program and every
fault, each leaf's squared difference and the reference's squared norm as
the harness sums them.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "laguna_xs2.fit_swa16k"
ALL = ("window_513", "window_511", "no_window_on_sliding", "window_on_full",
       "plain_rotary_on_full", "no_attention_factor",
       "rotary_whole_head_on_full", "theta_5e5_on_sliding", "no_gate",
       "band_crosses_documents", "positions_not_restarting",
       "no_topk_renorm", "routed_scale_1", "shared_expert_left_out",
       "e4m3_weights")
COUNTERS = ("loss", "lm_loss", "moe_aux", "moe_rows_held_share",
            "moe_load_max_over_mean", "attn_pairs_share",
            "window_pairs_share", "window_blocks_walked_share")


def _sibling():
    """``controls/lfm2_24b_a2b.py``: its ``compare``, ``_round_e4m3`` and
    ``_map_named`` serve here as they are."""
    from benchmark.harness import runner

    return runner.load_module(os.path.join(HERE, "lfm2_24b_a2b.py"))


def faults(config_mod, reference_mod, cfg: dict) -> dict:
    """name -> (a function ``(model, loss_fn, params, mutable, batch) -> (the
    compared scalar, its terms)``, a context that keeps a change to the
    program in place while it is traced)."""
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder, moe

    sibling = _sibling()
    matrices = ("kernel", "embedding", "lm_head", "router", "w_gate", "w_up",
                "w_down")
    window = cfg["sliding_window"]

    def program(model, loss_fn, params, mutable, batch):
        terms = config_mod.parts(model, loss_fn, params, mutable, batch)
        return config_mod.compared(terms), terms

    def reference(change=lambda p: p):
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

    def attention(change):
        """``dot_product_attention`` with its ``(window, segment ids)``
        changed by ``change``."""
        def make(sound):
            def fn(q, k, v, **kw):
                kw["window"], kw["segment_ids"] = change(
                    kw.get("window"), kw.get("segment_ids"))
                return sound(q, k, v, **kw)
            return fn
        return swapped(hybrid_decoder, "dot_product_attention", make)

    def rotary(sliding, **fields):
        """``rotary_of`` of one layer kind from a configuration with
        ``fields`` changed."""
        return swapped(hybrid_decoder, "rotary_of", lambda sound: (
            lambda c, s: sound(dataclasses.replace(c, **fields)
                               if s == sliding else c, s)))

    def routing(**kw):
        return swapped(moe, "_held_experts", lambda sound: (
            lambda *a, **k: sound(*a, **{**k, **kw})))

    def from_the_start(seg):
        return jnp.broadcast_to(jnp.arange(seg.shape[1], dtype=jnp.int32),
                                seg.shape)

    band = lambda change: attention(
        lambda w, seg: (change(w), seg) if w is not None else (w, seg))
    in_the_program = {
        "window_513": band(lambda w: w + 1),
        "window_511": band(lambda w: w - 1),
        "no_window_on_sliding": band(lambda w: None),
        "window_on_full": attention(
            lambda w, seg: (window if w is None else w, seg)),
        "plain_rotary_on_full": rotary(False, yarn_factor=1.0),
        "no_attention_factor": rotary(False, rope_attention_factor=1.0),
        "rotary_whole_head_on_full": rotary(False, partial_rotary_factor=1.0),
        "theta_5e5_on_sliding": rotary(
            True, sliding_rope_theta=float(cfg["rope_parameters"][
                "full_attention"]["rope_theta"])),
        "no_gate": swapped(hybrid_decoder, "head_gate",
                           lambda sound: lambda o, g: o),
        "band_crosses_documents": attention(
            lambda w, seg: (w, None if w is not None else seg)),
        "positions_not_restarting": swapped(
            hybrid_decoder, "document_positions",
            lambda sound: from_the_start),
        "no_topk_renorm": routing(norm_topk=False),
        "routed_scale_1": routing(routed_scale=1.0),
        "shared_expert_left_out": swapped(
            hybrid_decoder, "RoutedExperts",
            lambda sound: lambda *a, **kw: sound(
                *a, **{**kw, "shared_size": 0})),
    }
    none = contextlib.nullcontext
    out = {"sound": (program, none), "reference": (reference(), none)}
    out.update({name: (program, planted)
                for name, planted in in_the_program.items()})
    out["e4m3_weights"] = (reference(lambda p: sibling._map_named(
        lambda n, x: sibling._round_e4m3(x) if n.endswith(matrices) else x,
        p)), none)
    return out


def wrap_reference(checks, *, names, leaves: bool, out: dict):
    """``checks.reference`` with the controls behind it; the sound program's
    verdict is what the runner gets, unchanged."""
    sound_reference = checks.reference
    compare = _sibling().compare

    def reference(config_mod, reference_mod, cfg, built, state, sample):
        import jax
        import numpy as np

        verdict = sound_reference(config_mod, reference_mod, cfg, built,
                                  state, sample)
        out["harness"] = {"failures": verdict[0], **verdict[1]}
        print(f"# control: {json.dumps({'harness': out['harness']})}",
              flush=True)
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
    # the step's counters lap by lap, warm-up included: the loss's two
    # terms, and whether the held share of the assignments stays where it
    # began
    from distributeddeeplearningspark_tpu import telemetry
    out["laps"] = [
        {"step": e["step"], **{k: e["metrics"].get(k) for k in COUNTERS}}
        for e in telemetry.read_events(os.path.join(
            result["facts"]["out_dir"], "telemetry"))
        if e.get("kind") == "step_metrics"]
    print(f"# controls: {json.dumps({'laps': out['laps']})}", flush=True)
    out["result"] = {k: result[k] for k in ("correct", "attempted", "failed",
                                            "metrics", "device")}
    path = os.path.join(root, "benchmark", "out", "controls",
                        f"laguna-seed{seed}.json")
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
