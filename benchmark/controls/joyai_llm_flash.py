"""Planted faults for the comparison that decides ``correct`` in the cell of
``joyai_llm_flash``: what the limits of ``check`` in the configuration's file
were read against, kept so that they can be read again.

    python3 benchmark/controls/joyai_llm_flash.py --seed <n> --seconds 40

runs ``joyai_llm_flash.fit_s16k`` exactly as ``run.py`` does (same runner,
same window, same last line) and, where the runner compares the trained
state with the reference (``harness/checks.reference``, whose verdict on the
sound program the runner gets unchanged), makes THAT comparison again for
the sound program and once for every fault below, each time with the fault
standing in for the configuration's ``program_loss``: same trained
parameters and bias, same window of the feed, same limits, the reference
computed once. One ``# control:`` line a fault, with every TERM of the
compared scalar on both sides (``terms``: program, reference, the difference
of their logarithms), and ``benchmark/out/controls/joyai-seed<n>.json``. A
fault that comes out ``correct`` is one the comparison cannot see. The
comparison itself (``compare``) and the 8-bit rounding are those of
``controls/lfm2_24b_a2b.py``, loaded from that file.

``scores_scaled_by_sqrt_128``   scores over sqrt(128), the position-free
                                width, where the model says sqrt(192)
``rotary_on_position_free``     the 128 position-free dimensions of q and k
                                rotated by the position as well
``rotary_key_per_head``         each head given a rotary key of its own (the
                                shared one, its dimensions rolled by the
                                head's index)
``q_latent_norm_left_out``      no RMSNorm on the query latent
``kv_latent_norm_left_out``     no RMSNorm on the key-value latent
``shared_expert_left_out``      the expert layers without their shared expert
``routed_scale_1``              the routed part weighted by the normalised
                                scores alone, without the factor 2.5 (in the
                                model and in the probe)
``mtp_predicts_next_token``     the module's hidden states held against
                                x_{t+1}, the main head's own target
``mtp_weight_0``                the module's term weighted by 0: the loss the
                                main model alone would give
``e4m3_attention_path``         the program with the five weight matrices of
                                every latent-attention layer rounded to an
                                8-bit float's 3 bits of mantissa
``e4m3_weights``                the reference with every weight matrix so
                                rounded (weights only, a lower bound on 8-bit
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

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "joyai_llm_flash.fit_s16k"
ALL = ("scores_scaled_by_sqrt_128", "rotary_on_position_free",
       "rotary_key_per_head", "q_latent_norm_left_out",
       "kv_latent_norm_left_out", "shared_expert_left_out", "routed_scale_1",
       "mtp_predicts_next_token", "mtp_weight_0", "e4m3_attention_path",
       "e4m3_weights")
COUNTERS = ("loss", "lm_loss", "mtp_nll", "moe_rows_held_share",
            "moe_load_max_over_mean", "router_bias_abs_max")


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
    from distributeddeeplearningspark_tpu.models.llama import rotary_embedding
    from distributeddeeplearningspark_tpu.train import losses
    from distributeddeeplearningspark_tpu.train.fused_ce import (
        chunked_softmax_xent,
    )

    sibling = _sibling()
    nope = cfg.get("qk_nope_head_dim", 0)

    def program(change=lambda p: p, loss=None):
        def fn(model, loss_fn, params, mutable, batch):
            terms = config_mod.parts(model, loss or loss_fn, change(params),
                                     mutable, batch)
            return config_mod.compared(terms), terms
        return fn

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

    def attention_given(change):
        """The latent layer's attention call with ``(q, k)`` changed first
        (no segment ids in this cell: a position is its index)."""
        return swapped(
            hybrid_decoder, "dot_product_attention",
            lambda sound: lambda q, k, v, **kw: sound(*change(q, k), v, **kw))

    def rotated_too(q, k):
        pos = jnp.broadcast_to(jnp.arange(q.shape[1]), q.shape[:2])
        turn = lambda t: jnp.concatenate(
            [rotary_embedding(t[..., :nope], pos, float(cfg["rope_theta"]),
                              interleaved=True), t[..., nope:]], axis=-1)
        return turn(q), turn(k)

    def key_per_head(q, k):
        heads, rot = k.shape[2], k.shape[3] - nope
        at = (jnp.arange(rot)[None, :] + jnp.arange(heads)[:, None]) % rot
        rolled = jnp.take_along_axis(
            k[..., nope:], jnp.broadcast_to(at, (*k.shape[:3], rot)), axis=-1)
        return q, jnp.concatenate([k[..., :nope], rolled], axis=-1)

    def norm_left_out(which):
        """``RMSNorm`` that is the identity (its scale unused, so without a
        gradient) where the module's name is ``which``."""
        def make(sound):
            def norm(eps, dtype, name=None):
                if name == which:
                    return lambda x: x.astype(dtype)
                return sound(eps, dtype, name=name)
            return norm
        return swapped(hybrid_decoder, "RMSNorm", make)

    def mtp_loss(shift: int, weight=None):
        """``losses.latent_moe_lm`` with the module's rows held against the
        token ``shift`` positions on, the term weighted by ``weight``."""
        def loss_fn(outputs, batch):
            lm_loss, metrics = losses.hybrid_moe_lm(outputs, batch)
            ids = batch["input_ids"]
            rows = ids.shape[1] - 2
            mtp_nll = jnp.mean(chunked_softmax_xent(
                outputs["mtp_hidden"][:, :rows], outputs["lm_head"],
                ids[:, shift:shift + rows]))
            w = outputs["mtp_weight"] if weight is None else weight
            loss = lm_loss + w * mtp_nll
            return loss, {**metrics, "loss": loss, "lm_loss": lm_loss,
                          "mtp_nll": mtp_nll}
        return loss_fn

    in_the_program = {
        "scores_scaled_by_sqrt_128": swapped(
            hybrid_decoder, "dot_product_attention",
            lambda sound: lambda q, k, v, **kw: sound(
                q, k, v, **{**kw, "scale": float(nope) ** -0.5})),
        "rotary_on_position_free": attention_given(rotated_too),
        "rotary_key_per_head": attention_given(key_per_head),
        "q_latent_norm_left_out": norm_left_out("q_norm"),
        "kv_latent_norm_left_out": norm_left_out("kv_norm"),
        "shared_expert_left_out": swapped(
            hybrid_decoder, "RoutedExperts",
            lambda sound: lambda *a, **kw: sound(
                *a, **{**kw, "shared_size": 0})),
        "routed_scale_1": swapped(
            moe, "_held_experts",
            lambda sound: lambda *a, **kw: sound(
                *a, **{**kw, "routed_scale": 1.0})),
    }
    none = contextlib.nullcontext
    out = {"sound": (program(), none), "reference": (reference(), none)}
    out.update({name: (program(), planted)
                for name, planted in in_the_program.items()})
    out["mtp_predicts_next_token"] = (program(loss=mtp_loss(1)), none)
    out["mtp_weight_0"] = (program(loss=mtp_loss(2, 0.0)), none)
    out["e4m3_attention_path"] = (program(lambda p: sibling._map_named(
        lambda n, x: sibling._round_e4m3(x)
        if "/self_attn/w" in n else x, p)), none)
    out["e4m3_weights"] = (reference(lambda p: sibling._map_named(
        lambda n, x: sibling._round_e4m3(x) if "norm" not in n else x, p)),
        none)
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
    # the step's counters lap by lap, warm-up included: the two loss terms,
    # and whether the held share of the assignments stays where it began
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
                        f"joyai-seed{seed}.json")
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
