"""Planted faults for the comparison that decides ``correct`` in the cell of
``nemotron3_nano_30b_a3b``: what the limits of ``check`` in the
configuration's file were read against, kept so that they can be read again.

    python3 benchmark/controls/nemotron3_nano_30b_a3b.py --seed <n> --seconds 40

runs ``nemotron3_nano_30b_a3b.fit_seg16k`` exactly as ``run.py`` does (same
runner, same window, same last line) and, where the runner compares the
trained state with the reference (``harness/checks.reference``, whose verdict
on the sound program the runner gets unchanged), makes THAT comparison again
for the sound program and once for every fault below, each time with the
fault standing in for the configuration's ``program_loss``: same trained
parameters and bias, same window of the feed, same limits, the reference
computed once. One ``# control:`` line a fault, with every TERM of the
compared scalar on both sides (``terms``: program, reference, the difference
of their logarithms), and ``benchmark/out/controls/nemotron3-seed<n>.json``.
A fault that comes out ``correct`` is one the comparison cannot see. The
comparison itself (``compare``) and the 8-bit rounding are those of
``controls/lfm2_24b_a2b.py``, loaded from that file.

``state_not_reset``             the scan handed no segment ids: a document's
                                first position reads the state of the one
                                before it
``conv_crosses_documents``      the convolution handed no segment ids: its
                                taps reach back across a document's start
``dt_without_bias``             ``dt = softplus(dt)``, ``dt_bias`` left out
``d_skip_left_out``             ``y = h C`` without ``D x``
``norm_over_one_group``         the gated norm over one group of 4,096
                                where the model says 8 groups of 512
``gate_after_norm``             ``RMSNorm_groups(y) * w * silu(z)``: the gate
                                after the norm
``head_reads_group_h_mod_8``    head ``h`` reading ``B`` and ``C`` of group
                                ``h % 8`` where the model says ``h // 8``
``relu_not_squared``            the experts (routed and shared, and in the
                                probe) ``down(relu(up x))``
``routed_scale_1``              the routed part weighted by the normalised
                                scores alone, without the factor 2.5 (in the
                                model and in the probe)
``shared_expert_left_out``      the expert layers without their shared expert
``rotary_in_attention``         q and k of the attention layer rotated by the
                                position in the document (theta ``rope_theta``)
                                where the model has no positional embedding
``e4m3_mamba_path``             the program with the two projections of every
                                Mamba layer rounded to an 8-bit float's 3
                                bits of mantissa
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
CELL = "nemotron3_nano_30b_a3b.fit_seg16k"
ALL = ("state_not_reset", "conv_crosses_documents", "dt_without_bias",
       "d_skip_left_out", "norm_over_one_group", "gate_after_norm",
       "head_reads_group_h_mod_8", "relu_not_squared", "routed_scale_1",
       "shared_expert_left_out", "rotary_in_attention", "e4m3_mamba_path",
       "e4m3_weights")
COUNTERS = ("loss", "moe_rows_held_share", "moe_load_max_over_mean",
            "router_bias_abs_max", "attn_pairs_share",
            "ssm_chunks_reset_share", "ssm_state_abs_max")


def _sibling():
    """``controls/lfm2_24b_a2b.py``: its ``compare``, ``_round_e4m3`` and
    ``_map_named`` serve here as they are."""
    from benchmark.harness import runner

    return runner.load_module(os.path.join(HERE, "lfm2_24b_a2b.py"))


def faults(config_mod, reference_mod, cfg: dict) -> dict:
    """name -> (a function ``(model, loss_fn, params, mutable, batch) -> (the
    compared scalar, its terms)``, a context that keeps a change to the
    program in place while it is traced)."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from distributeddeeplearningspark_tpu.models import hybrid_decoder, moe
    from distributeddeeplearningspark_tpu.models.llama import rotary_embedding

    sibling = _sibling()
    matrices = ("kernel", "embedding", "lm_head", "router", "w_up", "w_down")

    def program(change=lambda p: p):
        def fn(model, loss_fn, params, mutable, batch):
            terms = config_mod.parts(model, loss_fn, change(params), mutable,
                                     batch)
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

    def leaf_zeroed(leaf):
        """The parameters with every ``.../mixer/<leaf>`` zero: ``dt_bias``
        (``dt = softplus(dt)``) or ``D`` (no ``D x``)."""
        return lambda p: sibling._map_named(
            lambda n, x: jnp.zeros_like(x) if n.endswith("/mixer/" + leaf)
            else x, p)

    def group_of_head_mod(sound):
        """The sound scan on heads so permuted that the head at place ``j``
        of the new order, which reads group ``j // (H / G)``, is the head
        ``h`` with ``h % G`` equal to it; its output permuted back."""
        def scan(x, dt, a, bm, cm, d, seg, **kw):
            h, g = x.shape[2], bm.shape[2]
            place = jnp.arange(h)
            perm = place // (h // g) + g * (place % (h // g))
            back = jnp.argsort(perm)
            y, peak = sound(x[:, :, perm], dt[:, :, perm], a[perm], bm, cm,
                            d[perm], seg, **kw)
            return y[:, :, back], peak
        return scan

    def gate_after(y, z, scale, groups, eps):
        f32 = jnp.float32
        y = y.astype(f32).reshape(*y.shape[:-1], groups, -1)
        y = y * jax.lax.rsqrt(
            jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
        return y.reshape(z.shape) * scale * nn.silu(z.astype(f32))

    def rotated(sound):
        def attention(q, k, v, **kw):
            pos = hybrid_decoder.document_positions(kw["segment_ids"])
            theta = float(cfg.get("rope_theta", 10000))
            return sound(rotary_embedding(q, pos, theta),
                         rotary_embedding(k, pos, theta), v, **kw)
        return attention

    in_the_program = {
        "state_not_reset": swapped(
            hybrid_decoder, "ssd_scan",
            lambda sound: lambda x, dt, a, bm, cm, d, seg, **kw: sound(
                x, dt, a, bm, cm, d, None, **kw)),
        "conv_crosses_documents": swapped(
            hybrid_decoder, "silu_short_conv",
            lambda sound: lambda v, w, b, seg: sound(v, w, b, None)),
        "norm_over_one_group": swapped(
            hybrid_decoder, "gated_group_norm",
            lambda sound: lambda y, z, scale, groups, eps: sound(
                y, z, scale, 1, eps)),
        "gate_after_norm": swapped(
            hybrid_decoder, "gated_group_norm", lambda sound: gate_after),
        "head_reads_group_h_mod_8": swapped(
            hybrid_decoder, "ssd_scan", group_of_head_mod),
        "relu_not_squared": swapped(moe, "relu2", lambda sound: nn.relu),
        "routed_scale_1": swapped(
            moe, "_held_experts",
            lambda sound: lambda *a, **kw: sound(
                *a, **{**kw, "routed_scale": 1.0})),
        "shared_expert_left_out": swapped(
            hybrid_decoder, "RoutedExperts",
            lambda sound: lambda *a, **kw: sound(
                *a, **{**kw, "shared_size": 0})),
        "rotary_in_attention": swapped(
            hybrid_decoder, "dot_product_attention", rotated),
    }
    none = contextlib.nullcontext
    out = {"sound": (program(), none), "reference": (reference(), none)}
    out.update({name: (program(), planted)
                for name, planted in in_the_program.items()})
    out["dt_without_bias"] = (program(leaf_zeroed("dt_bias")), none)
    out["d_skip_left_out"] = (program(leaf_zeroed("D")), none)
    out["e4m3_mamba_path"] = (program(lambda p: sibling._map_named(
        lambda n, x: sibling._round_e4m3(x)
        if "/mixer/" in n and n.endswith("kernel") else x, p)), none)
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
                        f"nemotron3-seed{seed}.json")
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
