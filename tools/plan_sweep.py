#!/usr/bin/env python
"""plan_sweep — measured layout search over the unified Plan compile layer.

Layout choices stop being folklore: this tool enumerates candidate
:class:`~distributeddeeplearningspark_tpu.parallel.plan.Plan`\\ s for a
model + mesh, runs a short *instrumented* probe per plan through the same
``compile_step_with_plan`` path production training uses, and ranks them by
REAL measurements from the anatomy ledger (telemetry/anatomy.py):

- ``step_time_s`` / ``steps_per_sec`` — the ranking key (timed steps after
  a warmup, closed with a device sync);
- ``mfu`` — the ledger's cost-analyzed FLOPs over the per-backend peak;
- ``bytes_accessed`` / ``compile_s`` — XLA cost analysis per compile;
- ``argument_bytes`` / ``temp_bytes`` — ``memory_analysis()``, the
  evidence that e.g. a ZeRO plan actually stopped replicating optimizer
  state;
- ``peak HBM`` — :func:`memory_watermarks` after the probe.

Every probe's compile is one ledgered ``compile`` event TAGGED with the
plan's name/signature, so ``dlstatus --anatomy`` on the sweep's telemetry
dir shows exactly one compile per plan. The winner re-runs on its already
compiled executable (the sweep asserts ZERO new compiles — what "pin this
plan" means operationally) and serializes via ``--pin`` so a training run
can load it: ``Trainer(..., plan=Plan.load("winner.plan.json"))``.

::

    python tools/plan_sweep.py                       # 8 fake CPU devices
    python tools/plan_sweep.py --mesh data=2,fsdp=2,seq=2 --steps 6 \
        --pin winner.plan.json --json
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import time
from typing import Any

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _HERE not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, _HERE)


def _build_batch(cfg, batch_size: int, seq: int):
    """Deterministic content-addressed probe batch: every plan probes the
    SAME bytes, and the digest rides every report so numbers from
    different runs are comparable by construction."""
    import numpy as np

    ids = np.stack([np.full((seq,), i % cfg.vocab_size, np.int32)
                    for i in range(batch_size)])
    batch = {"input_ids": ids,
             "loss_mask": np.ones((batch_size, seq), np.float32)}
    h = hashlib.blake2b(digest_size=8)
    for k in sorted(batch):
        h.update(k.encode())
        h.update(np.ascontiguousarray(batch[k]).tobytes())
    return batch, h.hexdigest()


def build_candidates(mesh, cfg, *, fsdp_min_size: int = 1,
                     only: "set[str] | None" = None):
    """(plans, skipped) applicable to ``mesh``'s axis sizes.

    Composed layouts exist ONLY here as Plans — e.g. ``ulysses+fsdp``
    is llama FSDP rules + the logical sequence axis mapped to ``seq`` +
    an ``attention_impl=ulysses`` model hint: zero new collective code.
    Plans whose axes the mesh can't honor are returned as ``skipped``
    rows with the reason (nothing silently vanishes from a ranking).
    """
    from distributeddeeplearningspark_tpu.models.llama import llama_rules
    from distributeddeeplearningspark_tpu.parallel import plan as plan_lib
    from distributeddeeplearningspark_tpu.parallel.sharding import ShardingRules

    shape = dict(mesh.shape)
    plans: list = []
    skipped: list[dict] = []

    def consider(plan, need: "dict[str, int] | None" = None):
        if only is not None and plan.name not in only:
            return
        lacking = {a: n for a, n in (need or {}).items()
                   if shape.get(a, 1) < n}
        if lacking:
            skipped.append({
                "plan": plan.name, "status": "skipped",
                "reason": f"mesh axes too small: needs {lacking}, mesh has "
                          f"{ {a: shape.get(a, 1) for a in lacking} }"})
            return
        plans.append(plan)

    consider(plan_lib.DP)
    consider(plan_lib.zero_plan(plan_lib.DP, name="dp+zero"))
    fsdp = plan_lib.Plan(
        name="fsdp", rules=ShardingRules(fsdp=True,
                                         fsdp_min_size=fsdp_min_size),
        description="auto-FSDP params + moments over 'fsdp'")
    consider(fsdp, {"fsdp": 2})
    llama = plan_lib.Plan(
        name="llama-fsdp",
        rules=llama_rules(cfg, fsdp=True, fsdp_min_size=fsdp_min_size),
        description="llama layout rules + auto-FSDP")
    consider(llama, {"fsdp": 2})
    # the composed context-parallel layout: exists only as this Plan
    consider(dataclasses.replace(
        llama, name="ulysses+fsdp", seq_axis="seq",
        model_hints=(("attention_impl", "ulysses"),),
        description="llama FSDP rules x ulysses context parallelism"),
        {"fsdp": 2, "seq": 2})
    consider(plan_lib.Plan(
        name="tensor", rules=llama_rules(cfg, fsdp=False),
        description="Megatron-style tensor parallelism"), {"tensor": 2})
    return plans, skipped


def probe_plan(plan, cfg, mesh, batch, *, steps: int = 6, warmup: int = 1,
               seed: int = 0, lr: float = 1e-3) -> dict:
    """One instrumented probe: init → ledgered compile → timed steps.

    Returns the measurement record; ``record["_runtime"]`` keeps the
    (instrumented step, state, global batch) alive for the winner's
    zero-new-compiles re-run."""
    import jax
    import numpy as np
    import optax

    from distributeddeeplearningspark_tpu.data.feed import put_global
    from distributeddeeplearningspark_tpu.models.llama import LlamaForCausalLM
    from distributeddeeplearningspark_tpu.parallel import plan as plan_lib
    from distributeddeeplearningspark_tpu.telemetry import anatomy as anatomy_lib
    from distributeddeeplearningspark_tpu.train import losses, step as step_lib

    hints = plan.hints()
    pcfg = cfg
    if hints.get("attention_impl"):
        pcfg = dataclasses.replace(cfg,
                                   attention_impl=hints["attention_impl"])
    model = LlamaForCausalLM(pcfg)
    mem0 = anatomy_lib.memory_watermarks()
    tx = plan.wrap_optimizer(optax.adam(lr), mesh)
    state, shardings = step_lib.init_state(
        model, tx, batch, mesh, plan.rules, seed=seed, plan=plan)
    step = plan_lib.compile_step_with_plan(
        step_lib.make_train_step(model.apply, tx, losses.causal_lm),
        plan, mesh, state_shardings=shardings, kind="train")
    gbatch = put_global(batch, mesh, seq_sharded=plan.seq_sharded)
    ledger = step.prepare(state, gbatch) or {}
    for _ in range(max(0, warmup)):
        state, _ = step(state, gbatch)
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    metrics = None
    for _ in range(steps):
        state, metrics = step(state, gbatch)
    jax.block_until_ready(state.params)
    wall = time.perf_counter() - t0
    loss = float(jax.device_get(metrics["loss"])) if metrics else None
    step_time = wall / max(1, steps)
    peak, peak_source = anatomy_lib.resolve_peak_flops()
    flops = step.flops_per_step
    mfu = None
    if peak and flops and wall > 0:
        mfu = flops * steps / wall / max(1, mesh.devices.size) / peak
    mem = anatomy_lib.memory_watermarks()
    if mem.get("source") == "live-buffers":
        # CPU fallback counts the whole process; the probe's own footprint
        # is the delta over its start (earlier probes' buffers excluded)
        peak_hbm = max(0, int(mem.get("live_bytes", 0))
                       - int(mem0.get("live_bytes", 0)))
        hbm_source = "live-buffers-delta"
    else:
        peak_hbm = mem.get("peak_bytes_in_use_max")
        hbm_source = mem.get("source")
    summary = step.compile_summary()
    rec: dict[str, Any] = {
        "plan": plan.name, "plan_sig": plan.signature(), "status": "ok",
        "style": plan.style, "logical_axes": {
            k: list(v) for k, v in plan.logical_axes().items()},
        "step_time_s": round(step_time, 6),
        "steps_per_sec": round(1.0 / step_time, 4) if step_time > 0 else None,
        "timed_steps": steps, "loss": loss,
        "mfu": round(mfu, 6) if mfu is not None else None,
        "flops_per_step": flops,
        "bytes_accessed": step.bytes_per_step,
        "compile_s": ledger.get("compile_s"),
        "argument_bytes": ledger.get("argument_bytes"),
        "output_bytes": ledger.get("output_bytes"),
        "temp_bytes": ledger.get("temp_bytes"),
        "peak_hbm_bytes": peak_hbm,
        "hbm_source": hbm_source,
        "peak_flops_source": peak_source,
        "compiles": summary["compiles"],
        "recompiles": summary["flagged_recompiles"],
    }
    rec["_runtime"] = (step, state, gbatch)
    return rec


def run_sweep(mesh, cfg, batch, *, steps: int = 6, warmup: int = 1,
              rerun_steps: int = 2, fsdp_min_size: int = 1,
              only: "set[str] | None" = None, seed: int = 0) -> dict:
    """Probe every candidate plan and rank by measured step time.

    The winner's probe re-runs ``rerun_steps`` more steps on its kept
    executable — ``winner_rerun_new_compiles`` MUST be 0 (pinning the
    winner costs no further compiles). Probe failures become ``skipped``
    rows (reason carried), never a silently missing candidate."""
    import jax

    from distributeddeeplearningspark_tpu.parallel import plan as plan_lib

    plans, skipped = build_candidates(mesh, cfg, fsdp_min_size=fsdp_min_size,
                                      only=only)
    ranked: list[dict] = []
    for plan in plans:
        try:
            ranked.append(probe_plan(plan, cfg, mesh, batch, steps=steps,
                                     warmup=warmup, seed=seed))
        except plan_lib.PlanValidationError as e:
            skipped.append({"plan": plan.name, "status": "skipped",
                            "reason": str(e)})
            continue
        except Exception as e:  # noqa: BLE001 — a broken probe is a row,
            # not a crashed sweep (the other candidates' numbers stand)
            skipped.append({"plan": plan.name, "status": "failed",
                            "reason": f"{type(e).__name__}: {str(e)[:300]}"})
            continue
        # keep only the best-so-far probe's executable+state alive (the
        # winner's zero-new-compiles re-run needs it; the rest would pile
        # N full states up in memory on a long candidate list)
        best = min(ranked, key=lambda r: r["step_time_s"])
        for r in ranked:
            if r is not best:
                r.pop("_runtime", None)
    ranked.sort(key=lambda r: r["step_time_s"])
    report: dict[str, Any] = {
        "mesh": {k: int(v) for k, v in dict(mesh.shape).items()},
        "devices": int(mesh.devices.size),
        "timed_steps": steps, "warmup_steps": warmup,
        "ranked": ranked, "skipped": skipped,
    }
    if ranked:
        winner = ranked[0]
        step, state, gbatch = winner["_runtime"]
        before = step.compile_summary()["compiles"]
        for _ in range(max(0, rerun_steps)):
            state, _ = step(state, gbatch)
        jax.block_until_ready(state.params)
        winner["_runtime"] = (step, state, gbatch)
        report["winner"] = winner["plan"]
        report["winner_sig"] = winner["plan_sig"]
        report["best_steps_per_sec"] = winner["steps_per_sec"]
        report["winner_rerun_steps"] = rerun_steps
        report["winner_rerun_new_compiles"] = (
            step.compile_summary()["compiles"] - before)
    for r in ranked:  # runtime handles never leave the library boundary
        r.pop("_runtime", None)
    return report


_COLS = ("plan", "step_time_s", "steps_per_sec", "mfu", "bytes_accessed",
         "peak_hbm_bytes", "compile_s", "argument_bytes")


def format_table(report: dict) -> str:
    """The ranked table, best plan first (what the operator reads)."""
    lines = [
        "plan sweep: mesh "
        + "x".join(f"{k}={v}" for k, v in report["mesh"].items() if v > 1
                   or k == "data")
        + f"  ({report['devices']} devices, {report['timed_steps']} timed "
          f"steps)",
        "  rank  " + "  ".join(f"{c:>15}" for c in _COLS),
    ]
    for i, r in enumerate(report["ranked"], 1):
        cells = []
        for c in _COLS:
            v = r.get(c)
            if v is None:
                cells.append(f"{'-':>15}")
            elif isinstance(v, float):
                cells.append(f"{v:>15.6g}")
            else:
                cells.append(f"{str(v):>15}")
        lines.append(f"  {i:>4}  " + "  ".join(cells))
    for r in report.get("skipped", ()):
        lines.append(f"  [{r['status']}] {r['plan']}: {r['reason']}")
    if report.get("winner"):
        lines.append(
            f"  winner: {report['winner']} [{report['winner_sig']}] "
            f"{report['best_steps_per_sec']} steps/s — re-ran "
            f"{report['winner_rerun_steps']} step(s) with "
            f"{report['winner_rerun_new_compiles']} new compile(s)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="plan_sweep",
        description="Rank candidate GSPMD Plans by measured step time.")
    ap.add_argument("--devices", type=int, default=8,
                    help="fake CPU device count when no real mesh backs the "
                         "box (default 8)")
    ap.add_argument("--mesh", default="data=2,fsdp=2,seq=2",
                    help="mesh axis sizes, e.g. data=2,fsdp=2,seq=2")
    ap.add_argument("--steps", type=int, default=6,
                    help="timed steps per probe (default 6)")
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--rerun-steps", type=int, default=2,
                    help="winner re-run length (asserts zero new compiles)")
    ap.add_argument("--batch", type=int, default=0,
                    help="probe batch size (default 2 rows per batch shard)")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--fsdp-min-size", type=int, default=1,
                    help="auto-FSDP threshold for the probe model "
                         "(default 1: tiny models still shard)")
    ap.add_argument("--plans", default="",
                    help="comma-separated plan-name filter (default: all "
                         "applicable)")
    ap.add_argument("--pin", default="",
                    help="serialize the winning Plan here "
                         "(Trainer(plan=Plan.load(path)) pins it)")
    ap.add_argument("--out", default="",
                    help="write the full JSON report here too")
    ap.add_argument("--json", action="store_true",
                    help="print the report as one JSON line instead of the "
                         "table")
    args = ap.parse_args(argv)

    from distributeddeeplearningspark_tpu.utils.env import (
        configure_compile_cache,
        ensure_cpu_devices,
    )

    ensure_cpu_devices(args.devices)
    configure_compile_cache()
    import jax

    if (len(jax.devices()) < args.devices
            and jax.devices()[0].platform == "cpu"
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        # a bare `python tools/plan_sweep.py` on a box with no accelerator:
        # jax has already fallen back to ONE host device, and the device
        # count can only be set before the backend starts — re-exec once
        # as an explicit CPU rehearsal with the count in XLA_FLAGS
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{args.devices}").strip()
        env.setdefault("JAX_PLATFORMS", "cpu")
        os.execve(sys.executable, [sys.executable, os.path.abspath(__file__)]
                  + list(argv if argv is not None else sys.argv[1:]), env)

    from distributeddeeplearningspark_tpu import telemetry as telemetry_lib
    from distributeddeeplearningspark_tpu.models.llama import LlamaConfig
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    wd = os.environ.get(telemetry_lib.WORKDIR_ENV)
    if wd:  # probes then land ledgered compiles for `dlstatus --anatomy`
        telemetry_lib.configure(wd)

    axes = {}
    for part in args.mesh.split(","):
        k, _, v = part.partition("=")
        axes[k.strip()] = int(v)
    mesh = MeshSpec(**axes).build()
    cfg = LlamaConfig.tiny()
    import math

    shards = math.prod(dict(mesh.shape).get(a, 1) for a in ("data", "fsdp"))
    batch_size = args.batch or 2 * shards
    batch, digest = _build_batch(cfg, batch_size, args.seq)
    only = ({p.strip() for p in args.plans.split(",") if p.strip()}
            or None)
    report = run_sweep(mesh, cfg, batch, steps=args.steps,
                       warmup=args.warmup, rerun_steps=args.rerun_steps,
                       fsdp_min_size=args.fsdp_min_size, only=only)
    report["batch_digest"] = digest
    report["batch_size"] = batch_size
    report["seq"] = args.seq
    if args.pin and report.get("winner"):
        import importlib

        plan_lib = importlib.import_module(
            "distributeddeeplearningspark_tpu.parallel.plan")
        plans, _ = build_candidates(mesh, cfg,
                                    fsdp_min_size=args.fsdp_min_size,
                                    only=only)
        winner = next(p for p in plans if p.name == report["winner"])
        winner.save(args.pin)
        report["pinned_to"] = args.pin
        assert plan_lib.Plan.load(args.pin).signature() == report["winner_sig"]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    if args.json:
        print(json.dumps(report))
    else:
        print(format_table(report))
    return 0 if report.get("ranked") else 1


if __name__ == "__main__":
    sys.exit(main())
