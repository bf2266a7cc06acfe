"""Benchmark harness — prints ONE JSON line with the headline metric.

One process that imports JAX once and runs on the local TPU. Metrics follow
BASELINE.json: **ResNet-50 images/sec/chip** (headline) and **BERT-base MLM
tokens/sec/chip** (in ``extra``), plus achieved MFU. ``vs_baseline`` is
measured-MFU / 0.50 (the north-star MFU target); the reference published no
absolute numbers, so the MFU target is the only honest denominator available.

- A device arm (everything but ``input``, ``mpmd`` and ``plan``) on a machine
  where jax finds no TPU exits non-zero: a number from a CPU run is never
  written under the name of a device metric.
- Each workload benches independently — a BERT failure still reports ResNet —
  but any arm that raises makes the exit code non-zero, with the error chain
  in ``extra.errors``.
- ``--model input`` is host-only: it holds jax to the host CPU before jax is
  imported and never asks for a device.

Also runs the flash-attention kernel regimes through Mosaic against the XLA
reference (:func:`pallas_smoke`).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

#: arms whose numbers are device metrics: they refuse to run without a TPU
DEVICE_ARMS = frozenset(
    {"all", "resnet", "bert", "llama", "dlrm", "kernels", "memval"})


def _force_sync(state) -> None:
    """End a timing window: wait until the whole donated step chain behind
    ``state`` has executed. Measured on the v5e (PR 21): 20 BERT-base steps
    take 2,729 ms to ``block_until_ready`` and a ``device_get`` of a
    reduction over the params right after it adds 1.6-1.8 ms, so
    ``block_until_ready`` alone closes the window honestly."""
    import jax

    jax.block_until_ready(state.params)


def bench_steps(step_fn, state, batch, *, warmup: int = 3, iters: int = 20,
                repeats: int = 3):
    """Time `repeats` back-to-back windows of `iters` steps each.

    Returns (median_step_time_s, per_window_times_list, state). VERDICT r2
    weak-#3 asked the harness itself to witness within-run variance: the
    median is the headline, the window list rides along so every artifact is
    self-describing about its own noise floor.
    """
    for _ in range(warmup):
        state, _ = step_fn(state, batch)
    _force_sync(state)
    times: list[float] = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, _ = step_fn(state, batch)
        _force_sync(state)
        times.append((time.perf_counter() - t0) / iters)
    return float(np.median(times)), times, state


def _timing_fields(times: list[float], iters: int) -> dict:
    """Self-describing variance block for a bench record (VERDICT r2 #8)."""
    lo, hi = min(times), max(times)
    return {
        "step_time_ms": round(float(np.median(times)) * 1e3, 3),
        "step_time_windows_ms": [round(t * 1e3, 3) for t in times],
        "spread_pct": round((hi - lo) / lo * 100, 2) if lo > 0 else 0.0,
        "repeats": len(times),
        "iters_per_window": iters,
    }


def _host_conditions() -> dict:
    """Host-side condition tuple so records are comparable run-to-run."""
    import os

    return {"nproc": os.cpu_count() or 1}


def _train_setup(model, batch, loss_fn, *, tx=None, rules=None, trainable=None):
    """Shared: mesh, sharded state, ledgered jitted step, global batch, flops.

    The step is wrapped in the compile ledger (telemetry/anatomy.py) and
    ``prepare``d: the FLOPs cost analysis and the warmup executable are ONE
    compile (the old path compiled a throwaway twin), and the arm's record
    gains the ledger fields — ``compile_s`` / ``recompile_count`` — via
    :func:`_ledger_fields`.
    """
    import optax

    from distributeddeeplearningspark_tpu.data.feed import put_global
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
    from distributeddeeplearningspark_tpu.parallel.sharding import REPLICATED
    from distributeddeeplearningspark_tpu.telemetry import anatomy as anatomy_lib
    from distributeddeeplearningspark_tpu.train import step as step_lib
    from distributeddeeplearningspark_tpu.utils.env import configure_compile_cache

    configure_compile_cache()
    mesh = MeshSpec(data=-1).build()
    tx = tx or optax.sgd(0.01, momentum=0.9)
    state, shardings = step_lib.init_state(
        model, tx, batch, mesh, rules if rules is not None else REPLICATED)
    train_step = anatomy_lib.instrument(
        step_lib.jit_train_step(
            step_lib.make_train_step(
                model.apply, tx, loss_fn,
                mutable_keys=tuple(state.mutable.keys()),
                trainable=trainable,
            ),
            mesh, shardings,
        ),
        name="bench-train_step",
    )
    gbatch = put_global(batch, mesh)
    train_step.prepare(state, gbatch)
    return mesh, state, train_step, gbatch, train_step.flops_per_step


def _ledger_fields(step) -> dict:
    """The per-arm compile-ledger rollup (tools/perf_guard.py folds these
    across rounds): total compile seconds and the flagged-recompile count —
    0 is the steady-state contract a recompile storm breaks."""
    summary = getattr(step, "compile_summary", None)
    if summary is None:
        return {}
    s = summary()
    return {"compile_s": s["total_compile_s"],
            "recompile_count": s["flagged_recompiles"],
            "compiles": s["compiles"]}


def _routes_to_flash(*, b: int, s: int, h: int, d: int, masked: bool) -> bool:
    """Would ops/attention 'auto' pick the flash kernel for this shape?

    Asks the real router with dummy shaped arrays so the bench's analytic
    FLOPs adjustment can never disagree with what the model actually ran.
    """
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.ops.attention import _pick_impl

    q = jnp.zeros((b, s, h, d), jnp.bfloat16)
    mask = jnp.ones((b, 1, 1, s), jnp.bool_) if masked else None
    return _pick_impl(q, q, None, mask) == "flash"


def _sanity_check_mfu(rec: dict) -> None:
    """MFU > 100% means the timing is an artifact, not a fast chip.

    Reads the most-trusted MFU the record carries (``mfu``, then the
    analytic ``mfu_model``, then the scan-opaque HLO count — ADVICE r2:
    bench_llama's analytically augmented FLOPs would make an impossible
    value look plausible if a timing window ever closed before the device
    had run).
    """
    mfu = rec.get("mfu", rec.get("mfu_model",
                                 rec.get("mfu_hlo_scan_opaque", 0.0)))
    if mfu > 1.0:
        rec["timing_suspect"] = (
            f"mfu {mfu:.2f} > 1.0 is physically impossible — the "
            "backend reported completion before executing; treat step_time "
            "as invalid")


def bench_resnet(iters: int, batch_size: int = 256,
                 fused_conv_bn: bool = False,
                 op_profile: bool = False) -> dict:
    """ResNet-50 images/sec/chip + MFU (BASELINE.json metric #1).

    ``fused_conv_bn``: route the bottlenecks' stride-1 1×1 conv→BN pairs
    through the Pallas matmul-with-BN-stats-epilogue kernel
    (ops/conv_bn.py) — the VERDICT r2 next-#2 byte-diet A/B.

    ``op_profile``: after timing, capture a 5-step trace and embed the
    per-op device-time budget in the record (VERDICT r4 next-#2: the
    v4-32 MFU projection needs the measured byte/op profile at more than
    one batch size — specifically whether the BN-stats share falls as the
    arithmetic intensity rises with batch).
    """
    from distributeddeeplearningspark_tpu.data.feed import stack_examples
    from distributeddeeplearningspark_tpu.metrics import device_peak_flops
    from distributeddeeplearningspark_tpu.models import ResNet50
    from distributeddeeplearningspark_tpu.train import losses

    model = ResNet50(num_classes=1000, dtype="bfloat16",
                     fused_conv_bn=fused_conv_bn)
    rng = np.random.default_rng(0)
    batch = stack_examples([
        {"image": rng.normal(0, 1, (224, 224, 3)).astype(np.float32),
         "label": np.int32(i % 1000)}
        for i in range(batch_size)
    ])
    mesh, state, step, gbatch, flops = _train_setup(model, batch, losses.softmax_xent)
    n_chips = mesh.devices.size
    step_time, times, state = bench_steps(step, state, gbatch, iters=iters)
    peak = device_peak_flops()
    mfu = (flops / step_time / n_chips / peak) if (flops and peak) else 0.0
    rec = {
        "images_per_sec_per_chip": round(batch_size / step_time / n_chips, 2),
        **_timing_fields(times, iters),
        **_ledger_fields(step),
        "mfu": round(mfu, 4),
        "batch_size": batch_size,
        "image_px": 224,
        "dtype": "bfloat16",
        "fused_conv_bn": fused_conv_bn,
        "chips": n_chips,
    }
    if op_profile:
        import tempfile

        from distributeddeeplearningspark_tpu.utils import profiling

        pdir = tempfile.mkdtemp(prefix="bench_resnet_prof_")
        try:
            with profiling.trace(pdir):
                for i in range(5):
                    with profiling.step_annotation(i):
                        state, _ = step(state, gbatch)
                _force_sync(state)
            bd = profiling.op_breakdown(pdir, top=15)
            # keep the record bounded: op class, share, ms — drop instances
            # (inside the guard: a malformed subprocess record must not void
            # the timing result it rides on either)
            rec["op_breakdown"] = ({
                "total_ms": bd.get("total_ms"),
                "ops": [{k: o.get(k) for k in ("name", "pct", "ms", "count")}
                        for o in bd.get("ops", [])[:12]],
            } if bd.get("ops") else bd)
        except Exception as e:  # noqa: BLE001 — a failed capture must not
            # void the timing record it rides on
            rec["op_breakdown"] = {
                "error": f"{type(e).__name__}: {str(e)[:300]}"}
        finally:
            import shutil

            shutil.rmtree(pdir, ignore_errors=True)
    _sanity_check_mfu(rec)
    return rec


def bench_bert(iters: int, batch_size: int = 32, seq: int = 512,
               segment_ids: bool = False) -> dict:
    """BERT-base MLM tokens/sec/chip + MFU (BASELINE.json metric #2).

    Full 512-token sequences with an all-ones attention mask (the padding-mask
    path BERT always runs through — routes to the Pallas flash kernel on TPU,
    see ops/attention._pick_impl), 15% MLM targets in the gathered
    (``mlm_positions``) form so the vocab projection runs on masked positions
    only (models/bert.py docstring), AdamW.
    """
    import optax

    from distributeddeeplearningspark_tpu.data.feed import stack_examples
    from distributeddeeplearningspark_tpu.data.text import pack_mlm_predictions
    from distributeddeeplearningspark_tpu.metrics import device_peak_flops
    from distributeddeeplearningspark_tpu.models import bert_base
    from distributeddeeplearningspark_tpu.train import losses

    model = bert_base()
    rng = np.random.default_rng(1)
    max_pred = int(seq * 0.15) + 4
    examples = []
    for _ in range(batch_size):
        ids = rng.integers(0, 30522, (seq,)).astype(np.int32)
        weights = (rng.random(seq) < 0.15).astype(np.float32)
        ex = {
            "input_ids": ids,
            "attention_mask": np.ones((seq,), np.int32),
            "mlm_labels": ids,
            "mlm_weights": weights,
        }
        if segment_ids:
            # packed-document shape (VERDICT r2 #4 A/B): ~3 docs per window,
            # Wikipedia-like boundary positions
            segs = np.zeros((seq,), np.int32)
            for b1 in sorted(rng.integers(1, seq, size=2)):
                segs[b1:] += 1
            ex["segment_ids"] = segs
        examples.append(pack_mlm_predictions(ex, max_pred))
    batch = stack_examples(examples)
    mesh, state, step, gbatch, flops = _train_setup(
        model, batch, losses.masked_lm, tx=optax.adamw(1e-4))
    n_chips = mesh.devices.size
    step_time, times, _ = bench_steps(step, state, gbatch, iters=iters)
    peak = device_peak_flops()
    # BERT-base routes to the Pallas flash kernel on TPU (s=512, key-only
    # mask — ops/attention._pick_impl); its QKᵀ/PV matmul FLOPs are
    # invisible to XLA cost analysis, so add them analytically per layer for
    # an honest MFU. Geometry comes from the benched model's own config so
    # the adjustment can never describe a different model than was timed.
    cfg = model.cfg
    head_dim = cfg.hidden_size // cfg.num_heads
    if flops and _routes_to_flash(b=batch_size, s=seq, h=cfg.num_heads,
                                  d=head_dim, masked=True):
        from distributeddeeplearningspark_tpu.metrics import attention_matmul_flops

        flops += cfg.num_layers * attention_matmul_flops(
            batch_size, cfg.num_heads, seq, head_dim, causal=False, train=True)
    mfu = (flops / step_time / n_chips / peak) if (flops and peak) else 0.0
    tokens = batch_size * seq
    rec = {
        "tokens_per_sec_per_chip": round(tokens / step_time / n_chips, 1),
        **_timing_fields(times, iters),
        **_ledger_fields(step),
        "mfu": round(mfu, 4),
        "batch_size": batch_size,
        "seq_len": seq,
        "segment_ids": segment_ids,
        "chips": n_chips,
    }
    rec["packing_economics"] = _bert_packing_economics(
        rec["tokens_per_sec_per_chip"])
    _sanity_check_mfu(rec)
    return rec


def _bert_packing_economics(raw_tok_per_sec: float) -> dict:
    """Price the packed-vs-per-document pipeline in EFFECTIVE (non-pad)
    tokens/sec — the half of VERDICT r2 #4 the device alone can't answer.
    The r4 chip window measured the packed path's segment-id masks FREE
    (117,618 vs 117,659 tok/s, −0.03%), so the whole win is pad_frac, which
    is a property of the input pipeline: measure it through the REAL
    mlm_dataset path (synthetic Wikipedia-like corpus, 60–120-word docs)
    and derive effective tok/s for both modes from the single measured
    device rate. Honest caveat: pad_frac is corpus-dependent; the synthetic
    corpus stands in for Wikipedia's short-document regime.
    """
    from distributeddeeplearningspark_tpu.data import text as text_lib

    docs = text_lib.synthetic_wikipedia(48, num_partitions=2)
    tok = text_lib.WordPieceTokenizer.train(docs.collect(), vocab_size=512)
    packed = text_lib.token_stats(
        text_lib.mlm_dataset(docs, tok, seq_len=512))
    naive = text_lib.token_stats(
        text_lib.mlm_dataset(docs, tok, seq_len=512, pack=False))
    return {
        "packed_pad_frac": packed["pad_frac"],
        "per_document_pad_frac": naive["pad_frac"],
        "effective_tokens_per_sec_packed": round(
            raw_tok_per_sec * packed["effective_frac"], 1),
        "effective_tokens_per_sec_per_document": round(
            raw_tok_per_sec * naive["effective_frac"], 1),
        "packing_speedup_effective": round(
            packed["effective_frac"] / max(naive["effective_frac"], 1e-9), 2),
        "segment_mask_cost_measured": "-0.03% (July 2026 bert A/B, ROADMAP Recent)",
    }


def _llama_09b_cfg(*, seq: int = 2048, fused_head: bool = False,
                   moe_experts: int = 0, base_quant: str | None = None):
    """THE 0.9b bench config — one definition shared by bench_llama and
    bench_memval, so the memory validation can never drift from the shape
    the series actually runs (a review caught exactly that: memval carrying
    f32 storage after the bench moved to bf16)."""
    from distributeddeeplearningspark_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=32000, hidden_size=2048, num_layers=16, num_heads=16,
        num_kv_heads=8, intermediate_size=5632, max_position=seq,
        lora_rank=16, dtype="bfloat16",
        # bf16 base-weight STORAGE (r4): the frozen base never takes an
        # optimizer step, so f32 masters were pure HBM waste — halves
        # param bytes read per step AND resident. Series condition
        # change vs r2's f32-storage numbers; recorded in the record.
        param_dtype="bfloat16",
        # MoE cost experiment: E routed experts (models/moe.py, sorted
        # grouped products, nothing dropped) — relative step time vs E=0
        # (dense) prices the routing, the sort and the gathers
        moe_experts=moe_experts,
        moe_top_k=min(2, moe_experts) if moe_experts else 2,
        base_quant=base_quant,
        # keep matmul outputs across the remat boundary: measured 429→391
        # ms (19.1k→21.0k tok/s) on this shape at b=4; b≥6 OOMs 16G HBM
        # with it, so the policy pays exactly while the batch still fits.
        # Long-context (s≥16384) flips to full remat + fused CE: the kept
        # dots alone exceed 16 GiB there, while the flipped pair measures
        # s=16384 b=1 at 9677 tok/s/chip on the r4 window (r2's boundary
        # was "s=16384 exceeds single-chip HBM" — bf16 base storage plus
        # these two knobs moved it)
        remat_policy=None if seq >= 16384 else "dots",
        # A/B knob: fuse the
        # LM-head matmul into the loss so [B,S,V] never materializes
        fused_head_loss=fused_head or seq >= 16384)


def bench_llama(iters: int, batch_size: int | None = None, seq: int = 2048,
                fused_head: bool = False, variant: str = "0.9b",
                segment_ids: bool = False, moe_experts: int = 0,
                base_quant: str | None = None) -> dict:
    """Llama LoRA fine-tune tokens/sec/chip (BASELINE.json config 5 shape).

    ``variant="0.9b"`` (default): single-chip-sized geometry (~0.9B params,
    hidden 2048 / 16 layers, GQA 16q/8kv, LoRA rank 16, AdamW on adapters
    only, remat on — remat=False fails in this backend's remote compile
    helper); the real 7B runs FSDP across chips (dryrun-validated).

    ``variant="7b"`` (VERDICT r2 next-#3): the REAL Llama-2 7B geometry,
    b=1, remat_policy=None, fused CE — borderline on a 16 GiB dev chip by
    the analytic budget (utils/memory.py), so either outcome is evidence:
    a measured tok/s/chip, or a structured OOM record alongside the
    checked-in per-chip budget proving the v4-32 FSDP fit.

    ``variant="tiny"``: a CPU-runnable geometry (hidden 256 / 4 layers) for
    RELATIVE experiments only — the MoE dispatch-cost table (r3 weak-#4)
    needs dense-vs-E step-time ratios; absolute numbers from this variant
    are meaningless and never enter a series.
    """
    import optax

    from distributeddeeplearningspark_tpu.data.feed import stack_examples
    from distributeddeeplearningspark_tpu.metrics import device_peak_flops
    from distributeddeeplearningspark_tpu.models import (
        LlamaConfig,
        LlamaForCausalLM,
        llama_rules,
        lora_trainable,
    )
    from distributeddeeplearningspark_tpu.train import losses, optim
    from distributeddeeplearningspark_tpu.utils.memory import (
        llama_memory_report, llama_param_count)

    if moe_experts and variant == "7b":
        raise ValueError("--moe-experts is a 0.9b-proxy experiment; the 7b "
                         "geometry is the dense contract shape")
    if variant == "7b":
        # b defaults to 1 (the known-good shape: s=1024 compiled 14.68 GiB
        # live with the scan relayout barrier) so a bare --variant 7b can't
        # cost the round its executed-7B evidence; an EXPLICIT --batch may
        # push to 2 — the b=2 fit question IS the llama_7b_b2 queue item's
        # evidence — but never past 2 on a 16 GiB chip.
        batch_size = 1 if batch_size is None else min(batch_size, 2)
        seq = min(seq, 2048)
        fused_head = True  # [B,S,V] f32 logits alone would be 0.25 GiB; the
        # cotangent doubles it — fused CE is mandatory at this margin
        cfg = LlamaConfig.llama2_7b(
            lora_rank=16, dtype="bfloat16", max_position=seq,
            remat_policy=None, fused_head_loss=True,
            base_quant=base_quant)
    elif variant == "tiny":
        batch_size, seq = min(batch_size or 2, 2), min(seq, 256)
        cfg = LlamaConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, max_position=seq,
            lora_rank=8, dtype="float32", remat=False,
            moe_experts=moe_experts,
            moe_top_k=min(2, moe_experts) if moe_experts else 2,
            base_quant=base_quant,
            fused_head_loss=fused_head)
    else:
        batch_size = 4 if batch_size is None else batch_size
        cfg = _llama_09b_cfg(seq=seq, fused_head=fused_head,
                             moe_experts=moe_experts, base_quant=base_quant)
    # the config builders may force fused CE on (7b always; 0.9b at s≥16384)
    # — the loss choice below must follow the config, not the CLI flag
    fused_head = cfg.fused_head_loss
    mem_report = llama_memory_report(
        cfg, batch=batch_size, seq=seq, mesh_shape={},
        hbm_per_chip_gib=16).to_dict()
    # the v4-32 contract layout (config 5), always recorded alongside — at
    # the CONTRACT shape (b=8 global, s=4096 for 7b), not the clamped
    # single-chip attempt shape, so the artifact's fit claim is the one that
    # matters
    v4_cfg = (LlamaConfig.llama2_7b(lora_rank=16, dtype="bfloat16",
                                    remat_policy=None, fused_head_loss=True)
              if variant == "7b" else cfg)
    v4_batch, v4_seq = (8, 4096) if variant == "7b" else (batch_size, seq)
    mem_v4_32 = llama_memory_report(
        v4_cfg, batch=v4_batch, seq=v4_seq,
        mesh_shape={"data": 2, "fsdp": 8}, hbm_per_chip_gib=32).to_dict()
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(2)

    def example():
        ex = {"input_ids": rng.integers(
                  0, cfg.vocab_size, (seq,)).astype(np.int32),
              "loss_mask": np.ones((seq,), np.float32)}
        if segment_ids:
            # packed-document shape (~4 docs/window, Wikipedia-ish): the A/B
            # prices cross-document isolation vs GPT-style packing
            segs = np.zeros((seq,), np.int32)
            for b1 in sorted(rng.integers(1, seq, size=3)):
                segs[b1:] += 1
            ex["segment_ids"] = segs
        return ex

    batch = stack_examples([example() for _ in range(batch_size)])
    try:
        mesh, state, step, gbatch, flops = _train_setup(
            model, batch,
            losses.causal_lm_fused if fused_head else losses.causal_lm,
            tx=optim.masked(optax.adamw(1e-4), lora_trainable),
            rules=llama_rules(cfg),
            # LoRA: freeze base weights out of autodiff entirely — their dW
            # matmuls and stacked f32 grad buffers are pure waste (step.py
            # `trainable` docstring)
            trainable=lora_trainable)
    except Exception as e:
        # 7B on one dev chip is allowed to OOM — that IS the evidence (with
        # the budget). ONLY resource exhaustion qualifies; any other failure
        # is a code bug and still raises (it must not masquerade as memory
        # evidence).
        msg = str(e)
        # explicit memory errors vs heuristic matches (ADVICE r3: the
        # compile helper's opaque exit-code shape, or a bare 'OOM'
        # substring, could equally be a non-memory compile failure — tag
        # them oom_suspected and keep enough raw error to audit)
        oom_explicit = any(s in msg for s in (
            "RESOURCE_EXHAUSTED", "Ran out of memory", "out of memory"))
        oom_suspected = not oom_explicit and any(s in msg for s in (
            "OOM", "tpu_compile_helper subprocess exit code"))
        if variant != "7b" or not (oom_explicit or oom_suspected):
            raise
        # the memory verdict lines can sit thousands of chars into the
        # error text — extract them verbatim so the record stays auditable
        # even after the head truncation (ADVICE r3 #1)
        mem_lines = [ln.strip() for ln in msg.splitlines()
                     if re.search(r"Ran out of memory|Used [0-9.]+[MG] of"
                                  r"|Exceeded .* capacity|RESOURCE_EXHAUSTED",
                                  ln)]
        return {
            "variant": variant,
            "error": f"{type(e).__name__}: {msg[:1500]}",
            "error_memory_lines": mem_lines[:8],
            "oom_suspected": oom_suspected,
            "oom_is_evidence": (
                "single-chip 7B attempt failed with an explicit memory "
                "error; see memory_report for the documented budget and "
                "memory_v4_32 for the contract-layout fit"
                if oom_explicit else
                "failure matches the compile helper's opaque OOM shape but carries "
                "no explicit memory string — treat as SUSPECTED memory "
                "exhaustion and audit the raw error above"),
            "memory_report": mem_report,
            "memory_v4_32": mem_v4_32,
            "batch_size": batch_size,
            "seq_len": seq,
        }
    n_chips = mesh.devices.size
    step_time, times, state = bench_steps(step, state, gbatch, iters=iters)
    moe_fields = {}
    if moe_experts:
        import jax

        state, m = step(state, gbatch)  # one extra step just for its metrics
        m = jax.device_get(m)
        moe_fields = {
            "moe_experts": moe_experts,
            "moe_top_k": cfg.moe_top_k,
            "moe_aux": round(float(m["moe_aux"]), 5),
        }
    peak = device_peak_flops()
    # Add the flash kernel's invisible attention matmul FLOPs (16 layers,
    # causal, q-head count; GQA doesn't change matmul FLOPs). With
    # remat_policy="dots" the projection matmuls are saved, not recomputed,
    # so cost analysis no longer double-counts them — but the elementwise
    # recompute still inflates the non-matmul tally slightly, and the number
    # stays labeled approximate for that reason.
    if flops and _routes_to_flash(b=batch_size, s=seq, h=cfg.num_heads,
                                  d=cfg.head_dim, masked=False):
        from distributeddeeplearningspark_tpu.metrics import attention_matmul_flops

        flops += cfg.num_layers * attention_matmul_flops(
            batch_size, cfg.num_heads, seq, cfg.head_dim,
            causal=True, train=True)
    mfu = (flops / step_time / n_chips / peak) if (flops and peak) else 0.0
    # Analytic model-FLOPs MFU (the PaLM-convention number): XLA cost
    # analysis reports the layer-scan body ONCE, not ×L (r5 measurement —
    # metrics.llama_model_flops_per_token docstring), so the compiled
    # count structurally understates every scanned model. mfu_model is
    # the honest, formula-documented series; the suspect number is kept
    # under a name that says so (VERDICT r4 weak-#5: `mfu_approx` read
    # alone handed a consumer the artifact value) so the discrepancy
    # itself stays visible in the series.
    from distributeddeeplearningspark_tpu.metrics import (
        llama_model_flops_per_token)

    flops_model = llama_model_flops_per_token(
        cfg, seq, frozen_base=cfg.lora_rank > 0) * batch_size * seq
    mfu_model = (flops_model / step_time / n_chips / peak) if peak else 0.0
    rec = {
        "tokens_per_sec_per_chip": round(batch_size * seq / step_time / n_chips, 1),
        **_timing_fields(times, iters),
        **_ledger_fields(step),
        "mfu_model": round(mfu_model, 4),
        "mfu_convention": ("frozen-base model FLOPs: 4P fwd+dx, dW for "
                           "LoRA only, +attn matmuls — NOT comparable to "
                           "full-train MFU denominators"
                           if cfg.lora_rank else
                           "full-train model FLOPs (6P + attn)"),
        "mfu_hlo_scan_opaque": round(mfu, 4),
        "mfu_hlo_scan_opaque_note": (
            "from compiled cost analysis, which counts the layer-scan "
            "body once (not xL) — known structural undercount, kept for "
            "series continuity with r2-r4 mfu_approx"),
        "variant": variant,
        "params": sum(llama_param_count(cfg).values()),
        "batch_size": batch_size,
        "seq_len": seq,
        "fused_head_loss": fused_head,
        "segment_ids": segment_ids,
        "param_dtype": str(cfg.param_dtype),
        "base_quant": cfg.base_quant,
        **moe_fields,
        "memory_report": mem_report,
        "memory_v4_32": mem_v4_32,
        "chips": n_chips,
    }
    _sanity_check_mfu(rec)
    return rec


def bench_llama_decode(iters: int, batch_size: int = 8,
                       prompt_len: int = 128, new_tokens: int = 128,
                       base_quant: str | None = None) -> dict:
    """KV-cached decode throughput at the 0.9b bench geometry — the
    serving-side axis (models/llama_gen.py: prefill + one-token
    lax.scan). Decode is weight-read-bound per token (batch 8 reads the
    whole base per step), so this is where int8 base storage should pay
    beyond fit: the ``--base-quant int8`` A/B measures the "per-token
    weight reads halve" claim (BASELINE r4 int8 row) that training
    throughput cannot see.
    """
    import jax

    from distributeddeeplearningspark_tpu.models import LlamaForCausalLM
    from distributeddeeplearningspark_tpu.models.llama_gen import generate

    total = prompt_len + new_tokens
    cfg = _llama_09b_cfg(seq=total, base_quant=base_quant)
    rng = np.random.default_rng(11)
    prompt_ids = rng.integers(
        0, cfg.vocab_size, (batch_size, prompt_len)).astype(np.int32)
    params = LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(0), {"input_ids": prompt_ids[:, :8]},
        train=False)["params"]

    def run(seed: int, n: int):
        out = generate(params, prompt_ids, cfg=cfg, max_new_tokens=n,
                       temperature=0.0, seed=seed,
                       max_cache_len=total)
        return int(jax.device_get(out[0, -1]))  # sync

    def timed(n: int, reps: int) -> tuple[float, float]:
        # The first device call of a shape includes jit compile time —
        # orders of magnitude above a steady-state step. It is timed
        # separately and DISCARDED from the average (VERDICT r5 weak-#5:
        # a first record that includes compile contaminates the reported
        # tok/s); the record carries what was thrown away.
        t0 = time.perf_counter()
        run(0, n)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for i in range(reps):
            run(i, n)
        return (time.perf_counter() - t0) / reps, first

    # prefill is compute-bound and identical in both arms of the int8 A/B
    # (the bench's whole point is the weight-read-bound DECODE steps), so
    # subtract a prompt-only run: full − (prefill + 1 step) isolates the
    # remaining new_tokens−1 scan steps. max_cache_len pinned to `total`
    # for both shapes so they share cache geometry.
    if new_tokens < 2:
        raise ValueError("decode bench needs new_tokens >= 2 (the prompt-"
                         "only arm subtracts away the first token)")
    reps = max(3, iters // 5)
    dt_full, first_full = timed(new_tokens, reps)
    dt_prefill, first_prefill = timed(1, reps)
    per_tok = (dt_full - dt_prefill) / (new_tokens - 1)
    rec_suspect = {}
    if per_tok <= 0:
        # a scheduling hiccup in the prompt-only window can exceed the
        # full run at small reps — the house timing_suspect convention:
        # never let a physically impossible number head a series record
        rec_suspect["timing_suspect"] = (
            f"prefill-only run ({dt_prefill * 1e3:.1f} ms) >= full run "
            f"({dt_full * 1e3:.1f} ms); per-step decode time is "
            f"unmeasurable this run — treat throughput as invalid")
        per_tok = float("inf")
    elif per_tok > (dt_full / new_tokens) * 1.10:
        # cross-check (VERDICT r5 weak-#5): decode steps are the CHEAPEST
        # tokens of a generation (no prefill attached), so the
        # subtraction-derived step time can never exceed the
        # whole-generation wall-clock divide. >10% over means something
        # non-steady-state (a stray compile, a scheduling stall) landed
        # inside one timing arm — flag rather than publish.
        rec_suspect["timing_suspect"] = (
            f"per-step decode time ({per_tok * 1e3:.2f} ms) exceeds the "
            f"whole-generation wall-clock divide "
            f"({dt_full / new_tokens * 1e3:.2f} ms/tok) by >10% — the "
            f"subtraction arms disagree; treat throughput as invalid")
    return {
        "decode_tokens_per_sec_per_chip": round(batch_size / per_tok, 1),
        **rec_suspect,
        # first device call per shape: jit compile + execute. Timed apart
        # and excluded from every average above; recorded so a reader can
        # see the contamination that was discarded.
        "first_call_discarded_ms": {
            "full": round(first_full * 1e3, 1),
            "prefill": round(first_prefill * 1e3, 1)},
        "ms_per_decode_step": round(per_tok * 1e3, 3),
        "prefill_plus_first_token_ms": round(dt_prefill * 1e3, 1),
        "end_to_end_tokens_per_sec": round(
            batch_size * new_tokens / dt_full, 1),
        "batch_size": batch_size,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "generate_calls_timed": reps,
        "base_quant": cfg.base_quant,
        "param_dtype": str(cfg.param_dtype),
        "chips": 1,
    }


def bench_dlrm(iters: int, batch_size: int = 8192,
               scatter_ab: bool = False) -> dict:
    """DLRM examples/sec/chip (config 4 shape: 13 dense + 26 embeddings).

    Recommender steps are tiny-FLOP / gather-bound, so the headline here is
    examples/sec, not MFU. Reported in ``extra`` only.

    ``scatter_ab``: also run the Pallas-vs-XLA row-scatter falsification
    experiment at the bench shape (VERDICT r2 next-#9 — does a hand-rolled
    per-row DMA scatter beat the 92 ns/row XLA floor?).
    """
    import optax

    from distributeddeeplearningspark_tpu.data.feed import put_global, stack_examples
    from distributeddeeplearningspark_tpu.models import DLRM
    from distributeddeeplearningspark_tpu.models.dlrm import (
        dlrm_rules,
        sparse_embed_specs,
    )
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
    from distributeddeeplearningspark_tpu.train import embed, losses, optim
    from distributeddeeplearningspark_tpu.train import step as step_lib

    vocabs = (100_000,) * 26
    model = DLRM(vocab_sizes=vocabs, embed_dim=64,
                 bottom_mlp=(512, 256, 64))
    rng = np.random.default_rng(3)
    batch = stack_examples([
        {"dense": rng.normal(0, 1, (13,)).astype(np.float32),
         "sparse": np.array([rng.integers(0, v) for v in vocabs], np.int32),
         "label": np.int32(rng.integers(0, 2))}
        for _ in range(batch_size)])
    # tables train row-sparsely (train/embed.py): the dense step spent 93%
    # of device time on full-table gradient/optimizer/layout traffic
    # (op_breakdown, r2)
    specs = sparse_embed_specs(model, lr=1e-2)
    tx = optim.masked(optax.adagrad(1e-2), embed.dense_trainable(specs))
    mesh = MeshSpec(data=-1).build()
    state, shardings = step_lib.init_state(
        model, tx, batch, mesh, dlrm_rules(), sparse_embed=specs)
    from distributeddeeplearningspark_tpu.telemetry import anatomy as anatomy_lib

    step = anatomy_lib.instrument(
        step_lib.jit_train_step(
            embed.make_sparse_embed_train_step(
                model.apply, tx, losses.binary_xent, specs),
            mesh, shardings),
        name="bench-train_step")
    gbatch = put_global(batch, mesh)
    n_chips = mesh.devices.size
    step_time, times, _ = bench_steps(step, state, gbatch, iters=iters)
    rec = {
        "examples_per_sec_per_chip": round(batch_size / step_time / n_chips, 1),
        **_timing_fields(times, iters),
        **_ledger_fields(step),
        "mfu": 0.0,  # gather-bound; MFU is not the meaningful axis here
        "batch_size": batch_size,
        "embedding_rows": sum(vocabs),
        "chips": n_chips,
    }
    if scatter_ab:
        from distributeddeeplearningspark_tpu.ops.scatter_rows import (
            bench_scatter_ab)

        rec["scatter_ab"] = bench_scatter_ab(
            k=batch_size * 26, v=sum(vocabs), d=64, iters=max(5, iters // 2))
    return rec


def bench_input(iters: int, batch_size: int = 256, *, n_images: int = 256,
                size: int = 500) -> dict:
    """HOST input-pipeline throughput: JPEG decode → train augment → batch.

    SURVEY §7 hard-part #2: the device consumes ~2.5k images/sec/chip
    (ResNet-50 row above), so the per-host decode+augment rate bounds how
    many chips one host can feed. Synthetic JPEGs (PIL-encoded, ~real
    ImageNet dimensions) through the REAL path: ``imagenet_folder`` →
    ``imagenet_train`` (native C++ decode/crop/flip/normalize kernels
    with PIL/numpy fallbacks) → ``host_batches``. CPU-only — runs even
    when the TPU is down.
    """
    import tempfile

    from PIL import Image

    from distributeddeeplearningspark_tpu.data.feed import host_batches
    from distributeddeeplearningspark_tpu.data.sources import imagenet_folder
    from distributeddeeplearningspark_tpu.data.vision import imagenet_train
    from distributeddeeplearningspark_tpu.utils import native

    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as root:
        import os

        for cls in range(4):
            d = os.path.join(root, f"class_{cls:03d}")
            os.makedirs(d)
            for i in range(n_images // 4):
                arr = rng.integers(0, 255, (size, size, 3), np.uint8)
                Image.fromarray(arr).save(
                    os.path.join(d, f"img_{i:04d}.jpg"), quality=90)
        # decode=False + repeat=True: decode runs inside the parallel
        # transform, and one thread pool lives across epoch boundaries
        ds = imagenet_train(
            imagenet_folder(root, num_partitions=4, decode=False),
            seed=0, repeat=True)
        feed = host_batches(ds, batch_size)
        next(feed)  # warm caches / lazy imports
        t0 = time.perf_counter()
        seen = 0
        for _ in range(max(2, iters // 4)):
            b = next(feed)
            seen += len(b["label"])
        dt = time.perf_counter() - t0
        jpeg_rate = seen / dt

        # Record path (VERDICT r2 next-#5): materialize once (decode +
        # shorter-side resize baked in), then stream per-epoch augmentation
        # from the records — the rdd.cache() analog every real TPU input
        # pipeline uses to stop paying JPEG decode per epoch.
        from distributeddeeplearningspark_tpu.data.records import (
            array_records, write_imagenet_records)

        # sibling temp dir — NOT inside `root`: folder_classes() would pick
        # a nested records dir up as a class directory on a later scan
        rec_tmp = tempfile.TemporaryDirectory()
        rec_dir = rec_tmp.name
        t0 = time.perf_counter()
        write_imagenet_records(root, rec_dir, size=256, num_shards=4)
        mat_dt = time.perf_counter() - t0
        rec_feed = host_batches(
            imagenet_train(array_records(rec_dir), seed=0, repeat=True),
            batch_size)
        next(rec_feed)
        t0 = time.perf_counter()
        rec_seen = 0
        for _ in range(max(2, iters // 4)):
            b = next(rec_feed)
            rec_seen += len(b["label"])
        rec_dt = time.perf_counter() - t0
        rec_rate = rec_seen / rec_dt

        # batched-fused feed: ONE native varbatch augment call per batch,
        # written straight into the batch buffer (no per-example calls, no
        # np.stack pass — r3 profile: those were 62% of the
        # record path's host time)
        from distributeddeeplearningspark_tpu.data.vision import (
            imagenet_train_batched)

        fused_feed = imagenet_train_batched(
            array_records(rec_dir).shuffle(0).repeat(), batch_size, seed=0)
        next(fused_feed)
        t0 = time.perf_counter()
        fused_seen = 0
        for _ in range(max(2, iters // 4)):
            b = next(fused_feed)
            fused_seen += len(b["label"])
        fused_dt = time.perf_counter() - t0
        fused_rate = fused_seen / fused_dt

        # Multi-process worker sweep (ISSUE 5): the JPEG path again through
        # the data/workers.py process pool at nproc ∈ {1, half, all}
        # workers, single-partition so the worker count is exact. The
        # serial (num_workers=0, num_threads=0) rate is the 1-process
        # anchor; the curve reports this MACHINE's parallel ceiling — on
        # shared/throttled vCPUs the 2-process aggregate can be well under
        # 2× the single-process rate (measured 68 vs 2×47 img/s on the
        # 2-core CI box), and the recorded `nproc` makes that legible.
        nproc = os.cpu_count() or 1
        ds_one = imagenet_folder(root, num_partitions=1, decode=False)

        def _worker_rate(nw: int, num_threads=None) -> float:
            f = host_batches(
                imagenet_train(ds_one, seed=0, repeat=True, num_workers=nw,
                               num_threads=num_threads), batch_size)
            next(f)  # pools spin up + caches warm outside the window
            t0 = time.perf_counter()
            seen = 0
            for _ in range(max(2, iters // 4)):
                seen += len(next(f)["label"])
            r = seen / (time.perf_counter() - t0)
            f.close()
            return r

        sweep_counts = sorted({1, max(1, nproc // 2), nproc})
        workers_sweep = {"serial": round(_worker_rate(0, num_threads=0), 1)}
        for nw in sweep_counts:
            workers_sweep[str(nw)] = round(_worker_rate(nw), 1)
        full, one = workers_sweep[str(nproc)], workers_sweep["1"]
        rec_tmp.cleanup()

    # Distributed-shuffle transport arms (ISSUE 12, supersedes the ISSUE 8
    # cardinality curve): keys/sec of a 200k-key groupBy.agg (count+sum,
    # every key twice so the reduce really combines) through each data-
    # plane arm — `tuple` (per-key pickled payloads, the pre-columnar
    # ceiling), `columnar` (flat key-hash/key/value planes), `device`
    # (jitted segment-reduce combines, data/device_agg.py; warmed once so
    # the rate is the steady state, compile cost rides the compile_s
    # field), plus the serial driver-dict reference. All four produce
    # byte-identical output (asserted), so the rates compare identical
    # work. perf_guard baselines these fields by their transport-tagged
    # names, so pre-columnar rounds never judge the new arms against the
    # tuple ceiling. Same caveat as the pool sweep above: this box's
    # nproc bounds the honest ceiling, and `nproc` rides in the record.
    from distributeddeeplearningspark_tpu.data.dataframe import DataFrame
    from distributeddeeplearningspark_tpu.rdd import PartitionedDataset

    shuffle_card = 200_000

    def _agg_rate(transport: str, nw: int, *, warm: bool = False) -> float:
        nch = 4

        def chunk(i):
            j = i % nch  # chunks nch..2nch-1 repeat the key range: 2 pairs
            k = np.arange(j * shuffle_card // nch,
                          (j + 1) * shuffle_card // nch, dtype=np.int64)
            return {"k": k, "v": (k % 97).astype(np.float64)}

        def run() -> tuple[float, str]:
            import hashlib

            ds = PartitionedDataset.from_generators(
                [(lambda i=i: iter([chunk(i)])) for i in range(2 * nch)])
            g = DataFrame(ds, ["k", "v"]).groupBy("k").agg(
                {"v": "sum", "k": "count"},
                num_workers=nw, transport=transport)
            t0 = time.perf_counter()
            chunks = [ch for p in range(g._chunks.num_partitions)
                      for ch in g._chunks.iter_partition(p)]
            dt = time.perf_counter() - t0
            rows = sum(len(ch["k"]) for ch in chunks)
            assert rows == shuffle_card, (rows, shuffle_card)
            # digest over the CONCATENATED column stream: chunk
            # boundaries are layout, not content (they differ by arm)
            h = hashlib.blake2b(digest_size=16)
            for c in sorted(chunks[0]):
                h.update(np.ascontiguousarray(
                    np.concatenate([ch[c] for ch in chunks])).tobytes())
            return shuffle_card / dt, h.hexdigest()

        if warm:
            run()  # compile outside the window (first-record discipline)
        return run()

    shuffle_arms = {}
    shuffle_sums = {}
    for arm, (tr, nw, warm) in {
            "serial": ("tuple", 0, False),
            "tuple": ("tuple", nproc, False),
            "columnar": ("columnar", nproc, False),
            "device": ("device", 0, True)}.items():
        rate, digest = _agg_rate(tr, nw, warm=warm)
        shuffle_arms[arm] = round(rate, 1)
        shuffle_sums[arm] = digest
    assert len(set(shuffle_sums.values())) == 1, (
        f"transport arms diverged: {shuffle_sums}")

    # Shuffle recovery overhead (ISSUE 14): the SAME 200k-key corpus
    # through the columnar exchange with a mapper AND a reducer SIGKILLed
    # mid-run (die_shuffle_worker, role=both) — lineage retry must finish
    # it digest-identical, and the wall-clock delta vs a clean pass is
    # the price of self-healing (retained-frame replay + slice
    # recompute). BOTH arms pin DLS_SHUFFLE_MAX_RETRIES=3 so they run
    # the same retain-mode transport regardless of the ambient env — the
    # pct must mean "recovery cost", not "whatever transport the host
    # happened to configure", or perf_guard's history series would mix
    # incomparable values. LOWER_BETTER in tools/perf_guard.py.
    drill_env = {"DLS_SHUFFLE_MAX_RETRIES": "3"}
    fault_env = {"DLS_FAULT": "die_shuffle_worker@2",
                 "DLS_FAULT_SHUFFLE_ROLE": "both",
                 "DLS_FAULT_SHUFFLE_ID": "0"}
    saved_env = {k: os.environ.get(k) for k in {**drill_env, **fault_env}}
    os.environ.update(drill_env)
    try:
        clean_rate, clean_sum = _agg_rate("columnar", nproc)
        os.environ.update(fault_env)
        faulted_rate, faulted_sum = _agg_rate("columnar", nproc)
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert clean_sum == shuffle_sums["columnar"], (
        "retain-mode clean pass diverged from the transport arms")
    assert faulted_sum == clean_sum, (
        "faulted shuffle diverged from the clean run")
    recovery_overhead_pct = round(
        max(0.0, (clean_rate / max(faulted_rate, 1e-9) - 1.0)) * 100.0, 1)

    return {
        # keep this key's historical meaning (JPEG-decode path) so the series
        # stays comparable across rounds; the record path reports separately
        "host_images_per_sec": round(jpeg_rate, 1),
        "jpeg_path_images_per_sec": round(jpeg_rate, 1),
        "record_path_images_per_sec": round(rec_rate, 1),
        "record_batched_images_per_sec": round(fused_rate, 1),
        "record_vs_jpeg_speedup": round(rec_rate / jpeg_rate, 2),
        "batched_vs_jpeg_speedup": round(fused_rate / jpeg_rate, 2),
        # data/workers.py process-pool scaling curve, images/sec by worker
        # count ("serial" = num_workers=0 + num_threads=0, the 1-process
        # in-process map)
        "workers_sweep_images_per_sec": workers_sweep,
        "workers_speedup_full_vs_1": round(full / one, 2),
        "workers_speedup_full_vs_serial": round(
            full / workers_sweep["serial"], 2),
        # data/exchange.py shuffle transport arms: 200k-key groupBy.agg
        # keys/sec per data-plane format ("serial" = driver dict; the
        # others run the exchange/device paths — byte-identical output,
        # digest-asserted)
        "shuffle_keys_per_sec": shuffle_arms,
        "shuffle_cardinality": shuffle_card,
        # faulted (mapper+reducer killed) vs clean wall-clock on the same
        # corpus — the cost of shuffle self-healing (ISSUE 14)
        "shuffle_recovery_overhead_pct": recovery_overhead_pct,
        "shuffle_tuple_keys_per_sec": shuffle_arms["tuple"],
        "shuffle_columnar_keys_per_sec": shuffle_arms["columnar"],
        "shuffle_device_keys_per_sec": shuffle_arms["device"],
        "columnar_speedup_vs_tuple": round(
            shuffle_arms["columnar"] / max(shuffle_arms["tuple"], 1e-9), 2),
        "shuffle_speedup_full_vs_serial": round(
            shuffle_arms["tuple"] / max(shuffle_arms["serial"], 1e-9), 2),
        "materialize_images_per_sec": round(n_images / mat_dt, 1),
        "native_kernels": native.available(),
        "image_px": size,
        "record_px": 256,
        "batch_size": batch_size,
        "n_images": n_images,
        "jpeg_quality": 90,
        # the compile-ledger fields every device arm records — null here,
        # explicitly: a host-only round compiles no device step, and an
        # absent key would read as "not instrumented yet" to the
        # perf_guard sentinel rather than "nothing to measure"
        "compile_s": None,
        "recompile_count": None,
        "mfu": None,
        "anatomy_reason": ("host-only input-pipeline workload: no device "
                           "step compiled, so compile ledger and MFU do "
                           "not apply"),
        **_host_conditions(),
    }


def bench_mpmd(iters: int, *, batch_size: int = 8, seq: int = 96,
               microbatches: int = 4) -> dict:
    """MPMD 2-stage pipeline throughput + bubble fraction (ISSUE 13).

    Two in-process stage programs (exact mode, each on half the visible
    devices) over the real socket transport; the bubble fraction comes
    from the run's own trace spans (``telemetry.fleet.pipeline_anatomy``),
    so ``pipeline_bubble_frac`` gets cross-round regression teeth in
    ``tools/perf_guard.py`` — transport or scheduling regressions show up
    as bubble growth before they show up as lost steps/sec.
    """
    import secrets
    import shutil
    import tempfile
    import threading

    import jax
    import numpy as np
    import optax

    from distributeddeeplearningspark_tpu import telemetry
    from distributeddeeplearningspark_tpu.models import LlamaConfig
    from distributeddeeplearningspark_tpu.parallel import mpmd as mpmd_lib
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
    from distributeddeeplearningspark_tpu.supervisor import free_port
    from distributeddeeplearningspark_tpu.telemetry import fleet as fleet_lib
    from distributeddeeplearningspark_tpu.train.pipeline_trainer import (
        LlamaStageProgram,
        PipelineStageRunner,
        StageRunConfig,
        theoretical_bubble,
    )

    cfg = LlamaConfig.tiny()
    steps = max(6, iters)
    warmup = 2

    def batch_fn(step: int) -> dict:
        rng = np.random.default_rng(1000 + step)
        return {"input_ids": rng.integers(
                    0, cfg.vocab_size, (batch_size, seq)).astype(np.int32),
                "loss_mask": np.ones((batch_size, seq), np.float32)}

    devs = jax.devices()
    # each stage takes half the devices, capped so a microbatch still
    # shards (rows-per-microbatch must divide by the stage's data width)
    half = max(1, min(len(devs) // 2, batch_size // microbatches))
    stage_devs = [devs[:half], devs[half:half * 2] or devs[:half]]
    wd = tempfile.mkdtemp(prefix="dls_bench_mpmd_")
    telemetry.configure(wd)
    ports, key = [free_port()], secrets.token_bytes(16)
    results: dict = {}
    errors: dict = {}

    def run_stage(stage: int) -> None:
        try:
            mesh = MeshSpec(data=len(stage_devs[stage])).build(
                stage_devs[stage])
            prog = LlamaStageProgram(cfg, stage, 2, mesh,
                                     optax.adamw(1e-3), mode="exact")
            tr = mpmd_lib.PipelineTransport(stage, 2, ports, key,
                                            connect_timeout=300)
            r = PipelineStageRunner(
                prog, tr,
                StageRunConfig(steps=steps, batch_size=batch_size,
                               microbatches=microbatches, seed=0),
                batch_fn=batch_fn if stage == 0 else None)
            results[stage] = r.run()
        except BaseException as e:  # noqa: BLE001 — reported below
            errors[stage] = e

    ths = [threading.Thread(target=run_stage, args=(s,)) for s in range(2)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(1800)
    if any(t.is_alive() for t in ths):
        # a wedged stage must be a NAMED timeout, not a downstream
        # KeyError after teardown races the still-running writer
        raise RuntimeError(
            "mpmd bench stage(s) still running after 1800s "
            f"(alive: {[i for i, t in enumerate(ths) if t.is_alive()]})")
    if errors:
        raise RuntimeError(f"mpmd bench stage failed: {errors}")
    events = telemetry.read_events(wd)
    telemetry.reset()
    shutil.rmtree(wd, ignore_errors=True)
    laps = [float(e["lap_s"]) for e in events
            if e.get("kind") == "step_metrics" and e.get("process") == "p0"]
    timed = laps[warmup:] or laps
    pl = fleet_lib.pipeline_anatomy(events) or {}
    return {
        "steps_per_sec": round(len(timed) / sum(timed), 3) if timed else 0.0,
        "pipeline_bubble_frac": pl.get("measured_bubble_frac"),
        "theoretical_bubble_frac": (
            pl.get("theoretical_bubble_frac")
            or round(theoretical_bubble(microbatches, 2), 4)),
        "stages": 2,
        "devices_per_stage": half,
        "microbatches": microbatches,
        "batch_size": batch_size,
        "seq": seq,
        "steps": steps,
        "mode": "exact",
        "final_loss": (results[0]["losses"] or [None])[-1],
        **_host_conditions(),
    }


def bench_plan_sweep(iters: int, *, batch_size: int = 0, seq: int = 32) -> dict:
    """Measured layout search (tools/plan_sweep.py) as a bench arm.

    Runs the digest-asserted small-model sweep on this box's devices and
    records ``plan_sweep_best_steps_per_sec`` plus the winning plan id —
    ``tools/perf_guard.py`` guards the rate HIGHER_BETTER under its own
    field name, so pre-plan BENCH history contributes nothing and the new
    series builds its own baseline (the transport-tagged-name scoping
    discipline). The probe batch is content-addressed: the digest is
    computed twice independently and asserted equal, then recorded, so a
    cross-round comparison is a comparison of the same bytes.
    """
    import importlib.util

    import jax

    from distributeddeeplearningspark_tpu.models import LlamaConfig
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    spec = importlib.util.spec_from_file_location(
        "plan_sweep", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "tools", "plan_sweep.py"))
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)

    n = len(jax.devices())
    if n % 4 == 0:
        mesh = MeshSpec(data=n // 4, fsdp=2, seq=2).build()
    elif n % 2 == 0:
        mesh = MeshSpec(data=n // 2, fsdp=2).build()
    else:
        mesh = MeshSpec(data=n).build()
    cfg = LlamaConfig.tiny()
    shards = mesh.shape["data"] * mesh.shape["fsdp"]
    bs = batch_size or 2 * shards
    batch, digest = sweep._build_batch(cfg, bs, seq)
    _, digest2 = sweep._build_batch(cfg, bs, seq)
    assert digest == digest2, "probe batch is not content-stable"
    report = sweep.run_sweep(mesh, cfg, batch, steps=max(4, iters // 4),
                             warmup=1)
    ranked = report["ranked"]
    # an all-probes-failed sweep must be a FAILED arm, not a 0.0 record
    # quietly entering BENCH history (the skipped rows carry the reasons)
    assert ranked, f"sweep ranked no plans: {report.get('skipped')}"
    assert ranked == sorted(ranked, key=lambda r: r["step_time_s"]), \
        "ranked table not ordered by measured step time"
    return {
        "plan_sweep_best_steps_per_sec": report.get("best_steps_per_sec"),
        "winning_plan": report.get("winner"),
        "winning_plan_sig": report.get("winner_sig"),
        "winner_rerun_new_compiles": report.get("winner_rerun_new_compiles"),
        "plans_ranked": [
            {k: r.get(k) for k in
             ("plan", "plan_sig", "step_time_s", "steps_per_sec", "mfu",
              "bytes_accessed", "peak_hbm_bytes", "compile_s",
              "argument_bytes", "compiles", "recompiles")}
            for r in ranked],
        "plans_skipped": report.get("skipped"),
        "batch_digest": digest,
        "batch_size": bs,
        "seq": seq,
        "mesh": report["mesh"],
        **_host_conditions(),
    }


def pallas_smoke() -> dict:
    """Mosaic-compile flash attention fwd + both bwd kernels on the chip and
    compare output and gradients with ``_xla_attention`` at the repo's bf16
    tolerance (tests/test_flash_attention.py: atol = rtol = 5e-2, here
    relative to the reference's largest magnitude).

    Covers the regimes the models use: causal d=128 (Llama), key-padding
    mask d=64 (BERT-base), GQA grouped KV, and packed documents (padding
    mask + segment ids). A kernel Mosaic refuses, or one that disagrees with
    the reference, raises.
    """
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.ops.attention import _xla_attention
    from distributeddeeplearningspark_tpu.ops.flash_attention import flash_attention
    from distributeddeeplearningspark_tpu.utils.env import on_tpu

    cases = {
        "causal_d128": dict(b=2, s=1024, h=4, hkv=4, d=128, causal=True),
        "masked_d64_bert": dict(b=2, s=512, h=12, hkv=12, d=64, mask=True),
        "gqa_causal_d128": dict(b=1, s=1024, h=8, hkv=2, d=128, causal=True),
        "masked_segments_d64": dict(b=2, s=1024, h=12, hkv=12, d=64,
                                    mask=True, segments=True),
    }
    results = {}
    for name, c in cases.items():
        b, s, h, hkv, d = c["b"], c["s"], c["h"], c["hkv"], c["d"]
        causal = c.get("causal", False)
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, s, hkv, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, s, hkv, d), jnp.bfloat16)
        pad = segs = None
        if c.get("mask"):  # the last eighth of every row is padding
            pad = (jnp.arange(s)[None, :] < s - s // 8).astype(
                jnp.int32).repeat(b, axis=0)
        if c.get("segments"):  # three documents a row, uneven boundaries
            segs = jnp.searchsorted(jnp.asarray([s // 5, s // 2]),
                                    jnp.arange(s), side="right").astype(
                jnp.int32)[None, :].repeat(b, axis=0)

        def flash_loss(q, k, v):
            o = flash_attention(q, k, v, mask=pad, causal=causal,
                                segment_ids=segs)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        def ref_loss(q, k, v):
            kr, vr = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
            m = None if pad is None else (pad > 0)[:, None, None, :]
            if segs is not None:
                same = segs[:, None, :, None] == segs[:, None, None, :]
                m = same if m is None else jnp.logical_and(m, same)
            o = _xla_attention(q, kr, vr, bias=None, mask=m, causal=causal,
                               scale=None)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        grad = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
            f, argnums=(0, 1, 2), has_aux=True))
        (_, o), g = grad(flash_loss)(q, k, v)
        (_, o_ref), g_ref = grad(ref_loss)(q, k, v)
        errs = {}
        for label, got, want in (("out", o, o_ref), ("dq", g[0], g_ref[0]),
                                 ("dk", g[1], g_ref[1]), ("dv", g[2], g_ref[2])):
            got = np.asarray(got, np.float32)
            want = np.asarray(want, np.float32)
            scale = float(np.max(np.abs(want)))
            errs[label] = round(float(np.max(np.abs(got - want))) / scale, 5)
            if not np.allclose(got, want, atol=5e-2 * scale, rtol=5e-2):
                raise AssertionError(
                    f"flash {name}: {label} disagrees with _xla_attention "
                    f"(max abs err / max |ref| = {errs[label]})")
        results[name] = {"kernel": "mosaic" if on_tpu() else "interpret",
                         "max_err_over_max_ref": errs}
    return results


def bench_kernels(*, conv_m: int = 0, scatter_v: int = 0) -> dict:
    """Mosaic compile + parity for the two r3 Pallas kernels (VERDICT r3
    weak-#1): ``ops/conv_bn.matmul_stats`` and
    ``ops/scatter_rows.scatter_add_rows`` were interpret-verified only, and
    r2 precedent says interpret-green kernels can still fail Mosaic's
    block-tiling rules on first chip contact. This mode forces the compiled
    path (Mosaic on a TPU; interpret elsewhere, labeled), checks
    numerics against the XLA reference chains fwd+bwd, and times both.
    Independent failures: one kernel's Mosaic rejection still reports the
    other's result.
    """
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.utils.env import on_tpu

    backend = jax.default_backend()
    on_device = on_tpu()
    rec: dict = {"backend": backend,
                 "mode": "compiled" if on_device else "interpret"}

    def timed(fn, *a):
        # timing is only meaningful for the compiled path; interpret-mode
        # Pallas walks the grid in Python and would take minutes
        if not on_device:
            return None
        out = fn(*a)  # warm
        leaf = jax.tree.leaves(out)[0]
        float(jax.device_get(jnp.sum(leaf.astype(jnp.float32))))
        n = 10
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*a)
        leaf = jax.tree.leaves(out)[0]
        float(jax.device_get(jnp.sum(leaf.astype(jnp.float32))))
        return (time.perf_counter() - t0) / n

    def ms(dt):
        return None if dt is None else round(dt * 1e3, 3)

    # --- conv_bn: ResNet stage-3 conv3 expansion shape (the fattest 1x1) ---
    try:
        from distributeddeeplearningspark_tpu.ops.conv_bn import matmul_stats

        m = conv_m or (256 * 14 * 14 if on_device else 512)
        k, n = 256, 1024
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (m, k), jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.bfloat16)
        c1 = jax.random.normal(jax.random.PRNGKey(2), (m, n), jnp.bfloat16)
        c2 = jax.random.normal(jax.random.PRNGKey(3), (n,), jnp.float32)

        def fused(x, w):
            y, s1, s2 = matmul_stats(x, w)
            return (jnp.sum(y.astype(jnp.float32) * c1.astype(jnp.float32))
                    + jnp.sum(s1 * c2) + jnp.sum(s2 * c2))

        def ref(x, w):
            y = jnp.dot(x, w, preferred_element_type=jnp.float32)
            s1, s2 = jnp.sum(y, 0), jnp.sum(y * y, 0)
            return (jnp.sum(y.astype(jnp.bfloat16).astype(jnp.float32)
                            * c1.astype(jnp.float32))
                    + jnp.sum(s1 * c2) + jnp.sum(s2 * c2))

        f_val, f_grads = jax.jit(jax.value_and_grad(fused, (0, 1)))(x, w)
        r_val, r_grads = jax.jit(jax.value_and_grad(ref, (0, 1)))(x, w)
        scale = float(jnp.abs(r_val)) + 1e-6
        gdiff = max(
            float(jnp.max(jnp.abs(fg.astype(jnp.float32)
                                  - rg.astype(jnp.float32))))
            / (float(jnp.max(jnp.abs(rg.astype(jnp.float32)))) + 1e-6)
            for fg, rg in zip(f_grads, r_grads))
        rec["conv_bn"] = {
            "compile": "ok",
            "shape_mkn": [m, k, n],
            "fwd_bwd_val_rel_err": round(abs(float(f_val - r_val)) / scale, 6),
            "grad_max_rel_err": round(gdiff, 6),
            "fused_ms": ms(timed(
                jax.jit(lambda x, w: matmul_stats(x, w)), x, w)),
            "xla_chain_ms": ms(timed(
                jax.jit(lambda x, w: (
                    (y := jnp.dot(x, w, preferred_element_type=jnp.float32))
                    .astype(jnp.bfloat16), jnp.sum(y, 0), jnp.sum(y * y, 0))),
                x, w)),
        }
    except Exception as e:  # noqa: BLE001 — report per-kernel, don't crash
        rec["conv_bn"] = {"compile": f"FAIL: {type(e).__name__}: {str(e)[:300]}"}

    # --- scatter_rows: row-granular scatter-add, unique in-range ids ---
    try:
        from distributeddeeplearningspark_tpu.ops.scatter_rows import (
            scatter_add_rows)

        v = scatter_v or (262_144 if on_device else 1024)
        d, kk = 64, min(8192 if on_device else 128, v // 2)
        rng = np.random.default_rng(0)
        idx = jnp.asarray(rng.choice(v, size=kk, replace=False).astype(np.int32))
        table = jax.random.normal(jax.random.PRNGKey(4), (v, d), jnp.float32)
        upd = jax.random.normal(jax.random.PRNGKey(5), (kk, d), jnp.float32)
        got = scatter_add_rows(table, idx, upd)
        want = table.at[idx].add(upd, unique_indices=True)
        rec["scatter_rows"] = {
            "compile": "ok",
            "shape_vdk": [v, d, kk],
            "max_abs_err": float(jnp.max(jnp.abs(got - want))),
            "pallas_ns_per_row": None if (dt := timed(
                jax.jit(scatter_add_rows), table, idx, upd)) is None
                else round(dt / kk * 1e9, 1),
            "xla_ns_per_row": None if (dt2 := timed(
                jax.jit(lambda t, i, u: t.at[i].add(u, unique_indices=True)),
                table, idx, upd)) is None else round(dt2 / kk * 1e9, 1),
        }
    except Exception as e:  # noqa: BLE001
        rec["scatter_rows"] = {
            "compile": f"FAIL: {type(e).__name__}: {str(e)[:300]}"}

    # --- ulysses: single-chip smoke through the CP all-to-all path ---
    # (VERDICT r4 weak-#7) seq degree 1 degenerates the all-to-alls to
    # identity, but the call still walks ulysses_attention's real code:
    # shard_map tracing, the _flash_hop_qualifies gate on full S, and —
    # on device — the Mosaic-compiled flash kernel inside the shard_map
    # body. That combination (Pallas under shard_map, Mosaic-compiled) is exactly
    # the interpret-vs-Mosaic risk class that bit r2, and it had never
    # met the real chip before this item.
    try:
        from distributeddeeplearningspark_tpu.ops.flash_attention import (
            flash_attention)
        from distributeddeeplearningspark_tpu.ops.ulysses import (
            ulysses_attention)
        from distributeddeeplearningspark_tpu.parallel.mesh import (
            single_device_mesh)

        mesh1 = single_device_mesh()
        b, s, h, d = 2, 1024, 8, 128
        key = jax.random.PRNGKey(7)
        q = jax.random.normal(key, (b, s, h, d), jnp.bfloat16)
        k1 = jax.random.normal(jax.random.PRNGKey(8), (b, s, h, d), jnp.bfloat16)
        v1 = jax.random.normal(jax.random.PRNGKey(9), (b, s, h, d), jnp.bfloat16)
        out = ulysses_attention(q, k1, v1, mesh=mesh1, causal=True)
        ref_out = flash_attention(q, k1, v1, causal=True,
                                  interpret=not on_device)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref_out.astype(jnp.float32))))
        rec["ulysses_smoke"] = {
            "compile": "ok",
            "shape_bshd": [b, s, h, d],
            "flash_inside_shard_map": on_device,
            "max_abs_err_vs_direct_flash": err,
            "finite": bool(np.isfinite(err)),
        }
    except Exception as e:  # noqa: BLE001
        rec["ulysses_smoke"] = {
            "compile": f"FAIL: {type(e).__name__}: {str(e)[:300]}"}
    return rec


def bench_memval() -> dict:
    """Compiler-vs-analytic memory validation (VERDICT r3 next-#7).

    AOT-compiles the 0.9b bench train step (and the 7b geometry, compile
    only — no weights materialized, so a too-big program fails in the
    compiler rather than wedging the chip) and compares
    ``compiled.memory_analysis()`` against ``utils/memory.py``'s analytic
    budget, so the "2x largest in-flight tensor" workspace fudge
    (memory.py:161-165) gets a measured delta and the 12.5-18 GiB test
    window can be tightened.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from distributeddeeplearningspark_tpu.models import (
        LlamaConfig, LlamaForCausalLM, llama_rules, lora_trainable)
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
    from distributeddeeplearningspark_tpu.train import (
        losses, optim, step as step_lib)
    from distributeddeeplearningspark_tpu.utils.memory import (
        GiB, llama_memory_report)

    rec: dict = {"backend": jax.default_backend()}
    shapes = {
        # the SAME config objects the bench series runs (shared helpers) —
        # validating any other shape would calibrate the workspace fudge
        # against a program the series never executes
        "0.9b": (_llama_09b_cfg(), 4, 2048),
        "7b": (LlamaConfig.llama2_7b(
            lora_rank=16, dtype="bfloat16", max_position=1024,
            remat_policy=None, fused_head_loss=True), 1, 1024),
        # int8 storage model (r4 session-2): 1 B kernels + f32 scales —
        # validates the quantized-base byte accounting the llama_7b_int8_b2
        # fit prediction rests on
        "7b_int8": (LlamaConfig.llama2_7b(
            lora_rank=16, dtype="bfloat16", max_position=2048,
            remat_policy=None, fused_head_loss=True,
            base_quant="int8"), 2, 2048),
    }
    for name, (cfg, b, s) in shapes.items():
        try:
            model = LlamaForCausalLM(cfg)
            mesh = MeshSpec(data=-1).build()
            tx = optim.masked(optax.adamw(1e-4), lora_trainable)
            batch = {"input_ids": jax.ShapeDtypeStruct((b, s), jnp.int32),
                     "loss_mask": jax.ShapeDtypeStruct((b, s), jnp.float32)}

            def init_fn(rng, _model=model, _tx=tx, _b=b, _s=s):
                variables = dict(_model.init(
                    {"params": rng, "dropout": rng},
                    {"input_ids": jnp.zeros((_b, _s), jnp.int32)}, train=False))
                params = variables.pop("params")
                return step_lib.TrainState.create(
                    params=params, opt_state=_tx.init(params),
                    mutable=variables, rng=rng, embed_state={})

            abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
            shardings = step_lib.state_shardings(abstract, mesh,
                                                 llama_rules(cfg))
            jitted = step_lib.jit_train_step(
                step_lib.make_train_step(
                    model.apply, tx,
                    losses.causal_lm_fused if cfg.fused_head_loss
                    else losses.causal_lm,
                    trainable=lora_trainable),
                mesh, shardings)
            t0 = time.perf_counter()
            compiled = jitted.lower(abstract, batch).compile()
            ma = compiled.memory_analysis()
            fields = {}
            for f in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                val = getattr(ma, f, None)
                if val is not None:
                    fields[f.replace("_size_in_bytes", "_gib")] = round(
                        int(val) / GiB, 3)
            # donation aliases args into outputs — live bytes are
            # max(args, outputs) + temps, not their sum
            live = (max(fields.get("argument_gib", 0.0),
                        fields.get("output_gib", 0.0))
                    + fields.get("temp_gib", 0.0))
            analytic = llama_memory_report(
                cfg, batch=b, seq=s, mesh_shape={}).to_dict()
            rec[name] = {
                "compile_s": round(time.perf_counter() - t0, 1),
                "compiled": fields,
                "compiled_live_gib": round(live, 3),
                "analytic_total_gib": analytic["total_gib_per_chip"],
                "analytic_components_gib": analytic["per_chip_gib"],
                "model_vs_compiler_pct": round(
                    (analytic["total_gib_per_chip"] - live) / live * 100, 1)
                    if live > 0 else None,
            }
        except Exception as e:  # noqa: BLE001 — 7b may exceed the compiler's
            # memory budget on a dev chip; that is itself a data point
            rec[name] = {"error": f"{type(e).__name__}: {str(e)[:400]}"}
    return rec


def emit(metric: str, value: float, unit: str, vs_baseline: float, extra: dict,
         headline: dict | None = None) -> None:
    """One JSON line. ``metric``/``value`` keep their series-comparable
    historical meaning; ``headline`` (VERDICT r3 weak-#2) names the round's
    BEST-path number explicitly so a host-only record can't read as
    stagnation in a dashboard that parses only the top-level value."""
    rec = {
        "metric": metric, "value": value, "unit": unit,
        "vs_baseline": vs_baseline, "extra": extra,
    }
    if headline is not None:
        rec["headline"] = headline
    print(json.dumps(rec))


def _devices() -> tuple[str, str, int]:
    """(platform, device_kind, device count) as jax reports them."""
    import jax

    devs = jax.devices()
    return devs[0].platform, devs[0].device_kind, len(devs)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model",
                    choices=["all", "resnet", "bert", "llama", "dlrm", "input",
                             "mpmd", "plan", "kernels", "memval"],
                    default="all")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=0,
                    help="override per-model default batch size (debug)")
    ap.add_argument("--seq", type=int, default=0,
                    help="override BERT sequence length (debug)")
    ap.add_argument("--scatter-ab", action="store_true",
                    help="dlrm only: Pallas-vs-XLA row-scatter experiment "
                         "at the bench shape (VERDICT r2 next-#9)")
    ap.add_argument("--variant", default="0.9b",
                    choices=["0.9b", "7b", "tiny"],
                    help="llama only: 0.9b single-chip proxy (default), "
                         "the real 7B geometry attempt + memory budget "
                         "(VERDICT r2 next-#3), or a CPU-runnable tiny "
                         "shape for relative A/Bs (MoE table)")
    ap.add_argument("--fused-conv-bn", action="store_true",
                    help="resnet only: Pallas 1x1-conv+BN-stats epilogue "
                         "kernel in the bottlenecks (byte-diet A/B)")
    ap.add_argument("--op-profile", action="store_true",
                    help="resnet only: capture a 5-step trace after timing "
                         "and embed the per-op device-time budget in the "
                         "record (feeds the v4-32 MFU projection, VERDICT "
                         "r4 next-#2)")
    ap.add_argument("--segment-ids", action="store_true",
                    help="bert/llama: bench the packed-document shape "
                         "(segment ids streamed into the flash kernel) — "
                         "prices cross-document isolation vs plain packing")
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="llama only: swap the FFN for top-2 routed "
                         "experts, E of them (0 = dense) — relative "
                         "step-time prices routing, sort and gathers")
    ap.add_argument("--base-quant", default=None, choices=["int8"],
                    help="llama only: QLoRA-style int8 frozen-base storage "
                         "(per-out-channel absmax scales; base HBM bytes "
                         "halve again vs bf16 — at 7B the base drops to "
                         "~6.3 GiB, the b=2 single-chip lever)")
    ap.add_argument("--fused-head-loss", action="store_true",
                    help="llama only: fuse the LM-head matmul into the loss "
                         "(A/B vs materialized [B,S,V] logits)")
    ap.add_argument("--decode", action="store_true",
                    help="llama only: KV-cached generation throughput at "
                         "the 0.9b shape instead of the train step; with "
                         "--base-quant int8 it prices the halved per-token "
                         "weight reads (the serving-side int8 claim)")
    ap.add_argument("--skip-smoke", action="store_true")
    return ap


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.base_quant and args.model not in ("llama", "all"):
        # a silently ignored flag would let a bf16 run masquerade as the
        # int8 number
        parser.error("--base-quant only applies to the llama bench")
    if args.decode and args.model != "llama":
        parser.error("--decode only applies to the llama bench")
    if args.decode and (args.seq or args.variant != "0.9b"
                        or args.fused_head_loss or args.segment_ids
                        or args.moe_experts):
        # no silently-ignored flags (the --base-quant guard pattern): the
        # decode bench pins the 0.9b dense geometry at
        # prompt=128/new=128 — a requested shape that was dropped would
        # masquerade as a measured series number
        parser.error("--decode supports only --batch/--iters/--base-quant; "
                     "it pins the 0.9b dense prompt=128/new=128 shape")

    extra: dict = {"errors": []}
    host_only = args.model == "input"
    if host_only:
        # host-only workload: the one jitted piece (the shuffle's device
        # arm) is held to the host CPU, set before jax is first imported,
        # and main never asks for a device
        os.environ["JAX_PLATFORMS"] = "cpu"
        extra["backend"] = "host"
        on_chip = False
        args.skip_smoke = True
    else:
        extra["platform"], extra["device"], extra["device_count"] = _devices()
        on_chip = extra["platform"] == "tpu"
        if args.model in DEVICE_ARMS and not on_chip:
            print(f"bench.py: --model {args.model} is a device arm and jax "
                  f"found no TPU (platform {extra['platform']!r}); a CPU "
                  f"number is never written under a device metric's name",
                  file=sys.stderr)
            return 1

    def hbm_stats() -> dict | None:
        if host_only:
            return None
        import jax

        s = jax.local_devices()[0].memory_stats() or {}
        keep = {k: int(v) for k, v in s.items()
                if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
        return keep or None

    want = {"all": ("resnet50", "bert_base_mlm", "llama_lora", "dlrm",
                    "input_pipeline"),
            "resnet": ("resnet50",),
            "bert": ("bert_base_mlm",),
            "llama": ("llama_decode",) if args.decode else ("llama_lora",),
            "dlrm": ("dlrm",),
            "input": ("input_pipeline",),
            "mpmd": ("mpmd_pipeline",),
            "plan": ("plan_sweep",),
            "kernels": ("pallas_kernels",),
            "memval": ("memory_validation",)}[args.model]
    runners = {
        "resnet50": lambda: bench_resnet(
            args.iters, fused_conv_bn=args.fused_conv_bn,
            op_profile=args.op_profile,
            **({"batch_size": args.batch} if args.batch else {})),
        "bert_base_mlm": lambda: bench_bert(
            args.iters,
            segment_ids=args.segment_ids,
            **({"batch_size": args.batch} if args.batch else {}),
            **({"seq": args.seq} if args.seq else {})),
        "llama_lora": lambda: bench_llama(
            max(5, args.iters // 2),
            fused_head=args.fused_head_loss,
            segment_ids=args.segment_ids,
            moe_experts=args.moe_experts,
            base_quant=args.base_quant,
            variant=args.variant,
            **({"batch_size": args.batch} if args.batch else {}),
            **({"seq": args.seq} if args.seq else {})),
        "input_pipeline": lambda: bench_input(
            args.iters, **({"batch_size": args.batch} if args.batch else {})),
        "mpmd_pipeline": lambda: bench_mpmd(
            args.iters, **({"batch_size": args.batch} if args.batch else {}),
            **({"seq": args.seq} if args.seq else {})),
        "plan_sweep": lambda: bench_plan_sweep(
            args.iters, **({"batch_size": args.batch} if args.batch else {}),
            **({"seq": args.seq} if args.seq else {})),
        "dlrm": lambda: bench_dlrm(
            args.iters, scatter_ab=args.scatter_ab,
            **({"batch_size": args.batch} if args.batch else {})),
        "llama_decode": lambda: bench_llama_decode(
            args.iters, base_quant=args.base_quant,
            **({"batch_size": args.batch} if args.batch else {})),
        "pallas_kernels": bench_kernels,
        "memory_validation": bench_memval,
    }
    results: dict = {}
    failed: list[str] = []
    runs = [(name, runners[name]) for name in want]
    if not args.skip_smoke and on_chip:
        runs.append(("pallas_smoke", pallas_smoke))
    for name, run in runs:
        try:
            results[name] = run()
        except Exception as e:  # noqa: BLE001 — the other arms still report;
            # the exit code carries the failure
            failed.append(name)
            extra["errors"].append(f"{name}: {type(e).__name__}: {str(e)[:300]}")
    rc = 1 if failed else 0
    # process-lifetime HBM watermark (peak_bytes_in_use is monotonic across
    # the whole process, so per-workload attribution would be wrong)
    mem = hbm_stats()
    if mem:
        extra["hbm_process"] = mem

    extra.update(results)
    if "resnet50" in results:
        name, r = "resnet50", results["resnet50"]
        value, unit = r["images_per_sec_per_chip"], "images/sec/chip"
        metric = "resnet50_images_per_sec_per_chip"
    elif "bert_base_mlm" in results:
        name, r = "bert_base_mlm", results["bert_base_mlm"]
        value, unit = r["tokens_per_sec_per_chip"], "tokens/sec/chip"
        metric = "bert_base_mlm_tokens_per_sec_per_chip"
    elif "llama_lora" in results:
        name, r = "llama_lora", results["llama_lora"]
        # the 7b variant's structured OOM-evidence record has no throughput
        # key — emit it with value 0 rather than crashing (the record IS the
        # round's evidence)
        value, unit = r.get("tokens_per_sec_per_chip", 0.0), "tokens/sec/chip"
        metric = "llama_lora_tokens_per_sec_per_chip"
    elif "llama_decode" in results:
        name, r = "llama_decode", results["llama_decode"]
        value, unit = r["decode_tokens_per_sec_per_chip"], "tokens/sec/chip"
        metric = "llama_decode_tokens_per_sec_per_chip"
    elif "dlrm" in results:
        name, r = "dlrm", results["dlrm"]
        value, unit = r["examples_per_sec_per_chip"], "examples/sec/chip"
        metric = "dlrm_examples_per_sec_per_chip"
    elif "input_pipeline" in results:
        name, r = "input_pipeline", results["input_pipeline"]
        value, unit = r["host_images_per_sec"], "images/sec/host"
        metric = "input_pipeline_host_images_per_sec"
    elif "mpmd_pipeline" in results:
        r = results["mpmd_pipeline"]
        emit("mpmd_pipeline_steps_per_sec", r["steps_per_sec"], "steps/sec",
             0.0, {**extra, **results},
             headline={
                 "metric": "mpmd_pipeline_steps_per_sec",
                 "value": r["steps_per_sec"], "unit": "steps/sec",
                 "note": (f"2-stage exact pipeline, bubble "
                          f"{r['pipeline_bubble_frac']} vs bound "
                          f"{r['theoretical_bubble_frac']}")})
        return rc
    elif "plan_sweep" in results:
        r = results["plan_sweep"]
        emit("plan_sweep_best_steps_per_sec",
             r["plan_sweep_best_steps_per_sec"] or 0.0, "steps/sec",
             0.0, {**extra, **results},
             headline={
                 "metric": "plan_sweep_best_steps_per_sec",
                 "value": r["plan_sweep_best_steps_per_sec"],
                 "unit": "steps/sec",
                 "note": (f"winner {r['winning_plan']} "
                          f"[{r['winning_plan_sig']}] over "
                          f"{len(r['plans_ranked'])} ranked plan(s), "
                          f"batch digest {r['batch_digest']}")})
        return rc
    elif "pallas_kernels" in results:
        r = results["pallas_kernels"]
        n_ok = sum(1 for kn in ("conv_bn", "scatter_rows", "ulysses_smoke")
                   if r.get(kn, {}).get("compile") == "ok")
        emit("pallas_kernels_compiled", float(n_ok), "kernels",
             n_ok / 3.0, {**extra, **results},
             headline={"metric": "pallas_kernels_compiled", "value": n_ok,
                       "unit": f"of 3 kernel paths ({r.get('mode')})"})
        return rc if n_ok == 3 else 1
    elif "memory_validation" in results:
        r = results["memory_validation"]
        delta = (r.get("0.9b") or {}).get("model_vs_compiler_pct")
        emit("memory_model_vs_compiler_pct",
             float(delta) if delta is not None else 0.0, "pct",
             0.0, {**extra, **results},
             headline={"metric": "memory_model_vs_compiler_pct",
                       "value": delta,
                       "unit": "analytic minus compiled-live, % of compiled"})
        return rc
    else:
        emit("bench_failed", 0.0, "none", 0.0, extra)
        return 1
    # `or`-chained, not .get-defaulted: the input_pipeline arm now records
    # an EXPLICIT "mfu": None (host arm, with a reason), which must fall
    # through to 0.0 here, not reach the round() below as None
    mfu = ((r.get("mfu") or r.get("mfu_model")
            or r.get("mfu_hlo_scan_opaque") or 0.0)
           if on_chip else 0.0)
    if any("timing_suspect" in res for res in results.values()):
        # a physically impossible measurement must not masquerade as a
        # headline number — surface it at the top level and zero the ratio
        extra["errors"].extend(
            f"{n}: {res['timing_suspect']}"
            for n, res in results.items() if "timing_suspect" in res)
        mfu = 0.0
    if name == "input_pipeline" and "record_batched_images_per_sec" in r:
        # host mode: the top-level value keeps the historical JPEG-path
        # series; the headline names the best path so the record
        # self-describes the round's actual result (r3 weak-#2)
        headline = {
            "metric": "input_pipeline_record_batched_images_per_sec",
            "value": r["record_batched_images_per_sec"],
            "unit": "images/sec/host",
            "note": "best-path host rate; top-level value is the "
                    "series-comparable JPEG path",
        }
    else:
        headline = {"metric": metric, "value": value, "unit": unit}
    emit(metric, value, unit, round(mfu / 0.50, 4), extra, headline=headline)
    return rc


if __name__ == "__main__":
    sys.exit(main())
