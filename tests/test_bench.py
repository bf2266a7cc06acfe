"""bench.py contract: one process, device arms refuse to run without a TPU,
a raising arm still lets the others report but makes the exit code non-zero,
and ``--model input`` never asks for a device."""

import json
import subprocess
import sys

import jax
import numpy as np

import bench

_FAKE_ARMS = {
    "bench_resnet": {"images_per_sec_per_chip": 123.0, "mfu": 0.5,
                     "step_time_ms": 1.0, "batch_size": 8, "chips": 1},
    "bench_bert": {"tokens_per_sec_per_chip": 1.0, "mfu": 0.3,
                   "step_time_ms": 1.0, "batch_size": 32, "seq_len": 512,
                   "chips": 1},
    "bench_llama": {"tokens_per_sec_per_chip": 1.0,
                    "mfu_hlo_scan_opaque": 0.1, "step_time_ms": 1.0,
                    "params": 1, "batch_size": 4, "seq_len": 2048,
                    "chips": 1},
    "bench_dlrm": {"examples_per_sec_per_chip": 1.0, "mfu": 0.0,
                   "step_time_ms": 1.0, "batch_size": 8192,
                   "embedding_rows": 1, "chips": 1},
    "bench_input": {"host_images_per_sec": 42.0},
}


def _fake_chip(monkeypatch, **overrides):
    """Pretend jax reported one v5e and stub every arm of ``--model all``
    (``overrides``: arm name -> record, or an exception to raise)."""
    monkeypatch.setattr(bench, "_devices", lambda: ("tpu", "TPU v5 lite", 1))
    monkeypatch.setattr(bench, "pallas_smoke", lambda: {"causal_d128": "ok"})
    for arm, rec in {**_FAKE_ARMS, **overrides}.items():
        def run(*a, _rec=rec, **kw):
            if isinstance(_rec, Exception):
                raise _rec
            return dict(_rec)

        monkeypatch.setattr(bench, arm, run)


def _last_json(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_device_arm_without_tpu_exits_nonzero(capsys):
    """The suite runs on the CPU backend: a device arm must refuse, print no
    result, and say why on stderr — never fall back to a host number."""
    assert bench.main(["--model", "bert"]) != 0
    out = capsys.readouterr()
    assert out.out.strip() == ""
    assert "no TPU" in out.err


def test_arm_that_raises_exits_nonzero_and_the_others_report(monkeypatch,
                                                             capsys):
    _fake_chip(monkeypatch,
               bench_bert=RuntimeError("RESOURCE_EXHAUSTED: OOM"))
    assert bench.main([]) == 1
    rec = _last_json(capsys)
    assert rec["metric"] == "resnet50_images_per_sec_per_chip"
    assert rec["value"] == 123.0
    assert any("OOM" in e for e in rec["extra"]["errors"])
    assert rec["extra"]["pallas_smoke"] == {"causal_d128": "ok"}
    assert rec["extra"]["platform"] == "tpu"
    assert rec["extra"]["device"] == "TPU v5 lite"


def test_clean_run_exits_zero(monkeypatch, capsys):
    _fake_chip(monkeypatch)
    assert bench.main([]) == 0
    assert _last_json(capsys)["extra"]["errors"] == []


def test_failing_kernel_smoke_exits_nonzero(monkeypatch, capsys):
    _fake_chip(monkeypatch)

    def mosaic_refuses():
        raise AssertionError("flash masked_d64_bert: dq disagrees")

    monkeypatch.setattr(bench, "pallas_smoke", mosaic_refuses)
    assert bench.main(["--model", "bert"]) == 1
    assert any("pallas_smoke" in e
               for e in _last_json(capsys)["extra"]["errors"])


def test_every_arm_failing_is_bench_failed_nonzero(monkeypatch, capsys):
    _fake_chip(monkeypatch, bench_bert=ValueError("boom"))
    assert bench.main(["--model", "bert", "--skip-smoke"]) == 1
    assert _last_json(capsys)["metric"] == "bench_failed"


def test_platform_agnostic_arm_that_raises_exits_nonzero(monkeypatch, capsys):
    """mpmd/plan run on whatever devices exist — and still may not hide a
    failure behind rc 0."""
    def boom(*a, **kw):
        raise RuntimeError("sweep ranked no plans")

    monkeypatch.setattr(bench, "bench_plan_sweep", boom)
    assert bench.main(["--model", "plan"]) == 1
    assert _last_json(capsys)["metric"] == "bench_failed"


def test_input_mode_never_asks_for_a_device(monkeypatch, capsys):
    """--model input is host-only: jax is held to the CPU before bench_input
    runs and main never touches jax.devices()."""
    def no_devices():
        raise AssertionError("--model input asked jax for a device")

    seen = {}

    def fake_input(iters, **kw):
        import os

        seen["platforms"] = os.environ.get("JAX_PLATFORMS")
        return {"host_images_per_sec": 42.0,
                "record_batched_images_per_sec": 99.0}

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # contain main's mutation
    monkeypatch.setattr(bench, "_devices", no_devices)
    monkeypatch.setattr(bench, "bench_input", fake_input)
    assert bench.main(["--model", "input"]) == 0
    assert seen["platforms"] == "cpu"
    rec = _last_json(capsys)
    assert rec["metric"] == "input_pipeline_host_images_per_sec"
    assert rec["extra"]["backend"] == "host"
    assert rec["headline"]["value"] == 99.0
    assert "device_numbers_this_round" not in rec["headline"]


def test_bench_cli_parses_before_heavy_import():
    """Argparse runs before any jax import: a bad flag exits 2 instantly
    (no backend init, no hang) and --help exits 0."""
    import pytest

    with pytest.raises(SystemExit) as e:
        bench.main(["--model", "nope"])
    assert e.value.code == 2
    with pytest.raises(SystemExit) as e:
        bench.main(["--help"])
    assert e.value.code == 0


def test_bench_help_never_touches_a_backend():
    """--help in a FRESH interpreter with a bogus JAX platform must succeed:
    if bench.py ever initializes jax before argparse, this fails/hangs (the r1
    'one flaky PJRT init burned the whole round' mode)."""
    out = subprocess.run(
        [sys.executable, "bench.py", "--help"],
        capture_output=True, text=True, timeout=60, cwd=".",
        env={**__import__("os").environ, "JAX_PLATFORMS": "bogus_platform"},
        check=False)
    assert out.returncode == 0
    assert "usage:" in out.stdout


def test_timing_suspect_zeroes_vs_baseline(monkeypatch, capsys):
    """An MFU>100% artifact must not be reported as a real headline ratio."""
    _fake_chip(monkeypatch, bench_resnet={
        "images_per_sec_per_chip": 9e4, "mfu": 10.47, "step_time_ms": 3.0,
        "batch_size": 256, "chips": 1, "timing_suspect": "mfu 10.47 > 1.0"})
    assert bench.main([]) == 0
    rec = _last_json(capsys)
    assert rec["vs_baseline"] == 0.0
    assert any("timing" in e or "mfu" in e for e in rec["extra"]["errors"])


def test_sanity_check_mfu_flags_impossible():
    rec = {"mfu": 10.47}
    bench._sanity_check_mfu(rec)
    assert "timing_suspect" in rec
    rec2 = {"mfu": 0.35}
    bench._sanity_check_mfu(rec2)
    assert "timing_suspect" not in rec2


def test_attention_matmul_flops_convention():
    """Model-flops convention: fwd = 2 matmuls, bwd = 4, causal halves,
    GQA/masking don't enter (both matmuls run at the q-head count)."""
    from distributeddeeplearningspark_tpu.metrics import attention_matmul_flops

    b, h, s, d = 2, 3, 64, 16
    one = 2.0 * b * h * s * s * d
    assert attention_matmul_flops(b, h, s, d, train=False) == 2 * one
    assert attention_matmul_flops(b, h, s, d, train=True) == 6 * one
    assert attention_matmul_flops(b, h, s, d, causal=True, train=True) == 3 * one


def test_llama_model_flops_formula():
    """The analytic MFU formula (metrics.llama_model_flops_per_token):
    closed-form identities that would catch any ×2/×L bookkeeping slip —
    the bug class it exists to route around (XLA cost analysis counts the
    layer-scan body once, not ×L — r5 finding, see
    test_cost_analysis_is_scan_opaque — deflating llama MFU to 12% on the
    r4 device record while the same step's analytic count puts it ~50%)."""
    from distributeddeeplearningspark_tpu.metrics import (
        attention_matmul_flops, llama_model_flops_per_token)
    from distributeddeeplearningspark_tpu.models import LlamaConfig

    cfg = LlamaConfig(vocab_size=2048, hidden_size=256, num_layers=4,
                      num_heads=8, num_kv_heads=4, intermediate_size=512,
                      max_position=256, lora_rank=8, dtype="float32")
    s = 256
    h, i, v = 256, 512, 2048
    kvh = cfg.num_kv_heads * cfg.head_dim
    p = cfg.num_layers * (2 * h * h + 2 * h * kvh + 3 * h * i) + v * h
    lora = sum(cfg.num_layers * 8 * (h + {"wq": h, "wv": kvh}[t])
               for t in ("wq", "wv"))
    attn = cfg.num_layers * attention_matmul_flops(
        1, 8, s, 32, causal=True, train=True) / s
    frozen = llama_model_flops_per_token(cfg, s, frozen_base=True)
    full = llama_model_flops_per_token(cfg, s, frozen_base=False)
    assert frozen == 4 * p + 6 * lora + attn
    assert full == 6 * p + 6 * lora + attn
    # full-autodiff : frozen ratio must be exactly the dW share
    assert (full - frozen) == 2 * p
    # no-LoRA config drops the adapter term and the frozen distinction
    dense_cfg = LlamaConfig(vocab_size=2048, hidden_size=256, num_layers=4,
                            num_heads=8, num_kv_heads=4,
                            intermediate_size=512, max_position=256,
                            dtype="float32")
    assert llama_model_flops_per_token(
        dense_cfg, s, frozen_base=False) == 6 * p + attn
    # MoE: top_k expert FFNs + router replace the dense FFN term (the r4
    # review caught mfu_model silently undercounting --moe-experts runs)
    moe_cfg = LlamaConfig(vocab_size=2048, hidden_size=256, num_layers=4,
                          num_heads=8, num_kv_heads=4, intermediate_size=512,
                          max_position=256, dtype="float32",
                          moe_experts=4, moe_top_k=2)
    p_moe = p + cfg.num_layers * ((2 - 1) * 3 * h * i + h * 4)
    assert llama_model_flops_per_token(
        moe_cfg, s, frozen_base=False) == 6 * p_moe + attn


def _compiled_llama_flops(num_layers: int, *, scan: bool):
    """Compile a tiny frozen-base llama step and return (measured HLO
    flops, analytic model flops) — shared by the cross-check tests."""
    import optax

    from distributeddeeplearningspark_tpu.metrics import (
        compiled_flops_per_step, llama_model_flops_per_token)
    from distributeddeeplearningspark_tpu.models import (
        LlamaConfig, LlamaForCausalLM, llama_rules, lora_trainable)
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
    from distributeddeeplearningspark_tpu.train import losses, step as step_lib

    b, s = 2, 256
    cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                      num_layers=num_layers, num_heads=8, num_kv_heads=4,
                      intermediate_size=512, max_position=s, lora_rank=8,
                      dtype="float32", remat=False, scan_layers=scan)
    model = LlamaForCausalLM(cfg)
    batch = {"input_ids": np.ones((b, s), np.int32),
             "loss_mask": np.ones((b, s), np.float32)}
    mesh = MeshSpec(data=1).build(jax.devices()[:1])
    state, sh = step_lib.init_state(
        model, optax.sgd(1e-3), batch, mesh,
        llama_rules(cfg, fsdp_min_size=1 << 30))
    step = step_lib.jit_train_step(
        step_lib.make_train_step(model.apply, optax.sgd(1e-3),
                                 losses.causal_lm, trainable=lora_trainable),
        mesh, sh)
    measured = compiled_flops_per_step(step.lower(state, batch).compile())
    assert measured is not None
    analytic = llama_model_flops_per_token(cfg, s, frozen_base=True) * b * s
    return measured, analytic


def test_llama_model_flops_vs_cpu_cost_analysis():
    """Cross-check the analytic formula against the UNROLLED compiled
    step, whose HLO cost analysis sees every layer (XLA convention:
    2 flops/MAC, same as the formula). Bounds are tight enough to catch a
    dropped backward at ANY depth (VERDICT r4 weak-#4: the old ±40%
    window on the scanned step passed only because a 2× convention error
    and the scan-body undercount canceled at L=4): measured r5 ratios are
    1.065 (L=2) and 1.105 (L=4) — the excess over 1.0 is elementwise/
    optimizer work the formula excludes — while a dropped backward
    divides the true count by ~2.1 (the measured fwd:frozen-step ratio),
    putting the ratio at ~0.5, far outside [0.95, 1.30] at every depth."""
    for num_layers in (2, 4):
        measured, analytic = _compiled_llama_flops(num_layers, scan=False)
        ratio = measured / analytic
        assert 0.95 < ratio < 1.30, (num_layers, measured, analytic, ratio)


def test_cost_analysis_is_scan_opaque():
    """Pin the mechanism `mfu_hlo_scan_opaque` is named for: XLA cost
    analysis reports the layer-scan body ONCE, not × trip count, so the
    scanned L=4 count comes in BELOW even the unrolled L=2 count (one
    body + head < two layers + head). If a jax upgrade starts counting
    scan trips, this fails and the suspect-number plumbing (bench_llama,
    metrics docstrings, BASELINE r5 log) should be retired."""
    scanned4, _ = _compiled_llama_flops(4, scan=True)
    unrolled2, _ = _compiled_llama_flops(2, scan=False)
    assert scanned4 < unrolled2, (scanned4, unrolled2)


def test_routes_to_flash_matches_router(monkeypatch):
    """The bench's FLOPs adjustment must follow the real attention router:
    off-TPU it reports False (XLA path), so no adjustment is applied."""
    assert bench._routes_to_flash(b=2, s=512, h=12, d=64, masked=True) is False

    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert bench._routes_to_flash(b=2, s=512, h=12, d=64, masked=True) is True
    # sub-block sequence falls back to XLA even on TPU
    assert bench._routes_to_flash(b=2, s=256, h=12, d=64, masked=True) is False


def test_llama_7b_oom_returns_structured_evidence(monkeypatch):
    """VERDICT r2 next-#3: a resource-exhaustion failure of the 7B attempt
    must come back as the budget-bearing evidence record; any other error
    must still raise (a code bug cannot masquerade as memory evidence)."""
    import pytest

    def oom(*a, **k):
        raise RuntimeError("XLA:TPU RESOURCE_EXHAUSTED: Ran out of memory "
                           "in hbm. Used 17.1G of 15.48G")

    monkeypatch.setattr(bench, "_train_setup", oom)
    rec = bench.bench_llama(2, variant="7b")
    assert rec["error"].startswith("RuntimeError")
    assert "memory_report" in rec and "memory_v4_32" in rec
    # the v4-32 record must carry the CONTRACT shape, not the clamped
    # single-chip attempt shape
    assert rec["memory_v4_32"]["mesh"] == {"data": 2, "fsdp": 8}
    assert "fits 32 GiB/chip: True" in " ".join(rec["memory_v4_32"]["notes"])
    # b clamps to 1 always; seq caps at 2048 (r4: relaxed from 1024 once
    # the executed-7B evidence existed at s=1024)
    assert rec["batch_size"] == 1 and rec["seq_len"] == 2048

    def bug(*a, **k):
        raise TypeError("not a memory problem")

    monkeypatch.setattr(bench, "_train_setup", bug)
    with pytest.raises(TypeError):
        bench.bench_llama(2, variant="7b")


def test_bench_kernels_interpret_smoke():
    """--model kernels off-chip: both Pallas kernels parity-check against
    their XLA reference chains in interpret mode (timing skipped — only the
    compiled path's numbers mean anything)."""
    rec = bench.bench_kernels()
    assert rec["mode"] == "interpret"
    assert rec["conv_bn"]["compile"] == "ok", rec["conv_bn"]
    assert rec["conv_bn"]["grad_max_rel_err"] < 0.02
    assert rec["conv_bn"]["fused_ms"] is None
    assert rec["scatter_rows"]["compile"] == "ok", rec["scatter_rows"]
    assert rec["scatter_rows"]["max_abs_err"] == 0.0
    # ulysses CP smoke (VERDICT r4 weak-#7): off-chip the local attention
    # is the einsum fallback vs interpret-mode flash — parity bounds the
    # whole all-to-all + local-attention chain
    assert rec["ulysses_smoke"]["compile"] == "ok", rec["ulysses_smoke"]
    assert rec["ulysses_smoke"]["finite"]
    assert rec["ulysses_smoke"]["max_abs_err_vs_direct_flash"] < 0.05


def test_pallas_smoke_interpret_parity():
    """The four flash regimes against _xla_attention, fwd + both bwd kernels
    (interpret mode here; Mosaic on the chip — chip_smoke's sibling check)."""
    rec = bench.pallas_smoke()
    assert set(rec) == {"causal_d128", "masked_d64_bert", "gqa_causal_d128",
                        "masked_segments_d64"}
    for case in rec.values():
        assert case["kernel"] == "interpret"
        assert max(case["max_err_over_max_ref"].values()) < 0.05


def test_llama_09b_cfg_long_context_flip():
    """s>=16384 must flip the 0.9b bench config to full remat + fused CE —
    the pair that made s=16384 fit a single 16 GiB chip on the r4 window
    (9677 tok/s/chip); below that the measured-fastest 'dots' policy stays."""
    import bench

    short = bench._llama_09b_cfg(seq=2048)
    assert short.remat_policy == "dots" and not short.fused_head_loss
    long = bench._llama_09b_cfg(seq=16384)
    assert long.remat_policy is None and long.fused_head_loss
    # explicit --fused-head-loss still wins at short seq
    assert bench._llama_09b_cfg(seq=2048, fused_head=True).fused_head_loss


def test_bench_llama_decode_record(monkeypatch):
    """--decode mode: the KV-cache generation bench produces its record
    shape off-chip at a tiny geometry (the 0.9b default is monkeypatched —
    128 sequential 0.9b decode steps on CPU would take minutes)."""
    from distributeddeeplearningspark_tpu.models import LlamaConfig

    def tiny_cfg(*, seq=2048, fused_head=False, moe_experts=0, moe_group=0,
                 base_quant=None):
        return LlamaConfig(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, intermediate_size=128, max_position=seq,
            lora_rank=4, dtype="float32", remat=False,
            base_quant=base_quant)

    monkeypatch.setattr(bench, "_llama_09b_cfg", tiny_cfg)
    rec = bench.bench_llama_decode(5, batch_size=2, prompt_len=8,
                                   new_tokens=8)
    assert rec["decode_tokens_per_sec_per_chip"] > 0
    assert rec["ms_per_decode_step"] > 0
    # prefill subtracted: a decode step must be cheaper than the whole
    # prefill+decode call
    assert rec["ms_per_decode_step"] * 7 < rec["prefill_plus_first_token_ms"] * 8
    assert rec["batch_size"] == 2 and rec["new_tokens"] == 8
    assert rec["base_quant"] is None
    # first-record discipline (VERDICT r5 weak-#5): the compile-bearing
    # first device call of each shape is timed apart, discarded from the
    # averages, and recorded; a clean run passes the wall-clock
    # cross-check (decode steps are the cheapest tokens, so the
    # subtraction-derived step must not exceed full_wall/new_tokens +10%)
    fc = rec["first_call_discarded_ms"]
    assert fc["full"] > 0 and fc["prefill"] > 0
    if "timing_suspect" not in rec:
        wall_divide_ms = (rec["end_to_end_tokens_per_sec"] and
                          rec["batch_size"] * 1e3
                          / rec["end_to_end_tokens_per_sec"])
        assert rec["ms_per_decode_step"] <= wall_divide_ms * 1.10
    # int8 composition: same record shape, quantized base leaves
    rec8 = bench.bench_llama_decode(5, batch_size=2, prompt_len=8,
                                    new_tokens=8, base_quant="int8")
    assert rec8["base_quant"] == "int8"
    # no silently-ignored flags with --decode (the house guard pattern)
    import pytest

    with pytest.raises(SystemExit):
        bench.main(["--model", "llama", "--decode", "--seq", "8192"])
    with pytest.raises(SystemExit):
        bench.main(["--model", "llama", "--decode", "--variant", "7b"])
