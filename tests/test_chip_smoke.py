"""chip_smoke.py's own checks, driven at a tiny size on the CPU mesh.

This is not a CPU mode of the smoke (it has none: tests/test_env.py pins the
refusal); it keeps the checks from rotting between chip runs. On the CPU the
run must fail for exactly the reasons that need a chip and for no other."""

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from distributeddeeplearningspark_tpu import Session
from distributeddeeplearningspark_tpu.data.feed import stack_examples
from distributeddeeplearningspark_tpu.models import bert_tiny


def test_compile_watch_sees_backend_compiles():
    watch = chip_smoke.CompileWatch()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,), jnp.float32))
    assert watch.compile_times and watch.stage_s["backend_compile"] > 0
    assert {"jaxpr_trace", "jaxpr_to_mlir_module"} <= set(watch.stage_s)
    # the suite runs with the persistent cache off: neither hit nor miss
    assert (watch.cache_hits, watch.cache_misses) == (0, 0)


def test_checks_fail_on_cpu_only_for_want_of_a_chip(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "SEQ_LEN", 128)
    monkeypatch.setattr(chip_smoke, "MAX_PREDICTIONS", 23)
    monkeypatch.setenv("DLS_TELEMETRY_DIR", str(tmp_path))
    watch = chip_smoke.CompileWatch()
    spark = Session.builder.master("local[*]").getOrCreate()  # every device
    batch_size = 2 * spark.num_devices
    ds, tok = chip_smoke.build_dataset(spark)
    make_model = lambda **kw: bert_tiny(vocab_size=1024, **kw)  # noqa: E731
    assert tok.vocab_size <= 1024
    run = chip_smoke.train(spark, make_model(), ds, batch_size=batch_size,
                           watch=watch)
    failures, facts = chip_smoke.check_run(run, batch_size=batch_size)
    ref_failures, ref_facts = chip_smoke.check_reference(
        make_model, run["trainer"].state.params,
        stack_examples(ds.take(2 * spark.num_devices)))

    assert sorted(f.split(":")[0].split(" holds")[0] for f in failures) == [
        *(f"TFRT_CPU_{i}" for i in range(8)),  # no allocator stats on CPU
        "no tpu_custom_call in the compiled train step"]
    assert len(ref_failures) == 1 and "wrong programs" in ref_failures[0]
    assert facts["laps"] == 4 and len(facts["losses"]) == 4
    assert facts["train_step"]["compiles"] == 1 and facts["train_step"]["aot"]
    assert facts["train_step"]["collectives"]["all-reduce"] > 0
    assert facts["compile_s"] > 0 and facts["step_ms"] > 0
    assert len(facts["lap_step_ms"]) == 3
    assert run["late_compiles"] == 0
    assert ref_facts["shape"] == [16, 23, 1024]
    assert (tmp_path / "train_step.hlo.txt").stat().st_size > 0

    # and the checks do catch what they are for: a second compile, a lost lap
    step = run["trainer"]._train_step
    step.records.append({**step.records[0], "recompile": True})
    run["laps"][1]["loss"] = float("nan")
    run["late_compiles"] = 1
    failures, _ = chip_smoke.check_run(run, batch_size=batch_size)
    assert any("compile once" in f for f in failures)
    assert any("is nan" in f for f in failures)
    assert any("after the first lap" in f for f in failures)


def test_verdict_line_has_exactly_the_contract_keys():
    """The driver parses the LAST stdout line and refuses any other key."""
    line = chip_smoke.verdict(jax.devices())
    assert line == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": jax.device_count()}}
    assert isinstance(line["device"]["kind"], str)
    assert type(line["device"]["count"]) is int
