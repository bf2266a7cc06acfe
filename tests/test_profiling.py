"""Profiler integration: trace capture window, annotations, XLA dump flag."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributeddeeplearningspark_tpu import PartitionedDataset, Session, Trainer
from distributeddeeplearningspark_tpu.models import LeNet5
from distributeddeeplearningspark_tpu.telemetry import spans
from distributeddeeplearningspark_tpu.train import losses
from distributeddeeplearningspark_tpu.utils import profiling


def test_trace_context_manager_writes_xplane(tmp_path):
    d = str(tmp_path / "prof")
    with profiling.trace(d):
        with spans.span("dls.fit/emit"):
            jax.block_until_ready(jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))))
    assert profiling.trace_files(d), "no .xplane.pb produced by trace capture"


def test_step_profiler_window(tmp_path):
    d = str(tmp_path / "prof")
    prof = profiling.StepProfiler(profiling.ProfileSpec(d, start_step=2, num_steps=2))
    for step in range(6):
        prof.observe(step)
        with profiling.step_annotation(step):
            jax.block_until_ready(jnp.ones((8,)) * step)
    prof.stop()
    assert profiling.trace_files(d)
    # idempotent: stop again is a no-op, disabled profiler observes freely
    prof.stop()
    profiling.StepProfiler(None).observe(0)


def test_fit_with_profile_and_flops(tmp_path):
    rng = np.random.default_rng(0)
    examples = [
        {"image": rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
         "label": np.int32(i % 10)}
        for i in range(64)
    ]
    spark = Session.builder.master("local[2]").getOrCreate()
    ds = PartitionedDataset.parallelize(examples, 2).repeat()
    trainer = Trainer(spark, LeNet5(), losses.softmax_xent, optax.sgd(0.01))
    prof_dir = str(tmp_path / "prof")
    state, summary = trainer.fit(
        ds, batch_size=16, steps=8, log_every=4,
        profile=profiling.ProfileSpec(prof_dir, start_step=4, num_steps=2),
        measure_flops=True,
    )
    assert profiling.trace_files(prof_dir)
    # CPU backend supports cost analysis, so MFU pieces must be present
    assert "step_time_ms" in summary


def test_enable_xla_dump_appends_flag(tmp_path, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    profiling.enable_xla_dump(str(tmp_path / "dump"))
    flags = os.environ["XLA_FLAGS"]
    assert "--xla_dump_to=" in flags and "device_count=8" in flags


def test_step_profiler_offset_is_resume_relative(tmp_path):
    d = str(tmp_path / "prof")
    prof = profiling.StepProfiler(
        profiling.ProfileSpec(d, start_step=2, num_steps=1), start_offset=1000
    )
    for step in range(1000, 1002):  # before window: 1000+2
        prof.observe(step)
        assert not prof._active
    prof.observe(1002)
    assert prof._active
    prof.stop()
    assert profiling.trace_files(d)


def test_fit_crash_mid_window_still_flushes_trace(tmp_path):
    rng = np.random.default_rng(0)
    examples = [
        {"image": rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
         "label": np.int32(i % 10)}
        for i in range(64)
    ]
    spark = Session.builder.master("local[2]").getOrCreate()
    ds = PartitionedDataset.parallelize(examples, 2).repeat()
    trainer = Trainer(spark, LeNet5(), losses.softmax_xent, optax.sgd(0.01))

    def boom(step, _):
        if step >= 3:
            raise RuntimeError("injected")

    prof_dir = str(tmp_path / "prof")
    with pytest.raises(RuntimeError, match="injected"):
        trainer.fit(ds, batch_size=16, steps=10, log_every=100,
                    profile=profiling.ProfileSpec(prof_dir, start_step=1, num_steps=8),
                    callbacks=[boom])
    assert profiling.trace_files(prof_dir), "crashed run must still flush its trace"
    # profiler fully stopped: a later fit with profiling must not collide
    state, _ = trainer.fit(ds, batch_size=16, steps=6, log_every=100,
                           profile=profiling.ProfileSpec(str(tmp_path / "p2"),
                                                         start_step=1, num_steps=2))
    assert profiling.trace_files(str(tmp_path / "p2"))


def test_op_breakdown_parses_cpu_trace(tmp_path):
    """op_breakdown must read a real capture without TensorBoard's converter:
    aggregate per-op times from the busiest line and report a sane budget
    (CPU traces carry host/TFRT lines rather than a TPU 'XLA Ops' line —
    the fallback path)."""
    d = str(tmp_path / "prof")
    with profiling.trace(d):
        x = jnp.ones((128, 128))
        for _ in range(3):
            x = jnp.dot(x, x)
        jax.block_until_ready(x)
    rec = profiling.op_breakdown(d, top=10)
    assert "error" not in rec, rec
    assert rec["event_count"] > 0
    assert rec["ops"] and len(rec["ops"]) <= 10
    total_pct = sum(o["pct"] for o in rec["ops"])
    assert 0 < total_pct <= 100.5, rec["ops"]
    assert rec["ops"] == sorted(rec["ops"], key=lambda o: -o["ms"])


def test_op_breakdown_missing_dir(tmp_path):
    rec = profiling.op_breakdown(str(tmp_path / "nothing_here"))
    assert "error" in rec


def test_profile_cli_prints_budget(tmp_path, capsys):
    d = str(tmp_path / "prof")
    with profiling.trace(d):
        jax.block_until_ready(jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))))
    assert profiling.profile_cli([d, "--top", "3"]) == 0
    out = capsys.readouterr().out
    assert "total" in out and "%" in out
    assert profiling.profile_cli([str(tmp_path / "missing"), "--json"]) == 1
