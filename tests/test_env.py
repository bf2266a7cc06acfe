"""utils/env.py: the one platform predicate and the chip smoke's refusal."""

import os
import subprocess
import sys

import jax
import pytest

from distributeddeeplearningspark_tpu.utils import env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_platform_predicate(monkeypatch):
    assert env.on_tpu() is False  # the suite runs on the CPU backend
    assert env.pallas_interpret() is True
    assert env.pallas_interpret(True) is True
    assert env.pallas_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert env.on_tpu() is True
    assert env.pallas_interpret() is False
    assert env.pallas_interpret(False) is False
    # a kernel that would run interpreted on a TPU platform is an error
    with pytest.raises(ValueError, match="interpret"):
        env.pallas_interpret(True)


def test_router_follows_the_predicate(monkeypatch):
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.ops import attention

    q = jnp.zeros((2, 512, 12, 64), jnp.bfloat16)
    assert attention._pick_impl(q, q, None, None) == "xla"
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    assert attention._pick_impl(q, q, None, None) == "flash"


def _run(code_or_script: list[str], **env_over) -> subprocess.CompletedProcess:
    child = {**os.environ, "PYTHONPATH": REPO, **env_over}
    return subprocess.run([sys.executable, *code_or_script], cwd=REPO,
                          capture_output=True, text=True, timeout=120,
                          env=child)


def test_chip_smoke_refuses_cpu_without_a_backend():
    """JAX_PLATFORMS=cpu: non-zero exit, no result line, one line saying
    why — and jax is never even imported, so no backend is initialised."""
    out = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    (line,) = out.stderr.strip().splitlines()
    assert line.startswith("chip_smoke: FAIL: JAX_PLATFORMS=cpu")
    probe = _run(["-c", (
        "import sys, chip_smoke\n"
        "assert 'jax' not in sys.modules, 'importing chip_smoke imported jax'\n"
        "try:\n"
        "    chip_smoke.require_tpu()\n"
        "except chip_smoke.SmokeFailure:\n"
        "    assert 'jax' not in sys.modules, 'the refusal touched jax'\n"
        "    print('refused')\n")], JAX_PLATFORMS="cpu")
    assert probe.returncode == 0, probe.stderr
    assert probe.stdout.strip() == "refused"


def test_ensure_cpu_devices_only_acts_on_a_bare_cpu_rehearsal(monkeypatch):
    """local[N] / dryrun_multichip(N) get their N host devices when the
    process is held to the CPU and XLA_FLAGS names no count — and nothing is
    touched on an accelerator platform or when the flag already decides."""
    asked = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: asked.append((key, value)))
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    env.ensure_cpu_devices(4)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    env.ensure_cpu_devices(None)
    env.ensure_cpu_devices(1)
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    env.ensure_cpu_devices(4)
    assert asked == []
    monkeypatch.setenv("XLA_FLAGS", "")
    env.ensure_cpu_devices(4)
    assert asked == [("jax_num_cpu_devices", 4)]

    def live_backend(key, value):
        raise RuntimeError("config should be updated before backends are "
                           "initialized")

    monkeypatch.setattr(jax.config, "update", live_backend)
    env.ensure_cpu_devices(4)  # the caller's own device check reports


def test_the_package_and_a_session_load_no_model_and_no_kernel():
    """``import distributeddeeplearningspark_tpu``, ``Session`` and
    ``Trainer`` load what they loaded before the latent-attention decoder
    was added (the list is PR 31's, module for module): a model's layers and
    kernels load when a model asks for them, so no cell's ``setup_s`` pays
    for a configuration it does not run."""
    probe = _run(["-c", (
        "import sys\n"
        "import distributeddeeplearningspark_tpu\n"
        "from distributeddeeplearningspark_tpu import Session, Trainer\n"
        "Session.builder.master('local[1]').getOrCreate()\n"
        "print(' '.join(sorted(m.split('.', 1)[1] for m in sys.modules\n"
        "    if m.startswith('distributeddeeplearningspark_tpu.'))))\n")],
        JAX_PLATFORMS="cpu")
    assert probe.returncode == 0, probe.stderr
    assert probe.stdout.strip().splitlines()[-1].split() == [
        "cli", "data", "data.dataframe", "data.feed", "data.prefetch",
        "data.records", "faults", "metrics", "parallel",
        "parallel.collectives", "parallel.mesh", "parallel.plan",
        "parallel.reshard", "parallel.sharding", "rdd", "session",
        "telemetry", "telemetry.anatomy", "telemetry.spans", "train",
        "train.losses", "train.optim", "train.state", "train.step",
        "train.trainer", "utils", "utils.env", "utils.profiling",
        "utils.sanitize"]
