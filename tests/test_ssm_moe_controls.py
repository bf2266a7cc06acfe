"""The planted faults of ``benchmark/controls/nemotron3_nano_30b_a3b.py`` at a
toy size on the CPU: one run of the cell ``nemotron3_nano_30b_a3b.fit_seg16k``
in float32 under the cell's OWN limits, the harness's comparison repeated
with each fault planted (the cell's other tests, and the toy size, are in
``tests/test_ssm_moe_benchmark.py``; a file of its own so that the two run
side by side)."""

import os
import time

import pytest

from test_ssm_moe_benchmark import (  # noqa: F401 - the toy size
    NAME, ROOT, _copy_of_the_benchmark, _load, _toy)

#: the toy run is counted in steps, not seconds: the clock its window reads
#: moves STEP_S a step from the first, so the 5 s of warm-up and the 1-s
#: window are 242 steps on any machine. A fault's term follows how far the
#: toy model has trained: ``head_reads_group_h_mod_8``'s ``scan_energy``
#: reads 0.041, 0.037, 0.027 and 0.0059 at 202, 242, 302 and 482 steps
STEP_S = 0.025


@pytest.fixture(scope="module")
def controls(tmp_path_factory):
    """``benchmark/controls/nemotron3_nano_30b_a3b.py`` at the toy size on
    ``local[1]``, in float32 but under the cell's OWN limits: one run of the
    cell, the harness's comparison repeated with each fault planted."""
    from benchmark.harness import runner, seedcache, window

    class StepClock(window.LapWindow):
        def __init__(self, **kw):
            super().__init__(clock=self.steps_s, **kw)
            self.first = None

        def steps_s(self):
            if self.first is None:
                self.first = (time.perf_counter(), self.last_step)
            t, step = self.first
            return t + (self.last_step - step) * STEP_S

    root = _copy_of_the_benchmark(
        tmp_path_factory.mktemp("controls") / "checkout")
    _toy(root, keep_check=True)
    mod = _load(str(root / "benchmark" / "controls" / f"{NAME}.py"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(runner, "LapWindow", StepClock)
        mp.setattr(seedcache, "ROOT", str(root / "benchmark" / ".cache"))
        mp.setenv("DLS_TELEMETRY_DIR", str(root / "telemetry"))  # restored
        return mod, mod.run(2 ** 31 + 11, 1.0, mod.ALL, master="local[1]",
                            root=str(root))


def test_the_sound_program_is_correct_under_the_cells_own_limits(controls):
    _, seen = controls
    assert seen["result"]["correct"], seen["sound"]
    assert seen["sound"]["failures"] == [] == seen["harness"]["failures"]
    # the comparison made again is the harness's own, to the digit
    for key in ("loss_program", "loss_reference", "grad_rel_err"):
        assert seen["sound"][key] == pytest.approx(seen["harness"][key],
                                                   rel=1e-6), key
    assert seen["router_bias_abs_max"] > 0
    # lap by lap, the share of the assignments on the experts held (4 of 8)
    shares = [lap["moe_rows_held_share"] for lap in seen["laps"]]
    assert len(shares) >= 2 and all(0.2 < s < 0.9 for s in shares), shares
    assert all(lap["ssm_state_abs_max"] > 0 for lap in seen["laps"])


FAULTS = ["state_not_reset", "conv_crosses_documents", "dt_without_bias",
          "d_skip_left_out", "norm_over_one_group", "gate_after_norm",
          "head_reads_group_h_mod_8", "relu_not_squared", "routed_scale_1",
          "shared_expert_left_out", "rotary_in_attention", "e4m3_mamba_path",
          "e4m3_weights"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(controls, fault):
    mod, seen = controls
    assert fault in mod.ALL and set(FAULTS) == set(mod.ALL)
    assert seen[fault]["correct"] is False and seen[fault]["failures"], \
        seen[fault]


@pytest.mark.parametrize("fault, term, least", [
    ("state_not_reset", "boundary_energy", 0.03),
    ("conv_crosses_documents", "boundary_energy", 0.05),
    ("dt_without_bias", "scan_energy", 0.5),
    ("d_skip_left_out", "scan_energy", 0.5),
    ("head_reads_group_h_mod_8", "scan_energy", 0.01),
    # behind the gated norm every position has the same size whatever the
    # scan gave it: ssm_energy reads the gate's and the norm's own order
    ("gate_after_norm", "ssm_energy", 0.5),
    ("rotary_in_attention", "attention_energy", 0.02),
    ("relu_not_squared", "expert_probe", 0.5),
    ("routed_scale_1", "experts_energy", 1.8),      # ln 6.25 = 1.83
    ("routed_scale_1", "expert_probe", 1.8)])
def test_a_fault_moves_the_term_that_is_there_for_it(controls, fault, term,
                                                     least):
    """Each term is printed on both sides; the sound program's differ from
    the reference's by rounding, a fault's by the fault."""
    sound = controls[1]["sound"]["terms"][term]
    assert abs(sound[2]) < 1e-4, sound
    assert abs(controls[1][fault]["terms"][term][2]) > least


def test_a_planted_fault_leaves_the_program_as_it_was():
    from distributeddeeplearningspark_tpu.models import hybrid_decoder, moe

    mod = _load(os.path.join(ROOT, "benchmark", "controls", NAME + ".py"))
    planted = mod.faults(None, None, {})
    for name, module, attribute in (
            ("state_not_reset", hybrid_decoder, "ssd_scan"),
            ("head_reads_group_h_mod_8", hybrid_decoder, "ssd_scan"),
            ("conv_crosses_documents", hybrid_decoder, "silu_short_conv"),
            ("norm_over_one_group", hybrid_decoder, "gated_group_norm"),
            ("gate_after_norm", hybrid_decoder, "gated_group_norm"),
            ("relu_not_squared", moe, "relu2"),
            ("routed_scale_1", moe, "_held_experts"),
            ("shared_expert_left_out", hybrid_decoder, "RoutedExperts"),
            ("rotary_in_attention", hybrid_decoder,
             "dot_product_attention")):
        sound = getattr(module, attribute)
        with planted[name][1]():
            assert getattr(module, attribute) is not sound, name
        assert getattr(module, attribute) is sound, name


def test_the_planted_convolution_reaches_the_kernels_through_auto(
        monkeypatch):
    """``conv_crosses_documents`` swaps ``hybrid_decoder.silu_short_conv`` by
    name and calls the sound one with the default ``impl="auto"``: answered
    "on a TPU", that is the kernels, run here interpreted, with the segment
    ids dropped."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from _jaxpr import eqns
    from distributeddeeplearningspark_tpu.models import hybrid_decoder
    from distributeddeeplearningspark_tpu.ops import short_conv

    mod = _load(os.path.join(ROOT, "benchmark", "controls", NAME + ".py"))
    monkeypatch.setattr(short_conv, "on_tpu", lambda: True)
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.normal(size=(1, 256, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128, 4)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    seg = jnp.asarray(np.repeat([0, 1], 128)[None], jnp.int32)
    with mod.faults(None, None, {})["conv_crosses_documents"][1]():
        jaxpr = jax.make_jaxpr(hybrid_decoder.silu_short_conv)(v, w, bias,
                                                              seg)
        got = hybrid_decoder.silu_short_conv(v, w, bias, seg)
    assert [e.params["name"] for e in eqns(jaxpr.jaxpr, "pallas_call")] == [
        "mamba_conv_fwd"]
    np.testing.assert_allclose(
        got, short_conv.silu_short_conv_xla(v, w, bias, None), atol=1e-5)
    sound = short_conv.silu_short_conv_xla(v, w, bias, seg)
    assert float(jnp.abs(got - sound).max()) > 0.1


def test_the_permuted_scan_is_the_scan_with_groups_read_h_mod_g():
    """The control ``head_reads_group_h_mod_8`` plants what its name says:
    against the sequential recurrence with the other mapping written out."""
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearningspark_tpu.models import hybrid_decoder
    from distributeddeeplearningspark_tpu.ops import ssd

    mod = _load(os.path.join(ROOT, "benchmark", "controls", NAME + ".py"))
    rng = np.random.default_rng(0)
    b, s, h, p, g, n = 1, 32, 6, 4, 3, 8
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, bm, cm = arr(b, s, h, p), arr(b, s, g, n), arr(b, s, g, n)
    dt, a, d = jnp.exp(arr(b, s, h) - 2), -jnp.exp(arr(h)), arr(h)
    with mod.faults(None, None, {})["head_reads_group_h_mod_8"][1]():
        got, _ = hybrid_decoder.ssd_scan(x, dt, a, bm, cm, d, None, chunk=8)
    by_head = lambda t: t[:, :, jnp.arange(h) % g]      # a group a head
    want = ssd.ssd_scan_sequential(x, dt, a, by_head(bm), by_head(cm), d)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    sound = ssd.ssd_scan_sequential(x, dt, a, bm, cm, d)
    assert float(jnp.max(jnp.abs(want - sound))) > 0.1
