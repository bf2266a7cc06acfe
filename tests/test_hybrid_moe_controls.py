"""The planted faults of ``benchmark/controls/lfm2_24b_a2b.py`` at a toy size
on the CPU: one run of the cell ``lfm2_24b_a2b.fit_seg32k`` in float32 under
the cell's OWN limits, the harness's comparison repeated with each fault
planted (the cell's other tests, and the toy size, are in
``tests/test_hybrid_moe_benchmark.py``; a file of its own so that the two
run side by side)."""

import os

import pytest

import _cells as cells
from _cells import ROOT, load
from test_hybrid_moe_benchmark import TOY


@pytest.fixture(scope="module")
def controls(tmp_path_factory):
    return cells.run_controls(tmp_path_factory, "lfm2_24b_a2b", TOY)


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
    assert len(shares) >= 2 and all(0.3 < s < 0.7 for s in shares), shares


@pytest.mark.parametrize("fault", [
    "taps_cross_documents", "attention_crosses_documents",
    "selection_without_bias", "drop_assignments", "taps_gradient_lost",
    "e4m3_weights"])
def test_a_planted_fault_is_not_correct(controls, fault):
    seen = controls[1][fault]
    assert seen["correct"] is False and seen["failures"], seen
    if fault == "taps_gradient_lost":
        assert seen["loss_abs_err"] < 1e-5


@pytest.mark.parametrize("fault, term", [
    ("taps_cross_documents", "boundary_energy"),
    ("taps_cross_documents", "boundary_probe"),
    ("selection_without_bias", "experts_energy"),
    ("selection_without_bias", "expert_probe"),
    ("drop_assignments", "experts_energy"),
    ("drop_assignments", "expert_probe")])
def test_a_fault_moves_the_term_of_the_timed_forward_and_the_probe(
        controls, fault, term):
    """Each term is printed on both sides; the sound program's differ from
    the reference's by rounding, a fault's by the fault."""
    sound = controls[1]["sound"]["terms"][term]
    assert abs(sound[2]) < 1e-4, sound
    assert abs(controls[1][fault]["terms"][term][2]) > 0.01


def test_positions_not_restarted_is_no_fault_and_the_comparison_says_so(
        controls):
    mod, seen = controls
    assert "positions_not_restarted" in mod.ALL
    assert seen["positions_not_restarted"]["correct"] is True


def test_a_planted_fault_leaves_the_program_as_it_was():
    from distributeddeeplearningspark_tpu.models import moe

    mod = load(os.path.join(ROOT, "benchmark", "controls",
                            "lfm2_24b_a2b.py"))
    from distributeddeeplearningspark_tpu.models import hybrid_decoder

    planted = mod.faults(None, None, {})
    for name, module, attribute in (
            ("drop_assignments", moe, "_zero_past"),
            ("selection_without_bias", moe, "_held_experts"),
            ("taps_cross_documents", hybrid_decoder, "gated_short_conv"),
            ("attention_crosses_documents", hybrid_decoder,
             "dot_product_attention"),
            ("positions_not_restarted", hybrid_decoder,
             "document_positions")):
        sound = getattr(module, attribute)
        with planted[name][1]():
            assert getattr(module, attribute) is not sound, name
        assert getattr(module, attribute) is sound, name
