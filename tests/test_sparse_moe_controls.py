"""The planted faults of ``benchmark/controls/keye_vl2_30b_a3b.py`` at a toy
size on the CPU: one run of the cell ``keye_vl2_30b_a3b.fit_s8k`` in float32
under the cell's OWN limits, the harness's comparison repeated with each
fault planted (the cell's other tests, and the toy size, are in
``tests/test_sparse_moe_benchmark.py``; a file of its own so that the two
run side by side)."""

import os

import pytest

import _cells as cells
from _cells import ROOT, load
from test_sparse_moe_benchmark import TOY


@pytest.fixture(scope="module")
def controls(tmp_path_factory):
    return cells.run_controls(tmp_path_factory, "keye_vl2_30b_a3b", TOY,
                              flips=True)[1]


def test_the_sound_program_is_correct_under_the_cells_own_limits(controls):
    assert controls["result"]["correct"], controls["sound"]
    assert controls["sound"]["failures"] == []
    # float32 on both sides: the selections agree but for ties in rounding
    for layer in controls["selection_flips"]:
        assert layer["share_of_selected"] < 0.01, layer


@pytest.mark.parametrize("fault", ["drop_assignments", "e4m3_weights",
                                   "dense_prefix", "wq_gradient_lost"])
def test_a_planted_fault_is_not_correct(controls, fault):
    """A dropped assignment, an 8-bit product and attention over
    the dense prefix each fail a limit of the comparison that decides
    ``correct`` (and a gradient that never reaches ``wq`` does)."""
    seen = controls[fault]
    assert seen["correct"] is False and seen["failures"], seen
    if fault == "drop_assignments":
        # it is the probe that sees it: the missing output is missing energy
        assert seen["loss_abs_err"] > 10 * 0.002, seen
    if fault == "wq_gradient_lost":
        assert seen["loss_abs_err"] < 1e-5 and seen["grad_rel_err"] > 0.07


def test_a_planted_fault_leaves_the_program_as_it_was():
    from distributeddeeplearningspark_tpu.models import moe

    mod = load(os.path.join(ROOT, "benchmark", "controls",
                            "keye_vl2_30b_a3b.py"))
    sound = moe._zero_past
    _, planted = mod.faults(None, None, {"sa_config": {"topk": 1}}, 8)[
        "drop_assignments"]
    with planted():
        assert moe._zero_past is not sound
    assert moe._zero_past is sound
