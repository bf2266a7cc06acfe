"""The planted faults of ``benchmark/controls/laguna_xs2.py`` at a toy size
on the CPU: one run of the cell ``laguna_xs2.fit_swa16k`` in float32 under
the cell's OWN limits, the harness's comparison repeated with each fault
planted (the cell's other tests, and the toy size, are in
``tests/test_window_moe_benchmark.py``; a file of its own so that the two
run side by side)."""

import os

import pytest

import _cells as cells
from _cells import ROOT, load
from test_window_moe_benchmark import NAME, TOY


@pytest.fixture(scope="module")
def controls(tmp_path_factory):
    return cells.run_controls(tmp_path_factory, NAME, TOY)


def test_the_sound_program_is_correct_under_the_cells_own_limits(controls):
    _, seen = controls
    assert seen["result"]["correct"], seen["sound"]
    assert seen["sound"]["failures"] == [] == seen["harness"]["failures"]
    # the comparison made again is the harness's own, to the digit
    for key in ("loss_program", "loss_reference", "grad_rel_err"):
        assert seen["sound"][key] == pytest.approx(seen["harness"][key],
                                                   rel=1e-6), key
    # lap by lap, the share of the assignments on the experts held (4 of 8)
    # and the Switch term, which the loss carries
    shares = [lap["moe_rows_held_share"] for lap in seen["laps"]]
    assert len(shares) >= 2 and all(0.2 < s < 0.9 for s in shares), shares
    for lap in seen["laps"]:
        assert lap["loss"] == pytest.approx(lap["lm_loss"] + lap["moe_aux"])


FAULTS = ["window_513", "window_511", "no_window_on_sliding",
          "window_on_full", "plain_rotary_on_full", "no_attention_factor",
          "rotary_whole_head_on_full", "theta_5e5_on_sliding", "no_gate",
          "band_crosses_documents", "positions_not_restarting",
          "no_topk_renorm", "routed_scale_1", "shared_expert_left_out",
          "e4m3_weights"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(controls, fault):
    mod, seen = controls
    assert fault in mod.ALL and set(FAULTS) == set(mod.ALL)
    assert seen[fault]["correct"] is False and seen[fault]["failures"], \
        seen[fault]


@pytest.mark.parametrize("fault, term, least", [
    ("window_513", "window_edge_probe", 0.1),
    ("window_511", "window_edge_probe", 0.1),
    ("no_window_on_sliding", "window_edge_probe", 0.1),
    ("plain_rotary_on_full", "rotary_probe", 0.01),
    ("no_attention_factor", "rotary_probe", 0.1),
    ("rotary_whole_head_on_full", "rotary_probe", 0.01),
    ("theta_5e5_on_sliding", "rotary_probe", 0.01),
    ("positions_not_restarting", "rotary_probe", 0.01),
    ("no_gate", "window_attention_energy", 0.5),
    ("no_gate", "full_attention_energy", 0.5),
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

    mod = load(os.path.join(ROOT, "benchmark", "controls", NAME + ".py"))
    planted = mod.faults(None, None, {"sliding_window": 16, "rope_parameters": {
        "full_attention": {"rope_theta": 5e5}}})
    for name, module, attribute in (
            ("window_513", hybrid_decoder, "dot_product_attention"),
            ("band_crosses_documents", hybrid_decoder,
             "dot_product_attention"),
            ("plain_rotary_on_full", hybrid_decoder, "rotary_of"),
            ("theta_5e5_on_sliding", hybrid_decoder, "rotary_of"),
            ("no_gate", hybrid_decoder, "head_gate"),
            ("positions_not_restarting", hybrid_decoder,
             "document_positions"),
            ("no_topk_renorm", moe, "_held_experts"),
            ("routed_scale_1", moe, "_held_experts"),
            ("shared_expert_left_out", hybrid_decoder, "RoutedExperts")):
        sound = getattr(module, attribute)
        with planted[name][1]():
            assert getattr(module, attribute) is not sound, name
        assert getattr(module, attribute) is sound, name
