"""The planted faults of ``benchmark/controls/joyai_llm_flash.py`` at a toy
size on the CPU: one run of the cell ``joyai_llm_flash.fit_s16k`` in float32
under the cell's OWN limits, the harness's comparison repeated with each
fault planted (the cell's other tests, and the toy size, are in
``tests/test_latent_moe_benchmark.py``; a file of its own so that the two
run side by side)."""

import os

import pytest

import _cells as cells
from _cells import ROOT, load
from test_latent_moe_benchmark import NAME, TOY


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
    assert seen["router_bias_abs_max"] > 0
    # lap by lap, the share of the assignments on the experts held (4 of 8)
    shares = [lap["moe_rows_held_share"] for lap in seen["laps"]]
    assert len(shares) >= 2 and all(0.2 < s < 0.9 for s in shares), shares
    assert all(lap["mtp_nll"] > 0 and lap["lm_loss"] > 0
               for lap in seen["laps"])


@pytest.mark.parametrize("fault", [
    "scores_scaled_by_sqrt_128", "rotary_on_position_free",
    "rotary_key_per_head", "q_latent_norm_left_out",
    "kv_latent_norm_left_out", "shared_expert_left_out", "routed_scale_1",
    "mtp_predicts_next_token", "mtp_weight_0", "e4m3_attention_path",
    "e4m3_weights"])
def test_a_planted_fault_is_not_correct(controls, fault):
    mod, seen = controls
    assert fault in mod.ALL
    assert seen[fault]["correct"] is False and seen[fault]["failures"], \
        seen[fault]


@pytest.mark.parametrize("fault, term, least", [
    ("scores_scaled_by_sqrt_128", "attention_energy", 0.01),
    ("routed_scale_1", "experts_energy", 1.5),      # ln 6.25 = 1.83, and the
    ("routed_scale_1", "expert_probe", 1.8),        # later blocks see other inputs
    ("q_latent_norm_left_out", "latents_spread", 1.0),
    ("kv_latent_norm_left_out", "latents_spread", 1.0),
    ("mtp_weight_0", "loss", 0.05)])
def test_a_fault_moves_the_term_that_is_there_for_it(controls, fault, term,
                                                     least):
    """Each term is printed on both sides; the sound program's differ from
    the reference's by rounding, a fault's by the fault."""
    sound = controls[1]["sound"]["terms"][term]
    assert abs(sound[2]) < 1e-4, sound
    assert abs(controls[1][fault]["terms"][term][2]) > least


def test_the_weight_0_fault_leaves_the_main_loss_where_it_was(controls):
    """Weighing the module's term by 0 moves the training loss (the compared
    scalar sees it) and not the main model's term."""
    seen = controls[1]
    main = seen["mtp_weight_0"]["terms"]["lm_loss"]
    assert main[0] == pytest.approx(main[1], rel=1e-5)
    assert seen["mtp_weight_0"]["terms"]["loss"][0] == pytest.approx(
        main[0], rel=1e-5)


def test_a_planted_fault_leaves_the_program_as_it_was():
    from distributeddeeplearningspark_tpu.models import hybrid_decoder, moe

    mod = load(os.path.join(ROOT, "benchmark", "controls", NAME + ".py"))
    planted = mod.faults(None, None, {})
    for name, module, attribute in (
            ("routed_scale_1", moe, "_held_experts"),
            ("shared_expert_left_out", hybrid_decoder, "RoutedExperts"),
            ("scores_scaled_by_sqrt_128", hybrid_decoder,
             "dot_product_attention"),
            ("rotary_on_position_free", hybrid_decoder,
             "dot_product_attention"),
            ("rotary_key_per_head", hybrid_decoder, "dot_product_attention"),
            ("q_latent_norm_left_out", hybrid_decoder, "RMSNorm"),
            ("kv_latent_norm_left_out", hybrid_decoder, "RMSNorm")):
        sound = getattr(module, attribute)
        with planted[name][1]():
            assert getattr(module, attribute) is not sound, name
        assert getattr(module, attribute) is sound, name
