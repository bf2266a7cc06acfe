"""Test harness: 8 fake CPU devices (SURVEY.md §4 "Multi-device sim").

Must run before any jax import: holds the suite to the CPU backend (it must
never take the chip a concurrent process owns) and splits the host into 8
virtual devices so real Mesh/pjit/GSPMD code paths — including collectives —
execute in unit tests exactly as they would on an 8-chip slice.
"""

import contextlib
import math
import os
import signal
import time

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

# The suite checks where the persistent compilation cache is PLACED
# (tests/test_session.py) but runs with the cache itself off: an executable
# loaded from a cache another test wrote would make compile counts, cost
# analyses and timings depend on test order.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

from distributeddeeplearningspark_tpu.session import Session  # noqa: E402

# ---------------------------------------------------------------------------
# Two-tier suite (VERDICT r2 next-#7): the default run (`pytest tests/ -q`,
# what the driver executes) deselects tests marked `slow` via pytest.ini's
# addopts and finishes in minutes on one core; the full suite is
# `pytest tests/ -q -m "slow or not slow"`, slow-only is `-m slow`.
# Slow = multi-second jit-compile integration tests, multi-process gangs,
# SIGKILL drills, subprocess benches — marked centrally here (measured list,
# --durations=50 2026-07-30) so test files stay clean and the tier boundary
# lives in one place.
# ---------------------------------------------------------------------------

_SLOW_PATTERNS = (
    "test_supervisor.py",          # multi-process gangs + SIGKILL drills
    # multi-second subprocess drill (abandons a recovering exchange and
    # asserts interpreter-exit reaps respawned children + epoch arenas)
    "test_exchange_recovery.py::test_interpreter_exit_mid_recovery",
    # chaos drills that compile whole-model steps; the pure-python drills
    # (restore-fallback, fault parsing) stay in the fast tier
    "test_chaos.py::test_rollback_without_checkpointer",
    "test_chaos.py::test_on_nonfinite_validation",
    # chaos drills through real supervised gangs and whole fits
    "test_chaos.py::test_kill_mid_finalize_recovers_from_verified_step",
    "test_chaos.py::test_hang_is_killed_classified_and_relaunched",
    "test_chaos.py::test_crash_drill_dlstatus_reports_attempts_and_goodput",
    "test_chaos.py::test_die_host_shrinks_gang_and_training_continues",
    "test_chaos.py::test_sigterm_drains_and_continues_from_current_step",
    "test_chaos.py::test_die_host_real_gang_reshards_onto_survivor",
    "test_chaos.py::test_nan_spike_skip_policy_finishes_finite",
    "test_chaos.py::test_nan_every_step_exhausts_skip_budget",
    "test_chaos.py::test_nan_spike_rollback_policy",
    "test_chaos.py::test_rollback_walks_past_nan_checkpoints",
    "test_serve_fleet.py::test_fleet_processes_end_to_end",  # real processes
    "test_telemetry.py::test_fit_emits_telemetry_and_dlstatus_reads_it",
    "test_profiling.py::test_fit", # Trainer runs writing real trace files
    "test_profiling.py::test_profile_cli",
    "test_profiling.py::test_op_breakdown",
    "test_llama_gen.py",           # KV-cache decode rollouts (big compiles)
    "test_metrics_flops.py::test_llama_model_flops_vs_cpu_cost_analysis",  # compiles full tiny train steps (unrolled, 2 depths)
    "test_metrics_flops.py::test_cost_analysis_is_scan_opaque",  # 2 more tiny compiles
    "test_checkpoint.py::test_trainer_resume",
    "test_checkpoint.py::test_roundtrip",
    "test_pipeline.py::test_pp_composes_with_tp_and_dp",
    "test_pipeline.py::test_pp_llama_loss_equals_non_pp",
    "test_pipeline.py::test_trainer_pp_fit",
    "test_ring_attention.py::test_llama_context_parallel_train_step",
    "test_ring_attention.py::TestFlashHops",
    "test_ring_attention.py::TestKeyPaddingMask::test_masked_and_causal",
    "test_ring_attention.py::test_ring_gqa_matches_xla_repeat",
    "test_llama.py::test_trainable_filter_grads",
    "test_llama.py::test_fused_head_loss",
    "test_llama.py::test_remat_policy_dots",
    "test_llama.py::test_fsdp_tp_sharded_train_step",
    "test_llama.py::test_int8_base_fsdp_tp_sharded_train_step",
    "test_llama.py::TestInt8Base::test_quality_bound_at_bench_geometry",  # two 0.9b fwds, ~2.5 min
    "test_llama.py::TestLoRA::test_masked_optimizer_freezes_base",
    "test_resnet.py::test_resnet_learns_on_fake_data",
    "test_resnet.py::test_batch_stats_update_in_train_step",
    "test_resnet_io.py::test_imported_resnet_matches_torch_logits",
    "test_resnet_io.py::test_trainer_load_pretrained",
    "test_sparse_embed.py::TestSparseTrainStep",
    "test_sparse_embed.py::test_unconsumed_override",
    "test_sparse_embed.py::test_trainer_wires_sparse_embed",
    "test_train_mnist.py::test_spmd_step_equals_driver_round_loop",
    "test_train_mnist.py::test_same_result_1_vs_8_devices",
    "test_train_mnist.py::test_mnist_end_to_end_accuracy",
    "test_train_mnist.py::test_predict_streams",
    "test_bert.py::test_bert_mlm_learns",
    "test_bert.py::test_hf_bert_import_logits_parity",
    "test_bert.py::test_gathered_mlm_head_matches_full_length",
    "test_flash_attention.py::test_flash_gqa_gradients",
    "test_flash_attention.py::test_flash_gradients_match_dense",
    "test_real_data.py::test_criteo_tsv_trains_dlrm_batch",  # a DLRM step
    # second pass (fast-tier --durations, 2026-07-30): everything ≥6s —
    # mostly whole-model jit compiles; cheaper siblings keep the coverage
    "test_resnet.py::test_resnet18_forward_shapes_and_dtypes",
    "test_resnet.py::test_norm_dtype_follows_compute_dtype",
    "test_grad_accum.py::test_accum_multiple_steps_trains",
    "test_grad_accum.py::test_trainer_fit_accum_wiring",
    "test_grad_accum.py::test_accum_equals_full_batch_step",
    "test_bert.py::test_hf_bert_export_round_trip",
    "test_bert.py::test_hf_bert_torch_import_matches_flax_import",
    "test_bert.py::TestSequencePacking::test_bert_consumes_segment_ids",
    "test_dataframe.py::test_criteo_shaped_pipeline_end_to_end",
    "test_llama.py::test_scan_matches_loop",
    "test_llama.py::TestLoRA::test_zero_init_matches_base",
    "test_llama.py::TestLoRA::test_merge_lora",
    "test_train_mnist.py::test_evaluate_weight_metric_aggregation",
    "test_train_mnist.py::test_evaluate_counts_tail_batch_exactly",
    "test_dlrm.py::test_dlrm_forward_shape",
    "test_dlrm.py::test_sharded_embedding_matches_replicated",
    "test_checkpoint.py::test_reshard_on_restore",
    "test_memory.py::test_7b_fsdp_layout_lowers_abstractly",
    # third pass: r3 additions that compile whole-model train steps
    "test_moe.py::TestMoELlama",
    "test_moe.py::test_predict_and_eval_get_plain_logits",
    "test_llama.py::TestLlamaPackedSegments",
    "test_llama.py::test_pp_rejects_segment_ids",
    "test_memory.py::test_param_count_matches_model_exactly",
    "test_llama.py::test_parity_with_transformers",
    "test_checkpoint.py::test_retention",
    # MPMD pipelines: whole-model jits on threads, plus a real
    # process-level stage-kill drill
    "test_mpmd.py::test_mpmd_bitwise_parity_vs_single_program_llama_pp",
    "test_mpmd.py::test_mpmd_heterogeneous_stage_meshes",
    "test_mpmd.py::test_mpmd_stage_geometry_change_on_restore",
    "test_mpmd.py::test_pipeline_supervisor_stage_kill_drill",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(pat in item.nodeid for pat in _SLOW_PATTERNS):
            item.add_marker(pytest.mark.slow)
    if hasattr(config, "workerinput"):      # an xdist worker's collection
        items.sort(key=_start_rank)


# ---------------------------------------------------------------------------
# The order the files start in. Under xdist's ``--dist loadfile`` a worker
# takes a whole file, and xdist by default hands out the files with the MOST
# tests first: a file of a few slow tests then starts last and the run waits
# for it alone. Under xdist the files slowest in a run of six workers on an
# 8-core host (seconds, setup included) start first, slowest first, and the
# rest keep their order; a run without xdist keeps pytest's order.
# ---------------------------------------------------------------------------

_SLOWEST_FIRST = (
    "test_compile_for_v5e.py",          # 700
    "test_window_moe_controls.py",      # 600
    "test_hybrid_decoder.py",           # 557
    "test_latent_moe_controls.py",      # 399
    "test_latent_attention.py",         # 397
    "test_window_moe_benchmark.py",     # 372
    "test_latent_moe_benchmark.py",     # 284
    "test_sparse_decoder.py",           # 279
    "test_ssm_moe_controls.py",         # 274
    "test_hybrid_decoder_ssm.py",       # 254
    "test_flash_attention.py",          # 243
    "test_ssm_moe_benchmark.py",        # 241
    "test_hybrid_moe_controls.py",      # 223
    "test_hybrid_decoder_window.py",    # 201
    "test_main_path.py",                # 198
    "test_sparse_moe_controls.py",      # 191
    "test_llama.py",                    # 181
    "test_flash_window.py",             # 175
    "test_ssd.py",                      # 174
    "test_moe.py",                      # 164
)


def _start_rank(item):
    name = item.nodeid.split("::")[0].rsplit("/", 1)[-1]
    return (_SLOWEST_FIRST.index(name) if name in _SLOWEST_FIRST
            else len(_SLOWEST_FIRST))


def pytest_configure(config):
    # the order above, not the count of tests, decides which file starts
    # first (xdist reads this on the controller; without xdist it is unused)
    config.option.loadscopereorder = False


# ---------------------------------------------------------------------------
# One time limit for every test. The whole run has a limit of its own, so a
# test that hangs would take the run's; this one fails the test instead. It
# counts from the start of the test's setup, so the fixtures it is the first
# to use are in it, and it is armed in each phase alone, so that it never
# fires while pytest writes a report. A test that sets its own SIGALRM
# handler puts this one back when it ends.
# ---------------------------------------------------------------------------

#: seconds a test may take, its setup and teardown included
TEST_LIMIT_S = 300
#: the tests that may take longer, and how long (seconds measured under six
#: xdist workers on an 8-core host). The controls of the model cells: the
#: first test's setup is one run of the cell, then the harness's comparison
#: compiled again for each planted fault (``benchmark/harness/checks.py``),
#: up to 640 s. A rehearsal of a cell: two runs, each compiling the step and
#: the reference check, up to 209 s. The train steps compiled for a
#: described v5e at published widths, up to 222 s
_LONGER_LIMITS_S = {"_moe_controls": 900,
                    "::test_the_cell_rehearses_at_a_toy_size": 600,
                    "test_compile_for_v5e.py": 600}


@contextlib.contextmanager
def _time_limit(item):
    limit = next((s for pat, s in _LONGER_LIMITS_S.items()
                  if pat in item.nodeid), TEST_LIMIT_S)
    if not hasattr(item, "_limit_deadline"):
        item._limit_deadline = time.monotonic() + limit

    def expired(signum, frame):
        raise TimeoutError(f"{item.nodeid} ran over its {limit}-s limit")

    before = signal.signal(signal.SIGALRM, expired)
    signal.alarm(max(1, math.ceil(item._limit_deadline - time.monotonic())))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    with _time_limit(item):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _time_limit(item):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    with _time_limit(item):
        yield


@pytest.fixture(autouse=True)
def _reset_session():
    """Each test gets a clean Session slate (module-level singleton) and a
    clean telemetry binding — a writer configured against one test's tmp
    dir must not leak events into the next test's run."""
    yield
    if Session._active is not None:
        Session._active.stop()
    from distributeddeeplearningspark_tpu import telemetry

    telemetry.reset()


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs
