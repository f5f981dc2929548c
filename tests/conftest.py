"""Test configuration: force an 8-device virtual CPU mesh before JAX imports.

The reference (multigpu.py:262-263) tests distribution by spawning one process
per physical GPU; we instead simulate an 8-device TPU slice on CPU so the whole
distributed surface is exercised in CI without hardware (SURVEY.md section 4).
"""
import os
import sys

import pytest

# JAX_PLATFORMS is the one way to choose a backend; the suite defaults to
# CPU (JAX_PLATFORMS=tpu runs the few chip-only tests on a chip).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compilation cache: the suite is dominated by XLA compiles of
# the VGG train/epoch programs (~30s each on CPU); caching their serialized
# executables roughly halves re-run time.  Set as an ENV VAR, before jax is
# imported, so this process binds it and every SUBPROCESS the suite spawns
# — jax.distributed multihost workers, CLI end-to-end runs, bench children
# — shares the same cache (utils/platform.py leaves a set variable alone).
# An outer setting wins.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(os.path.dirname(__file__), ".jax_cache"))

# Make the repo root importable regardless of pytest rootdir configuration.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_addoption(parser):
    parser.addoption(
        "--extended", action="store_true", default=False,
        help="Run the extended cross-strategy sweep too.  Every strategy "
             "axis (resident/accum/zero/sync_bn/device_augment/multi-host) "
             "keeps at least one representative equality test in the "
             "default run; the 'extended' marker holds the remaining "
             "combinations and long-horizon traces, each covered "
             "transitively by a default test (VERDICT r2 #10: the default "
             "suite must stay under 30 minutes on a 1-core box).")


# Tier ledger (round 20).  Tier-1 (`-m 'not slow'`) must finish inside the
# driver's 870 s wall clock on a 1-core 2.1 GHz box; a --durations=0 sweep
# measured the default suite at ~2000 s there, so the heaviest nodes move to
# the slow tier.  Every strategy axis keeps at least one representative
# equality test in tier-1 (same rule as the 'extended' marker below):
# resume -> test_resume_across_mesh_sizes_and_modes + resume_continues_exactly
# + midepoch_preemption[9-gathered]; resident -> resident_matches_streaming +
# resident_cli_end_to_end; ZeRO -> test_zero_matches_replicated; torch parity
# -> vgg_loss_parity_vs_torch[1]; TP -> tp_24_42_match_1d_and_live_shardings;
# KV decode -> test_decode_logits_identical_to_full_forward_every_step.
# Re-tier against fresh --durations data whenever this set changes.
TIER2_SLOW_NODES = frozenset({
    "tests/test_autoplan.py::test_search_is_deterministic_bit_identical",
    "tests/test_checkpoint.py::test_async_save_error_does_not_mask_inflight",
    "tests/test_cli_extras.py::test_eval_every",
    "tests/test_cli_extras.py::test_export_torch_roundtrip",
    "tests/test_cli_extras.py::test_graft_entry_hooks",
    "tests/test_cli_extras.py::test_init_from_torch_checkpoint",
    "tests/test_e2e.py::test_cli_end_to_end",
    "tests/test_e2e.py::test_training_learns_synthetic_signal",
    "tests/test_grad_accum.py::test_accum_matches_hand_composition",
    "tests/test_grad_accum.py::test_accum_of_one_equals_plain_step",
    "tests/test_kvcache.py::"
    "test_engine_greedy_tokens_match_reference_across_buckets[13]",
    "tests/test_metrics_and_misc.py::test_metrics_jsonl",
    "tests/test_metrics_and_misc.py::test_resnet18_train_step_runs",
    "tests/test_multichip_envelope.py::"
    "test_streaming_matches_resident_on_6_device_mesh",
    "tests/test_prefetch.py::test_grad_accum_group_stream_prefetch_bitwise",
    "tests/test_prefetch.py::test_trainer_final_state_bitwise_across_depths",
    "tests/test_resident.py::test_resident_matches_streaming_device_augment",
    "tests/test_resident.py::test_resident_ragged_tail",
    "tests/test_resident.py::test_resident_single_replica_ragged",
    "tests/test_resilience.py::test_bench_scan_record_carries_unroll_marker",
    "tests/test_resilience.py::"
    "test_drift_audit_restore_recovers_and_completes",
    "tests/test_resilience.py::"
    "test_fail_ckpt_write_surfaces_at_next_boundary_lineage_untorn",
    "tests/test_resilience.py::test_guard_spike_rollback_skips_poisoned_window",
    "tests/test_resilience.py::"
    "test_legacy_checkpoint_missing_data_state_warns",
    "tests/test_resilience.py::"
    "test_midepoch_preemption_resume_bit_identical[5-gathered]",
    "tests/test_resilience.py::"
    "test_midepoch_preemption_resume_bit_identical[5-sharded]",
    "tests/test_resilience.py::"
    "test_midepoch_preemption_resume_bit_identical[9-sharded]",
    "tests/test_resilience.py::test_on_nan_restore_budget_exhausts",
    "tests/test_resilience.py::test_on_nan_restore_recovers_and_completes",
    "tests/test_resilience.py::test_on_nan_skip_logs_and_continues",
    "tests/test_resilience.py::"
    "test_preemption_drill_resume_matches_uninterrupted",
    "tests/test_resilience.py::test_resume_falls_back_on_torn_head",
    "tests/test_resilience.py::"
    "test_sharded_lineage_trims_dropped_epochs_shards",
    "tests/test_resilience.py::test_sharded_resume_falls_back_on_missing_shard",
    "tests/test_resilience.py::test_sharded_resume_falls_back_on_torn_shard",
    "tests/test_resilience.py::test_torn_data_state_degrades_to_epoch_boundary",
    "tests/test_round2_fixes.py::test_resident_eval_test_set_uploaded_once",
    "tests/test_round3_fixes.py::test_cli_eval_computes_in_trained_precision",
    "tests/test_round4_fixes.py::"
    "test_optimizer_steps_formula_matches_actual_grouping",
    "tests/test_round4_fixes.py::test_pipelined_losses_complete_on_abort",
    "tests/test_round4_fixes.py::"
    "test_ragged_accum_step_count_matches_schedule_resident",
    "tests/test_round4_fixes.py::"
    "test_ragged_accum_step_count_matches_schedule_streaming",
    "tests/test_sync_bn.py::test_unsynced_bn_differs_across_sharding",
    "tests/test_tp.py::test_checkpoint_portable_across_mesh_shapes",
    "tests/test_tp.py::test_sharded_checkpoint_portability_matrix",
    "tests/test_tp.py::test_tp_accum_m1_bit_identical",
    "tests/test_tp.py::test_tp_m1_bit_identical_to_1d_with_dropout",
    "tests/test_tp.py::test_tp_resident_epoch_matches_streaming",
    "tests/test_tp.py::test_tp_zero_composes_and_momentum_spec_merges",
    "tests/test_train_step.py::test_golden_trace_full_lr_triangle",
    "tests/test_train_step.py::test_vgg_loss_parity_vs_torch[8]",
    "tests/test_zero.py::test_zero_checkpoint_interchangeable",
    "tests/test_zero.py::test_zero_cli_end_to_end",
    "tests/test_zero.py::test_zero_resident_accum_all_composed",
    "tests/test_zero.py::test_zero_resident_matches_replicated_streaming",
    "tests/test_zero.py::test_zero_sync_bn_matches_replicated",
})


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid in TIER2_SLOW_NODES:
            item.add_marker(pytest.mark.slow)
    if config.getoption("--extended"):
        return
    skip = pytest.mark.skip(
        reason="extended cross-strategy sweep; run with --extended")
    for item in items:
        if "extended" in item.keywords:
            item.add_marker(skip)
