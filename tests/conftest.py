"""Test harness config: run JAX on a virtual 8-device CPU mesh
(SURVEY §4: single-process multi-device harness via
--xla_force_host_platform_device_count, mirroring the reference's in-process
multi-worker tests, tests/cpp/collective/test_worker.h:155).

Tests run on the CPU: the platform is forced through jax.config.update,
which holds whatever imported jax before this file ran.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    # must be in the environment before the CPU backend initializes
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# arm the runtime lockdep witness for the whole suite (before the first
# xgboost_tpu import, so module-level locks are witnessed): every test
# doubles as a lock-order/seam-discipline probe, and the session fixture
# below asserts the suite produced zero reports.  Respect an explicit
# operator setting (e.g. XGBOOST_TPU_LOCKDEP=0 to profile witness cost).
os.environ.setdefault("XGBOOST_TPU_LOCKDEP", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running multi-process tests")
    config.addinivalue_line(
        "markers", "quick: fast smoke tier (`pytest -m quick` < 3 min) — "
        "the reference's marker-tier role (SURVEY §4); full suite nightly")


# Fast smoke tier: files whose tests are individually cheap, minus members
# measured slow (> ~8 s single-core).  Keep `pytest -m quick` under 3 min:
# it is the per-commit gate; the full suite is the nightly/per-milestone one.
_QUICK_FILES = {
    "test_basic.py", "test_model_io.py", "test_boosters.py",
    "test_bestfirst.py", "test_exact.py", "test_grower_parity.py",
    "test_collective_backend.py", "test_constraints.py",
    "test_continuation.py", "test_device_ingest.py", "test_hist_kernels.py",
    "test_multiquantile.py", "test_ranking.py", "test_survival.py",
    "test_categorical.py", "test_shap.py", "test_golden_models.py",
    "test_serving.py", "test_arrow.py", "test_telemetry.py",
    "test_timer_observer.py", "test_reliability.py",
    "test_serving_faults.py", "test_reliability_multiprocess.py",
    "test_analysis.py", "test_native_threads.py", "test_elastic.py",
    "test_lifecycle.py", "test_updaters_process.py", "test_extmem.py",
    "test_integrity.py", "test_chaos.py", "test_watchdog.py",
    "test_failover.py", "test_resources.py", "test_window_store.py",
    "test_online.py", "test_profiler.py", "test_lockdep.py",
}
_QUICK_DENY = {
    # measured > ~8 s (full-suite --durations)
    "test_streamed_sparse_predict_bounded_memory", "test_pandas_input",
    "test_base_margin_and_weights", "test_max_leaves_budget",
    "test_monotone_increasing_decreasing", "test_quantile_objective_coverage",
    "test_interaction_constraints_respected", "test_num_parallel_tree_forest",
    "test_bestfirst_matches_depthwise_on_balanced_data",
    "test_lossguide_distributed_global_bestfirst",
    "test_exact_close_to_hist", "test_exact_two_process_matches_single",
    "test_onehot_vs_partition_regimes", "test_categorical_training_improves",
    "test_category_recode_between_frames", "test_unseen_category_goes_left",
    "test_device_shap_throughput", "test_device_shap_matches_host",
    "test_jax_array_input_matches_numpy", "test_subtraction_trick_same_trees",
    "test_single_quantile_still_scalar", "test_multi_quantile_training",
    "test_multi_expectile_training", "test_rank_objectives_improve",
    "test_aft_improves_and_correlates", "test_inmemory_thread_workers_identical_trees",
    "test_feature_weights_bias_column_sampling",
    "test_config_roundtrip_continuation", "test_iteration_range_and_slice",
    "test_aft_interval_censored", "test_custom_objective",
    "test_categorical_save_load_exact", "test_torch_dlpack_input",
    "test_continuation_identity_same_booster",
    "test_bestfirst_budget_and_quality", "test_gradient_based_sampling",
    "test_deterministic_across_runs", "test_adaptive_leaf_mae",
    "test_rank_requires_groups", "test_dart_trains_and_roundtrips",
    "test_exact_oracle_parity", "test_continuation_identity_after_reload",
    "test_ranker_sklearn_with_eval", "test_dart_weighted_sampling",
    "test_categorical_nan_uses_default_direction",
    "test_cox_partial_likelihood",
    "test_inmemory_elastic_shrink_finishes_at_reduced_world",
    "test_two_process_elastic_shrink_to_single_worker",
    "test_manager_continuation_resumes_from_checkpoint",
    "test_lifecycle_end_to_end_fleet",
    "test_online_closed_loop_end_to_end",
    "test_chaos_online_episode_green_and_deterministic",
    "test_extmem_matches_incore", "test_extmem_multidevice_matches_single",
    "test_sparse_page_dmatrix_raw_predict_and_training",
    "test_sparse_page_dmatrix_scipy_batches_and_sentinel",
    "test_tracker_sigkill_mid_round_bitwise_parity",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        fname = os.path.basename(str(item.fspath))
        base = item.name.split("[")[0]
        if fname in _QUICK_FILES and base not in _QUICK_DENY:
            item.add_marker(pytest.mark.quick)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """A silently-skipping oracle must be LOUD (VERDICT r3 weak #2): every
    'oracle-verified' parity claim is unverifiable while the oracle binary
    is missing, so say so in the suite summary, unmissably."""
    from xgboost_tpu.testing import HAVE_ORACLE, ORACLE_PKG

    if not HAVE_ORACLE:
        terminalreporter.write_sep(
            "=", "ORACLE MISSING — parity UNVERIFIED", red=True, bold=True)
        terminalreporter.write_line(
            f"The reference-xgboost oracle is not built ({ORACLE_PKG}); every "
            "test_oracle_parity/test_exact oracle check SKIPPED.\n"
            "Rebuild with: bash oracle/build_oracle.sh   (~40 min, durable "
            "under /root/oracle_build)")


@pytest.fixture(scope="session", autouse=True)
def _lockdep_clean_session():
    """The whole suite must leave the lockdep witness silent: any
    lock-order inversion or lock-held-across-seam report from real test
    traffic is a concurrency bug, not noise.  Tests that provoke reports
    deliberately (test_lockdep.py) clear them before returning."""
    yield
    from xgboost_tpu.reliability import lockdep

    if lockdep.enabled():
        rs = lockdep.reports()
        assert not rs, "lockdep witness reports leaked from the suite: " \
            + "; ".join(f"[{r['kind']}] {r['msg']}" for r in rs)


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


@pytest.fixture(scope="module", autouse=True)
def _bound_compile_cache():
    """Drop compiled executables between test modules.  A full-suite run
    accumulates hundreds of jitted level programs; XLA:CPU has been observed
    to segfault inside backend_compile_and_load near the end of the suite
    (whole-suite run 2026-07-29), and clearing per module bounds the live
    executable count at a small recompile cost."""
    yield
    jax.clear_caches()
