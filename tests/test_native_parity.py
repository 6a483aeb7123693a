"""Native-kernel vs XLA-formulation parity (native/xtb_kernels.h).

The CPU backend swaps the XLA scatter/cumsum/scan formulations for native
C++ kernels behind XLA FFI custom calls.  These tests pin the contract the
swap relies on:

- histogram: BITWISE equality (same f32 add order);
- split scan: identical decisions (feature, bin, default direction) and
  last-ulp-close gains/sums — full bitwise equality is NOT promised (the
  cumsum reduction orders differ), which is exactly why distributed init
  reconciles kernel availability across ranks (utils/native.py);
- predict: BITWISE equality (rows-outer/trees-inner preserves the scan's
  per-row add order).

Env overrides force each side; jax.clear_caches() between sides keeps the
shape-keyed jit cache from serving the other implementation's executable.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xgboost_tpu.ops.histogram import build_histogram
from xgboost_tpu.ops.split import SplitParams, evaluate_splits
from xgboost_tpu.utils import native

pytestmark = pytest.mark.skipif(not native.load_ffi(),
                                reason="FFI kernels unavailable")


def _with_impl(env_key, env_val, fn):
    old = os.environ.get(env_key)
    os.environ[env_key] = env_val
    jax.clear_caches()
    try:
        return fn()
    finally:
        if old is None:
            del os.environ[env_key]
        else:
            os.environ[env_key] = old
        jax.clear_caches()


def test_hist_native_bitwise_matches_scatter():
    rng = np.random.default_rng(0)
    for R, F, B, N, stride, dt in ((3000, 6, 17, 4, 1, np.int32),
                                   (5000, 3, 33, 8, 2, np.uint8),
                                   (2048, 5, 257, 2, 1, np.int16)):
        bins = jnp.asarray(rng.integers(0, B + 1, size=(R, F)).astype(dt))
        gpair = jnp.asarray(rng.normal(size=(R, 2)), jnp.float32)
        node0 = N - 1
        pos = jnp.asarray(
            rng.integers(node0 - 1, node0 + 2 * N, size=R), jnp.int32)

        def run():
            return np.asarray(build_histogram(
                bins, gpair, pos, node0=node0, n_nodes=N, n_bin=B,
                stride=stride))

        got = _with_impl("XTB_HIST_IMPL", "native", run)
        want = _with_impl("XTB_HIST_IMPL", "scatter", run)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("params", [
    SplitParams(eta=0.3, gamma=0.0, min_child_weight=1.0, lambda_=1.0,
                alpha=0.0, max_delta_step=0.0),
    SplitParams(eta=0.3, gamma=0.0, min_child_weight=3.0, lambda_=0.5,
                alpha=0.3, max_delta_step=0.0),
    SplitParams(eta=0.3, gamma=0.0, min_child_weight=0.0, lambda_=1.0,
                alpha=0.0, max_delta_step=0.7),
])
def test_split_native_decisions_match_xla(params):
    rng = np.random.default_rng(7)
    for trial in range(6):
        N, F, B = int(rng.integers(1, 9)), int(rng.integers(1, 7)), 33
        hist = rng.normal(size=(N, F, B, 2)).astype(np.float32)
        hist[..., 1] = np.abs(hist[..., 1])  # hessians non-negative
        # zero out padding beyond per-feature widths incl. degenerate 0/1
        n_bins = rng.integers(0 if trial == 5 else 1, B, size=F).astype(
            np.int32)
        for f in range(F):
            hist[:, f, n_bins[f]:] = 0.0
        totals = hist.sum(axis=(1, 2)) / max(F, 1)
        totals[..., 1] += 0.5  # missing mass
        fmask = rng.random((N, F)) > 0.2
        fmask[:, 0] = True

        def run():
            return evaluate_splits(
                jnp.asarray(hist), jnp.asarray(totals),
                jnp.asarray(n_bins), params, jnp.asarray(fmask))

        a = _with_impl("XTB_NO_NATIVE_SPLIT", "", run)    # native
        b = _with_impl("XTB_NO_NATIVE_SPLIT", "1", run)   # XLA
        valid = np.isfinite(np.asarray(b.gain))
        np.testing.assert_array_equal(np.asarray(a.feature)[valid],
                                      np.asarray(b.feature)[valid])
        np.testing.assert_array_equal(np.asarray(a.bin)[valid],
                                      np.asarray(b.bin)[valid])
        np.testing.assert_array_equal(np.asarray(a.default_left)[valid],
                                      np.asarray(b.default_left)[valid])
        np.testing.assert_allclose(np.asarray(a.gain)[valid],
                                   np.asarray(b.gain)[valid], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(a.left_sum)[valid],
                                   np.asarray(b.left_sum)[valid],
                                   rtol=1e-5, atol=1e-5)


def test_predict_native_bitwise_matches_xla():
    import xgboost_tpu as xtb

    rng = np.random.default_rng(3)
    X = rng.normal(size=(1200, 6)).astype(np.float32)
    X[rng.random(X.shape) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) > 0).astype(np.float32)
    bst = xtb.train({"objective": "binary:logistic", "max_depth": 4,
                     "eta": 0.3, "max_bin": 32},
                    xtb.DMatrix(X, label=y), 4, verbose_eval=False)

    def run():
        return np.asarray(bst.predict(xtb.DMatrix(X), output_margin=True))

    a = _with_impl("XTB_NO_NATIVE_PREDICT", "", run)
    b = _with_impl("XTB_NO_NATIVE_PREDICT", "1", run)
    np.testing.assert_array_equal(a, b)


def test_lambdarank_native_matches_xla():
    """Native CSR-group top-k lambda pass vs the padded XLA formulation:
    same pair set and weights -> f32-tolerance-equal gradients, across
    ragged group sizes (incl. singleton groups) and both weight modes."""
    from xgboost_tpu.objective.ranking import (_lambda_gradients_topk,
                                               _lambda_gradients_topk_native,
                                               make_topk_layout)

    rng = np.random.default_rng(5)
    sizes = np.concatenate([rng.integers(1, 40, size=30), [1, 2, 200]])
    gptr = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    R = int(gptr[-1])
    pred = rng.normal(size=R).astype(np.float32)
    y = rng.integers(0, 5, size=R).astype(np.float32)

    for ndcg_w, snorm, gnorm, k in ((True, True, True, 8),
                                    (False, False, False, 3),
                                    (True, False, True, 256)):
        ga, ha = _lambda_gradients_topk_native(
            jnp.asarray(pred), jnp.asarray(y), jnp.asarray(gptr), k=k,
            ndcg_weight=ndcg_w, score_norm=snorm, group_norm=gnorm)
        gb, hb = _lambda_gradients_topk(
            jnp.asarray(pred), make_topk_layout(gptr, y, k), k=k,
            ndcg_weight=ndcg_w, score_norm=snorm, group_norm=gnorm)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=2e-4, atol=2e-6)
        np.testing.assert_allclose(np.asarray(ha), np.asarray(hb),
                                   rtol=2e-4, atol=2e-6)


def test_lambdarank_zero_spread_first_iteration():
    """All-equal scores (round 0 with base_score): score normalization must
    be skipped identically on both paths."""
    from xgboost_tpu.objective.ranking import (_lambda_gradients_topk,
                                               _lambda_gradients_topk_native,
                                               make_topk_layout)

    rng = np.random.default_rng(1)
    gptr = np.array([0, 20, 50], np.int32)
    R = 50
    pred = np.full(R, 0.5, np.float32)
    y = rng.integers(0, 4, size=R).astype(np.float32)
    ga, ha = _lambda_gradients_topk_native(
        jnp.asarray(pred), jnp.asarray(y), jnp.asarray(gptr), k=32,
        ndcg_weight=True, score_norm=True, group_norm=True)
    gb, hb = _lambda_gradients_topk(
        jnp.asarray(pred), make_topk_layout(gptr, y, 32), k=32,
        ndcg_weight=True, score_norm=True, group_norm=True)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), rtol=2e-4,
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(ha), np.asarray(hb), rtol=2e-4,
                               atol=2e-6)
