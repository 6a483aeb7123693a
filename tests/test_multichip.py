"""Distributed-consistency tests: sharded grower over the 8-device CPU mesh
must produce bitwise-identical trees to single-device training
(SURVEY §4 distributed-consistency pattern; reference:
tests/cpp/tree/test_gpu_hist.cu, tests/python/test_collective.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from xgboost_tpu.data.ellpack import build_ellpack
from xgboost_tpu.data.quantile import sketch_dense
from xgboost_tpu.ops.split import SplitParams
from xgboost_tpu.parallel import ShardedHistTreeGrower, make_mesh
from xgboost_tpu.tree.grow import HistTreeGrower


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(0)
    R, F = 1000, 6
    X = rng.normal(size=(R, F)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] ** 2 > 0.5).astype(np.float32)
    g = np.stack([0.5 - y, np.full(R, 0.25)], 1).astype(np.float32)
    cuts = sketch_dense(X, 16, use_device=False)
    ell = build_ellpack(X, cuts, row_align=1024)
    gp = np.zeros((ell.n_padded, 2), np.float32)
    gp[:R] = g
    valid = np.arange(ell.n_padded) < R
    return ell, jnp.asarray(gp), jnp.asarray(valid)


@pytest.mark.parametrize("shared_width,max_depth",
                         [(True, 4), (False, 4), (True, 7)])
def test_sharded_tree_identical_to_single(problem, eight_devices, monkeypatch,
                                          shared_width, max_depth):
    """Under both width rules of the one depth-wise loop the mesh grower
    inherits: padded interior programs, and a program a depth.  Depth 7
    crosses a tier of ``level_width``: two interior programs (32 and 64
    slots) and the histogram handed over between them."""
    monkeypatch.setattr("xgboost_tpu.tree.grow.default_padded_levels",
                        lambda max_depth: shared_width)
    ell, gp, valid = problem
    params = SplitParams(0.3, 0.0, 1.0, 1.0, 0.0, 0.0)

    single = HistTreeGrower(max_depth, params)
    s1 = single.grow(ell.bins, gp, valid, ell.cuts_pad, ell.n_bins)

    mesh = make_mesh(8)
    row2d = NamedSharding(mesh, P("data", None))
    row1d = NamedSharding(mesh, P("data"))
    bins_s = jax.device_put(ell.bins, row2d)
    gp_s = jax.device_put(gp, row2d)
    valid_s = jax.device_put(valid, row1d)

    multi = ShardedHistTreeGrower(max_depth, params, mesh)
    multi._build(ell.bins.shape[1], ell.cuts_pad.shape[1])
    assert len(multi._interior_fns) == (2 if max_depth == 7 else 1)
    s8 = multi.grow(bins_s, gp_s, valid_s, ell.cuts_pad, ell.n_bins)

    np.testing.assert_array_equal(np.asarray(s1.feat), np.asarray(s8.feat))
    np.testing.assert_array_equal(np.asarray(s1.sbin), np.asarray(s8.sbin))
    np.testing.assert_array_equal(np.asarray(s1.is_leaf), np.asarray(s8.is_leaf))
    np.testing.assert_array_equal(np.asarray(s1.pos), np.asarray(s8.pos))
    # f32 psum vs local sum: tiny accumulation-order differences allowed
    np.testing.assert_allclose(
        np.asarray(s1.leaf_val), np.asarray(s8.leaf_val), rtol=2e-4, atol=1e-6
    )


def test_dryrun_multichip_runs(eight_devices):
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_booster_n_devices_matches_single(eight_devices):
    """End-to-end train() over the 8-device mesh == single-device training."""
    import xgboost_tpu as xtb
    from xgboost_tpu.testing.data import make_binary

    X, y = make_binary(1200, 6, seed=11)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.5}
    b1 = xtb.train(params, xtb.DMatrix(X, label=y), 5, verbose_eval=False)
    b8 = xtb.train({**params, "n_devices": 8}, xtb.DMatrix(X, label=y), 5,
                   verbose_eval=False)
    p1, p8 = b1.predict(xtb.DMatrix(X)), b8.predict(xtb.DMatrix(X))
    np.testing.assert_allclose(p1, p8, rtol=5e-4, atol=1e-5)
    for t1, t8 in zip(b1.trees, b8.trees):
        np.testing.assert_array_equal(t1.split_indices, t8.split_indices)
        np.testing.assert_array_equal(t1.left_children, t8.left_children)


def test_booster_n_devices_non_pow2(eight_devices):
    """n_devices=3 (not a divisor of 1024): the page re-aligns to
    lcm(1024, 3) and training matches single-device (VERDICT r3 #10)."""
    import xgboost_tpu as xtb
    from xgboost_tpu.testing.data import make_binary

    X, y = make_binary(900, 5, seed=23)
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.5}
    b1 = xtb.train(params, xtb.DMatrix(X, label=y), 3, verbose_eval=False)
    b3 = xtb.train({**params, "n_devices": 3}, xtb.DMatrix(X, label=y), 3,
                   verbose_eval=False)
    p1, p3 = b1.predict(xtb.DMatrix(X)), b3.predict(xtb.DMatrix(X))
    np.testing.assert_allclose(p1, p3, rtol=5e-4, atol=1e-5)
    for t1, t3 in zip(b1.trees, b3.trees):
        np.testing.assert_array_equal(t1.split_indices, t3.split_indices)


@pytest.mark.slow
def test_mesh_scan_chunking_above_chunk_size(eight_devices):
    """>2048 rows per device forces the chunked scan inside shard_map
    (regression: the scan carry must enter with the shard-varying type —
    seeding with zeros used to fail jax's varying-axes check, and this
    path was never reached by the small mesh tests)."""
    import xgboost_tpu as xtb
    from xgboost_tpu.testing.data import make_binary

    X, y = make_binary(8 * 2600, 6, seed=11)   # 2600 rows/device > chunk
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.5,
              "max_bin": 32}
    b8 = xtb.train({**params, "n_devices": 8}, xtb.DMatrix(X, label=y), 2,
                   verbose_eval=False)
    b1 = xtb.train(params, xtb.DMatrix(X, label=y), 2, verbose_eval=False)
    for t1, t8 in zip(b1.trees, b8.trees):
        np.testing.assert_array_equal(t1.split_indices, t8.split_indices)
