"""Device grower vs pure-numpy reference — the core correctness oracle
(the role of GPU↔CPU parity tests in the reference, SURVEY §4)."""
import numpy as np
import pytest

import xgboost_tpu as xtb
from xgboost_tpu.data.ellpack import build_ellpack
from xgboost_tpu.data.quantile import sketch_dense
from xgboost_tpu.ops.split import SplitParams
from xgboost_tpu.testing.reference import grow_tree_np
from xgboost_tpu.tree.grow import HistTreeGrower


def _grow_both(X, gpair_np, max_depth=4, max_bin=16, **kw):
    import jax.numpy as jnp

    cuts = sketch_dense(X, max_bin, use_device=False)
    ell = build_ellpack(X, cuts, row_align=64)
    R, R_pad = ell.n_rows, ell.n_padded
    gp = np.zeros((R_pad, 2), np.float32)
    gp[:R] = gpair_np
    valid = jnp.arange(R_pad) < R

    params = SplitParams(
        eta=kw.get("eta", 0.3), gamma=kw.get("gamma", 0.0),
        min_child_weight=kw.get("min_child_weight", 1.0),
        lambda_=kw.get("lambda_", 1.0), alpha=kw.get("alpha", 0.0),
        max_delta_step=kw.get("max_delta_step", 0.0),
    )
    grower = HistTreeGrower(max_depth, params)
    state = grower.grow(ell.bins, jnp.asarray(gp), valid, ell.cuts_pad, ell.n_bins)
    dev = HistTreeGrower.to_host(state)

    bins_np = np.asarray(ell.bins)[:R]
    ref = grow_tree_np(
        bins_np, gpair_np.astype(np.float64), ell.bin_width,
        np.asarray(cuts.n_bins_array()), max_depth,
        lam=params.lambda_, alpha=params.alpha, mds=params.max_delta_step,
        min_child_weight=params.min_child_weight, gamma=params.gamma, eta=params.eta,
    )
    return dev, ref


@pytest.mark.parametrize("sparsity", [0.0, 0.3])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tree_structure_matches_reference(seed, sparsity):
    rng = np.random.default_rng(seed)
    n, f = 400, 6
    X = rng.normal(size=(n, f)).astype(np.float32)
    if sparsity:
        X[rng.random((n, f)) < sparsity] = np.nan
    y = (X[:, 0] * 1.5 + np.nan_to_num(X[:, 1]) + 0.2 * rng.normal(size=n) > 0).astype(
        np.float32
    )
    p = 1.0 / (1.0 + np.exp(0.0))
    grad = (p - y).astype(np.float32)
    hess = np.full(n, p * (1 - p), np.float32)
    gpair = np.stack([grad, hess], axis=1)

    dev, ref = _grow_both(X, gpair, max_depth=4, max_bin=16)

    np.testing.assert_array_equal(dev.feat, ref["feat"])
    np.testing.assert_array_equal(dev.sbin, ref["sbin"])
    np.testing.assert_array_equal(dev.is_leaf, ref["is_leaf"])
    split_mask = ref["feat"] >= 0
    np.testing.assert_array_equal(dev.dleft[split_mask], ref["dleft"][split_mask])
    np.testing.assert_allclose(dev.leaf_val, ref["leaf_val"], rtol=1e-2, atol=5e-4)


@pytest.mark.parametrize(
    "kw",
    [
        dict(alpha=0.5),
        dict(min_child_weight=5.0),
        dict(gamma=1.0),
        dict(max_delta_step=0.5),
        dict(lambda_=10.0),
    ],
)
def test_regularizers_match_reference(kw):
    rng = np.random.default_rng(7)
    n, f = 300, 5
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = X[:, 0] - 2 * X[:, 2] + 0.1 * rng.normal(size=n)
    gpair = np.stack([-(y - 0.0), np.ones(n)], axis=1).astype(np.float32)

    dev, ref = _grow_both(X, gpair, max_depth=3, max_bin=12, **kw)
    np.testing.assert_array_equal(dev.feat, ref["feat"])
    np.testing.assert_array_equal(dev.sbin, ref["sbin"])
    np.testing.assert_allclose(dev.leaf_val, ref["leaf_val"], rtol=1e-2, atol=5e-4)


def test_leaf_positions_match_rows():
    rng = np.random.default_rng(3)
    n, f = 200, 4
    X = rng.normal(size=(n, f)).astype(np.float32)
    gpair = np.stack([rng.normal(size=n), np.ones(n)], axis=1).astype(np.float32)
    import jax.numpy as jnp

    from xgboost_tpu.tree.grow import leaf_margin_delta

    dev, ref = _grow_both(X, gpair, max_depth=3, max_bin=8)
    # every valid row must sit on a leaf whose numpy row set contains it
    # (reconstruct from ref rows_of)
    pos_expected = np.zeros(n, np.int64)
    for node, rows in ref["rows_of"].items():
        if ref["is_leaf"][node]:
            pos_expected[rows] = node
    # device pos is internal; verify via margin deltas instead
    delta_ref = ref["leaf_val"][pos_expected]
    # device margin delta
    cuts = None
    # regrow to capture state
    from xgboost_tpu.data.ellpack import build_ellpack
    from xgboost_tpu.data.quantile import sketch_dense
    from xgboost_tpu.ops.split import SplitParams
    from xgboost_tpu.tree.grow import HistTreeGrower

    cuts = sketch_dense(X, 8, use_device=False)
    ell = build_ellpack(X, cuts, row_align=64)
    gp = np.zeros((ell.n_padded, 2), np.float32)
    gp[:n] = gpair
    valid = jnp.arange(ell.n_padded) < n
    grower = HistTreeGrower(3, SplitParams(0.3, 0.0, 1.0, 1.0, 0.0, 0.0))
    state = grower.grow(ell.bins, jnp.asarray(gp), valid, ell.cuts_pad, ell.n_bins)
    delta_dev = np.asarray(leaf_margin_delta(state.pos, state.leaf_val))[:n]
    np.testing.assert_allclose(delta_dev, delta_ref, rtol=1e-2, atol=5e-4)


@pytest.mark.parametrize("max_depth", [1, 2, 7, 8, 9])
def test_padded_levels_parity_deep(max_depth):
    """The shared padded interior programs (compile-wall fix) must grow
    identical trees to per-depth programs at depth > 5 — on CPU the default
    flips to per-depth for speed, so pin the padded path explicitly.  The
    edges of the one depth-wise loop: no interior level at depth 1, one of
    width 2 at depth 2 (both rules' width, two programs); and of
    ``level_width``'s tiers: depth 7 hands the histogram over once (32 ->
    the cap 64), depth 8 once (32 -> 128), depth 9 twice (32 -> 128 -> the
    cap 256)."""
    import hashlib

    import xgboost_tpu as xtb
    from xgboost_tpu.data.dmatrix import DMatrix
    from xgboost_tpu.ops.split import SplitParams
    from xgboost_tpu.tree.grow import HistTreeGrower
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    X = rng.normal(size=(3000, 6)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.3 * rng.normal(size=3000) > 0).astype(np.float32)
    d = DMatrix(X, label=y)
    ell = d.ensure_ellpack(max_bin=32)
    bins = jnp.asarray(ell.bins)
    R = bins.shape[0]
    valid = jnp.arange(R) < 3000
    gp = np.zeros((R, 2), np.float32)
    gp[:3000, 0] = 0.5 - y
    gp[:3000, 1] = 0.25
    gp = jnp.asarray(gp)
    params = SplitParams(eta=0.3, gamma=0.0, min_child_weight=1.0,
                         lambda_=1.0, alpha=0.0, max_delta_step=0.0)

    args = (bins, gp, valid, jnp.asarray(ell.cuts_pad),
            jnp.asarray(ell.n_bins))
    t_pad = HistTreeGrower(max_depth, params, padded_levels=True).grow(*args)
    t_per = HistTreeGrower(max_depth, params, padded_levels=False).grow(*args)
    for name in ("feat", "sbin", "thr", "leaf_val", "is_leaf"):
        np.testing.assert_array_equal(np.asarray(getattr(t_pad, name)),
                                      np.asarray(getattr(t_per, name)),
                                      err_msg=name)


@pytest.mark.parametrize("max_depth", range(1, 13))
def test_level_width_holds_the_level_under_the_cap(max_depth):
    """``level_width``, the one place a shared interior program's width is
    decided: every interior depth fits, none is wider than the widest
    interior level, the width never shrinks on the way down (the hand-over
    pads, it does not cut), the tiers are 32, 128, 512, ..., and up to
    depth 6 there is one width, as before the tiers."""
    from xgboost_tpu.tree.grow import level_width

    widths = [level_width(d, max_depth) for d in range(1, max_depth)]
    cap = 1 << (max_depth - 1)
    for d, w in zip(range(1, max_depth), widths):
        assert (1 << d) <= w <= cap, (d, w)
        assert w == cap or w in (32, 128, 512, 2048), (d, w)
    assert widths == sorted(widths)
    if max_depth <= 6:
        assert set(widths) <= {cap}
    if max_depth == 8:
        assert widths == [32] * 5 + [128] * 2
