"""The program's trees against ``benchmarks/reference_missing.py``, structure
for structure, on the CPU at small sizes: every split's feature, cut and
default direction is the one the reference's float64 scan puts first (or ties
with it within the room the comparison has), every node's sums are the exact
sums over the rows the raw thresholds and the default directions send there,
every leaf's value is eta times its weight, and the margin is the walk's."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import data_missing, reference, reference_missing as rm  # noqa: E402

ROUNDS, ETA, LAM, MCW, MAX_BIN = 3, 0.1, 1.0, 1.0, 64


def rows_with_absent_entries(absent: float, rows=24_000, features=60, seed=0):
    """``rows x features`` with ``absent`` of the entries NaN in blocks of
    columns and one column NaN throughout; a label that reads values and
    whether they exist; about 3% positives."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    X[:, 10:20] = np.round(X[:, 10:20] * 2) / 2  # a few distinct levels
    block = rng.random((rows, features // 5)) < absent
    X[np.repeat(block, 5, axis=1)] = np.nan
    X[:, features - 1] = np.nan
    u = np.where(np.isnan(X), [1.0, -1.0, 0.5, 0.0, 0.0][:5] + [0.0] * (features - 5), X)
    score = (u[:, 0] + u[:, 1] + u[:, 2] * u[:, 12] - np.abs(u[:, 3])
             + 0.5 * rng.normal(size=rows))
    return X, (score > np.quantile(score, 0.97)).astype(np.float32)


def train(X, y, depth: int, spw):
    import xgboost_tpu as xtb

    params = {"objective": "binary:logistic", "tree_method": "hist",
              "max_depth": depth, "eta": ETA, "max_bin": MAX_BIN,
              "base_score": 0.5}
    if spw is not None:
        params["scale_pos_weight"] = spw
    d = xtb.QuantileDMatrix(X, label=y, max_bin=MAX_BIN)
    bst = xtb.train(params, d, ROUNDS, verbose_eval=False)
    page, cache = d._ellpack, bst._get_cache(d)
    model = json.loads(bst.save_raw("json").decode())
    return (model, np.asarray(page.cuts.cut_ptrs, np.int64),
            np.asarray(page.cuts.cut_values, np.float32),
            np.ascontiguousarray(np.asarray(page.bins)[:len(X)].T),
            int(page.bin_width), np.asarray(cache.margin)[:len(X), 0])


def hold_against_the_reference(X, y, depth: int, spw):
    model, ptrs, cuts, page_fr, sentinel, margin_got = train(X, y, depth, spw)
    trees = rm.model_trees(model)
    assert len(trees) == ROUNDS
    assert np.array_equal(rm.bin_rows(X, ptrs, cuts, sentinel), page_fr)
    index = rm.PresentIndex(page_fr, sentinel)
    assert index.entries == int((~np.isnan(X)).sum())
    walker = rm.Walker(X)
    check = rm.SplitCheck(walker, index, ptrs, cuts, LAM, MCW, depth)
    margin = np.zeros(len(X))
    g, h = np.empty(len(X)), np.empty(len(X))
    y64 = y.astype(np.float64)
    inner = same = 0
    for tree in trees:
        rm.weighted_gpair(margin, y64, spw or 1.0, g, h)
        total, under, absent, leaf = rm.direction_sums(tree, walker, g, h)
        G, H, A = reference.node_sums(tree, leaf, g, h)
        assert np.allclose(total[:, 0], G) and np.allclose(total[:, 1], H)
        gaps = reference.sums_gaps(tree, G, H, A, LAM, ETA)
        assert gaps["hess_gap"] < 1e-4 and gaps["grad_gap"] < 1e-4, gaps
        assert gaps["leaf_gap"] < 1e-5, gaps
        at, gains, clear, near = rm.direction_gains(tree, total, under,
                                                    absent, LAM, MCW)
        lost, on_offer = rm.default_gap_parts(tree, gains, clear, near, at)
        assert lost == 0.0 and on_offer > 0
        got = check.run(tree, g, h)
        assert got["split_gap"] == 0.0, got
        inner += int(tree.inner.sum())
        same += got["nodes_same"]
        assert int(tree.depth.max()) <= depth
        margin += tree.cond[leaf]
    # where the structures part, two cuts tie within 1e-4 of the best gain
    assert same >= 0.9 * inner, (same, inner)
    assert np.max(np.abs(margin_got - margin)
                  / np.maximum(np.abs(margin), 0.05)) < 1e-5
    return trees


@pytest.mark.parametrize("spw", [None, 32.0], ids=["unweighted", "spw"])
@pytest.mark.parametrize("absent", [0.0, 0.5, 0.81])
@pytest.mark.parametrize("depth", [3, 6, 8])
def test_trees_are_the_references_structure_for_structure(depth, absent, spw):
    X, y = rows_with_absent_entries(absent)
    trees = hold_against_the_reference(X, y, depth, spw)
    splits = np.concatenate([t.feat[t.inner] for t in trees])
    assert 59 not in splits  # a column with no value offers no cut
    left = np.concatenate([t.dleft[t.inner] for t in trees])
    if absent and depth > 3:
        assert 0 < left.sum() < len(left)  # both directions are taken


def test_trees_at_968_columns_are_the_references():
    X, y = data_missing.bosch_like(3, 4_000)
    y[:40] = 1.0  # 4,000 parts hold a dozen positives: a few more to split on
    trees = hold_against_the_reference(X, y, 6, float(len(y) / y.sum()))
    assert all(t.inner.sum() >= 3 for t in trees)


def test_a_walk_sends_nan_by_default_left():
    tree = rm.Tree({"left_children": [1, -1, -1], "right_children": [2, -1, -1],
                    "split_indices": [0, 0, 0],
                    "split_conditions": [0.5, -1.0, 1.0],
                    "default_left": [1, 0, 0], "base_weights": [0, -10, 10],
                    "sum_hessian": [3, 1, 2]})
    X = np.array([[0.2], [0.7], [np.nan]], np.float32)
    assert rm.walk([tree], X, 0.0).tolist() == [-1.0, 1.0, -1.0]
    assert rm.walk([tree], X, 0.0, dleft=False).tolist() == [-1.0, 1.0, 1.0]
    tree.dleft[0] = False
    assert rm.walk([tree], X, 0.0).tolist() == [-1.0, 1.0, 1.0]


def test_both_directions_and_the_present_absent_cut_are_on_offer():
    """One feature, three bins, absent rows that carry the gradient: the best
    candidate is the last bin's cut with the absent rows right."""
    hist = np.zeros((1, 3, 2))
    hist[0, :, 0] = [-1.0, -1.0, -1.0]
    hist[0, :, 1] = [2.0, 2.0, 2.0]
    gain, ok = rm.split_gains(hist, G=3.0, H=12.0, n_bins=np.array([3]),
                              lam=1.0, mcw=1.0)
    assert gain.shape == ok.shape == (2, 1, 3)
    assert ok[0].all() and ok[1, 0, :2].all() and not ok[1, 0, 2]
    best = np.unravel_index(np.argmax(np.where(ok, gain, -np.inf)), gain.shape)
    assert best == (0, 0, 2)  # present left, absent right
    assert gain[0, 0, 2] == pytest.approx(9 / 7 + 36 / 7 - 9 / 13)
    assert gain[1, 0, 0] == pytest.approx(25 / 9 + 4 / 5 - 9 / 13)


def test_the_weighted_pair_scales_the_positives():
    m, y = np.array([0.0, 0.0, 1.0]), np.array([1.0, 0.0, 1.0])
    g, h = rm.weighted_gpair(m, y, 172.0)
    g1, h1 = reference.logistic_gpair(m, y)
    assert g.tolist() == [172 * g1[0], g1[1], 172 * g1[2]]
    assert h.tolist() == [172 * h1[0], h1[1], 172 * h1[2]]


def test_binning_puts_nan_in_the_sentinel():
    ptrs, cuts = np.array([0, 3, 4]), np.array([0.0, 1.0, 2.0, 5.0], np.float32)
    X = np.array([[-1.0, np.nan], [0.5, 1.0], [np.nan, 9.0], [7.0, np.nan]],
                 np.float32)
    assert rm.bin_rows(X, ptrs, cuts, 256).tolist() == [
        [0, 1, 256, 2], [256, 0, 0, 256]]
    assert rm.bin_rows(X, ptrs, cuts, 256, nan_to=0).tolist() == [
        [0, 1, 0, 2], [0, 0, 0, 0]]
    index = rm.PresentIndex(rm.bin_rows(X, ptrs, cuts, 256), 256)
    assert [r.tolist() for r in index.rows] == [[0, 1, 3], [1, 2]]
    assert index.entries == 5
