"""The program's categorical splits against ``benchmarks/reference_cat.py``
on the CPU, at small sizes on seeded random data: both branches of the
scan, a column of more categories than ``max_bin``, ``max_cat_threshold``
where it binds, a categorical NaN under the default direction, depth-wise
growth and a loss-guided tree."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reference_cat  # noqa: E402

ROWS = 6_000


def make(seed, cats, numeric=1, nan_share=0.0):
    """Columns of ``cats`` codes each (random effects, none monotone in code
    order) and ``numeric`` normal columns; a logistic label."""
    rng = np.random.default_rng(seed)
    cols, latent = [], rng.logistic(0.0, 0.5, size=ROWS)
    for n in cats:
        codes = rng.integers(0, n, size=ROWS)
        latent += rng.normal(0.0, 1.0, size=n)[codes]
        col = codes.astype(np.float32)
        col[rng.random(ROWS) < nan_share] = np.nan
        latent += np.where(np.isnan(col), 0.8, 0.0)
        cols.append(col)
    for _ in range(numeric):
        v = rng.normal(size=ROWS).astype(np.float32)
        latent += 0.5 * v
        cols.append(v)
    X = np.stack(cols, axis=1)
    y = (latent > np.median(latent)).astype(np.float32)
    return X, y, ["c"] * len(cats) + ["q"] * numeric


def train(X, y, types, rounds=3, **params):
    import xgboost_tpu as xtb

    p = {"objective": "binary:logistic", "max_depth": 4, "max_bin": 32,
         "eta": 0.3, "base_score": 0.5, "max_cat_to_onehot": 4,
         "max_cat_threshold": 64, **params}
    d = xtb.QuantileDMatrix(X, label=y, max_bin=p["max_bin"],
                            feature_types=types, enable_categorical=True)
    bst = xtb.train(p, d, rounds, verbose_eval=False)
    return p, d, bst


def compare(X, y, types, p, d, bst, split_tree=None, **kw):
    model = json.loads(bytes(bst.save_raw("json")))
    page = d._ellpack
    cache = bst._get_cache(d)
    page_fr = np.ascontiguousarray(np.asarray(cache.bins)[:ROWS].T)
    trees = len(model["learner"]["gradient_booster"]["model"]["trees"])
    return model, reference_cat.compare_training(
        X, y, model, np.asarray(page.cuts.cut_ptrs, np.int64),
        np.asarray(page.cuts.cut_values, np.float32), page_fr,
        int(page.bin_width), np.arange(ROWS), page_fr,
        np.asarray(cache.margin)[:ROWS, 0],
        cat_cols=[f for f, t in enumerate(types) if t == "c"],
        numeric_cols=[f for f, t in enumerate(types) if t == "q"],
        max_bin=p["max_bin"], max_depth=p["max_depth"], eta=p["eta"],
        lam=1.0, mcw=1.0, onehot_max=p["max_cat_to_onehot"],
        threshold=p["max_cat_threshold"], base_margin=0.0, follow=trees,
        split_tree=trees - 1 if split_tree is None else split_tree, **kw)


def sets_by_column(model):
    """{column: [set sizes]} over the categorical splits of every tree."""
    out = {}
    for t in reference_cat.model_trees(model):
        for n, codes in t.sets.items():
            out.setdefault(int(t.feat[n]), []).append(len(codes))
    return out


def sound(numbers):
    assert numbers["bin_mismatch"] == 0.0
    assert numbers["hess_gap"] < 1e-5 and numbers["grad_gap"] < 1e-5
    assert numbers["leaf_gap"] < 1e-5 and numbers["margin_gap"] < 1e-5
    assert numbers["split_gap"] < 1e-6
    assert numbers["catset_gap"] == 0.0


def test_both_branches_of_the_scan_match_the_reference():
    """A column of 3 categories (under max_cat_to_onehot: one against the
    rest) and one of 40 (a partition sorted by weight)."""
    X, y, types = make(1, cats=(3, 40))
    p, d, bst = train(X, y, types)
    model, numbers = compare(X, y, types, p, d, bst)
    sound(numbers)
    sizes = sets_by_column(model)
    assert set(sizes[0]) == {1}                       # one-hot
    assert max(sizes[1]) > 1                          # partition


def test_a_column_of_more_categories_than_max_bin_trains():
    """100 categories under max_bin=16: a category a bin, the page as wide
    as the column, and the splits the reference's."""
    X, y, types = make(2, cats=(100, 8))
    p, d, bst = train(X, y, types, max_bin=16)
    page = d._ellpack
    assert page.cuts.n_bins(0) == 100 and page.bin_width == 100
    assert page.cuts.n_bins(2) <= 16
    model, numbers = compare(X, y, types, p, d, bst)
    sound(numbers)
    assert max(sets_by_column(model)[0]) > 16


def test_max_cat_threshold_binds_and_is_honoured():
    """At max_cat_threshold=4 a partition sends at most 3 categories to the
    side its scan gathers; a scan that ignored it would choose other sets."""
    X, y, types = make(3, cats=(40,), numeric=0)
    p, d, bst = train(X, y, types, max_cat_threshold=4)
    model, numbers = compare(X, y, types, p, d, bst, faults=True)
    sound(numbers)
    assert numbers["catset_gap_nothreshold"] > 0.0
    for t in reference_cat.model_trees(model):
        for n, codes in t.sets.items():
            assert min(len(codes), 40 - len(codes)) <= 3, (n, len(codes))


def test_a_categorical_nan_follows_the_default_direction():
    """A fifth of the codes absent, their own effect on the label: the
    scan learns their direction and the route sends them by it."""
    X, y, types = make(4, cats=(12, 30), nan_share=0.2)
    p, d, bst = train(X, y, types)
    assert (np.asarray(d._ellpack.bins) == d._ellpack.bin_width).any()
    model, numbers = compare(X, y, types, p, d, bst)
    sound(numbers)
    dleft = [bool(dl) for t in model["learner"]["gradient_booster"][
        "model"]["trees"] for dl, st in zip(t["default_left"], t["split_type"])
        if st == 1]
    assert any(dleft) and not all(dleft)


@pytest.mark.parametrize("depth", [2, 6])
def test_depthwise_trees_match_the_reference(depth):
    X, y, types = make(5, cats=(7, 25, 60), numeric=2)
    p, d, bst = train(X, y, types, max_depth=depth)
    sound(compare(X, y, types, p, d, bst)[1])


def test_a_loss_guided_tree_matches_the_reference():
    """``tree/bestfirst.py`` with a budget of 16 leaves: the node sums, the
    margin and every categorical split's set are the reference's (a leaf
    the budget left unsplit is no fault there, so ``split_gap`` is not
    read)."""
    X, y, types = make(6, cats=(5, 50), numeric=1)
    p, d, bst = train(X, y, types, max_depth=0, grow_policy="lossguide",
                      max_leaves=16)
    model = json.loads(bytes(bst.save_raw("json")))
    depth = max(int(reference_cat.Tree(t).depth.max()) for t in
                model["learner"]["gradient_booster"]["model"]["trees"])
    p["max_depth"] = depth
    _, numbers = compare(X, y, types, p, d, bst)
    assert numbers["hess_gap"] < 1e-5 and numbers["margin_gap"] < 1e-5
    assert numbers["catset_gap"] == 0.0
    assert sets_by_column(model)
