"""Bin-width tiers through ``xtb.train``: the trees of a page whose columns
need 2 to 256 bins are the trees grown over one tier of 256, structure for
structure, ``default_left`` included; and what the spans say of the tiers."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _bosch(rows):
    from benchmarks.data_missing import bosch_like

    X, y = bosch_like(11, rows)[:2]
    return X, y, {"objective": "binary:logistic", "max_depth": 3,
                  "eta": 0.1, "scale_pos_weight": 30.0}


def _counts(rows):
    """40 count columns of 2 to 200 distinct values beside 24 continuous
    ones (the ranking cell's mix), a tenth of the entries absent."""
    rng = np.random.default_rng(12)
    tops = np.r_[np.repeat([2, 4, 8, 13, 21, 41, 97, 200], 5)]
    X = np.concatenate(
        [rng.integers(0, tops, size=(rows, len(tops))).astype(np.float32),
         rng.normal(size=(rows, 24)).astype(np.float32)], axis=1)
    y = (X[:, 3] + X[:, 37] / 50 + X[:, 45] + rng.normal(size=rows) > 2.0)
    X[rng.random(X.shape) < 0.1] = np.nan
    return X, y.astype(np.float32), {"objective": "binary:logistic",
                                     "max_depth": 5, "eta": 0.3}


def _trees(bst):
    return [(t.split_indices.tolist(), t.split_bins.tolist(),
             t.default_left.tolist(), t.left_children.tolist(),
             t.right_children.tolist()) for t in bst.trees]


@pytest.mark.parametrize("policy", ["depthwise", "lossguide"])
@pytest.mark.parametrize("data", ["bosch-24000x968", "counts-6000x64"])
def test_trees_with_tiers_are_the_trees_without(monkeypatch, data, policy):
    import xgboost_tpu as xtb
    from xgboost_tpu.ops import histogram
    from xgboost_tpu.telemetry.spans import recent

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    kind, shape = data.split("-")
    rows = int(shape.split("x")[0])
    if kind == "bosch" and policy == "lossguide":
        rows //= 4  # the pass builds 32 nodes' one-hot a chunk without tiers
    X, y, params = {"bosch": _bosch, "counts": _counts}[kind](rows)
    if policy == "lossguide":
        params.update(grow_policy="lossguide", max_leaves=12, max_depth=0)

    def train():
        d = xtb.QuantileDMatrix(X, label=y)
        bst = xtb.train(params, d, 2, verbose_eval=False)
        (binned,) = recent("dmatrix.bin")[-1:]
        return bst, binned, d

    with_tiers, binned, d = train()
    F, B = X.shape[1], 256
    assert d._ellpack.tiers is not None
    assert binned["bins.onehot_rows"] == histogram.onehot_rows(
        d._ellpack.tiers, B, F)
    assert binned["bins.onehot_rows"] < (0.5 if kind == "bosch" else 0.9) * F * B
    assert binned["bins.tiers"].startswith("32:")
    if policy == "depthwise":
        assert recent("grow.build_hist+eval_split")[-1]["onehot_rows"] \
            == binned["bins.onehot_rows"]
    monkeypatch.setattr(histogram, "bin_tiers", lambda n_bins, n_bin: None)
    without, binned, d = train()
    assert d._ellpack.tiers is None
    assert binned["bins.onehot_rows"] == F * B
    assert binned["bins.tiers"] == f"{B}:{F}"
    assert _trees(with_tiers) == _trees(without)
    assert any(not all(dl) for *_, dl, _, _ in _trees(without))
    for a, b in zip(with_tiers.trees, without.trees):
        # a split's cut to the bit, a leaf's value to float32's last bits
        np.testing.assert_allclose(a.split_conditions, b.split_conditions,
                                   rtol=1e-5, atol=1e-7)
