"""The airline maker (``benchmarks/data_cat.py``): benchm-ml's columns,
kinds and cardinalities, Distance a function of the airport pair, effects
that no code order follows, the label's share, and rows that the seed alone
decides."""
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import data_cat  # noqa: E402

ROWS = 200_000


def test_the_columns_are_benchm_mls():
    X, y = data_cat.airline_like(1, ROWS)
    assert X.shape == (ROWS, 8) and X.dtype == np.float32
    assert y.dtype == np.float32 and set(np.unique(y)) == {0.0, 1.0}
    assert not np.isnan(X).any()
    col = {c: i for i, c in enumerate(data_cat.COLUMNS)}
    for name, n in data_cat.CATEGORIES.items():
        codes = X[:, col[name]]
        assert np.array_equal(codes, np.round(codes))
        assert codes.min() == 0 and codes.max() == n - 1, name
        assert len(np.unique(codes)) == n, name
    dep = X[:, col["DepTime"]].astype(np.int64)
    assert (dep % 100 < 60).all() and (dep // 100 < 24).all()
    assert data_cat.categorical_columns() == [0, 1, 2, 4, 5, 6]
    assert data_cat.numeric_columns() == [3, 7]


def test_distance_is_a_function_of_the_pair_and_hubs_are_busy():
    X, _ = data_cat.airline_like(2, ROWS)
    o, d, miles = X[:, 5].astype(int), X[:, 6].astype(int), X[:, 7]
    assert (o != d).all()
    table = data_cat.world().distance
    assert np.array_equal(miles, table[o, d])
    assert np.array_equal(table, table.T)
    share = np.bincount(o, minlength=data_cat.AIRPORTS) / ROWS
    assert 0.04 < share.max() < 0.09 and share.min() > 0


def test_no_effect_follows_the_code_order():
    w = data_cat.world()
    for name, eff in w.effects.items():
        if len(eff) > 3:
            steps = np.sign(np.diff(eff))
            assert 0 < (steps > 0).mean() < 1, name


def test_the_label_is_a_fifth_positive_and_the_seed_decides_the_rows():
    big = 2 ** 31 + 12_345  # a seed past 32 signed bits
    X1, y1 = data_cat.airline_like(big, ROWS)
    X2, y2 = data_cat.airline_like(big, ROWS)
    assert np.array_equal(X1, X2) and np.array_equal(y1, y2)
    assert abs(y1.mean() - data_cat.POSITIVE_SHARE) < 0.002
    X3, _ = data_cat.airline_like(big + 1, ROWS)
    assert not np.array_equal(X1, X3)
