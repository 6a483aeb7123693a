"""The Bosch-shaped maker: the plant's shares counted at full size without a
row being built, the same shares and ranges in rows built at rehearsal size,
and the same rows from the same seed."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import data_missing as dm, run  # noqa: E402

REHEARSAL = 24_000


@pytest.fixture(scope="module")
def plant():
    return dm.plant()


@pytest.fixture(scope="module")
def built():
    return {seed: dm.bosch_like(seed, REHEARSAL) for seed in (0, 7, 2 ** 31 + 5)}


def test_columns_are_dealt_unevenly_to_52_stations_on_4_lines(plant):
    assert len(plant.columns) == dm.STATIONS == 52
    assert plant.columns.sum() == dm.FEATURES == 968
    assert plant.columns.min() >= 1 and plant.columns.max() == 100
    assert len(np.unique(plant.columns)) > 20
    assert np.bincount(dm.LINE_OF_STATION).tolist() == [24, 2, 3, 23]
    assert (np.diff(plant.station) >= 0).all() and plant.station[-1] == 51
    assert int(np.isinf(plant.steps).sum()) == 12
    assert int((plant.steps == 0).sum()) == 120 == len(dm.continuous_columns())
    levels = 6 * plant.steps[np.isfinite(plant.steps) & (plant.steps > 0)] + 1
    assert 3 <= levels.min() < 10 and 200 < levels.max() <= 250


def test_a_part_takes_one_of_a_few_dozen_paths(plant):
    assert plant.passes.shape == (36, 52)
    assert plant.share.sum() == pytest.approx(1.0)
    first = np.array([np.flatnonzero(p)[0] for p in plant.passes])
    line = dm.LINE_OF_STATION[first]
    by_line = np.bincount(line, weights=plant.share, minlength=3)
    assert by_line[0] > by_line[1] > 4 * by_line[2] > 0  # line 2 is rare
    ends_on_3 = plant.passes[:, dm.LINE_OF_STATION == 3].any(axis=1)
    assert plant.share[ends_on_3].sum() > 0.85
    # a path never holds two of the first three lines
    for p in plant.passes:
        assert len(set(dm.LINE_OF_STATION[np.flatnonzero(p)]) - {3}) == 1


def test_the_full_size_shares_counted_from_the_plant(plant):
    cfg = run.load_cell("bosch-d8.train")["config"]["dataset"]
    assert (cfg["rows"], cfg["features"]) == (946_997, 968)
    present = plant.present()
    cells = cfg["rows"] * cfg["features"]
    expected = cfg["rows"] * float(present.sum())  # present entries expected
    assert 0.18 <= expected / cells <= 0.20
    assert 1 - expected / cells == pytest.approx(cfg["missing_share"], abs=0.01)
    assert 0.01 <= present.min() and present.max() <= 0.60
    held = plant.path_columns()
    # a row of a path holds Binomial(columns, 0.98) entries: six deviations
    low = held * 0.98 - 6 * np.sqrt(held * 0.02 * 0.98)
    assert low.min() >= 50 and held.max() <= 450
    often = plant.passed()[plant.station[dm.continuous_columns()]]
    assert often.min() >= dm.CONTINUOUS_LEAST_PASSED
    assert len(set(plant.station[plant.told])) == 6
    assert np.isfinite(plant.steps[plant.told]).all()


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_rows_built_at_rehearsal_size_keep_every_share(built, plant, seed):
    X, y = built[seed]
    assert X.shape == (REHEARSAL, 968) and X.dtype == np.float32
    assert y.dtype == np.float32 and set(np.unique(y)) <= {0.0, 1.0}
    there = ~np.isnan(X)
    assert 0.18 <= there.mean() <= 0.20
    by_column = there.mean(axis=0)
    assert 0.01 <= by_column.min() and by_column.max() <= 0.60
    assert by_column == pytest.approx(plant.present(), abs=0.015)
    by_row = there.sum(axis=1)
    assert 50 <= by_row.min() and by_row.max() <= 450
    assert -1.05 <= np.nanmin(X) and np.nanmax(X) <= 1.05
    # absent in station blocks: a row has all of a station's columns but for
    # the drop-out, or none
    for s in (0, 24, 30, 51):
        block = there[:, plant.first[s]:plant.first[s] + plant.columns[s]]
        share = block.mean(axis=1)
        assert ((share == 0) | (share > 0.7) | (plant.columns[s] < 8)).all()
    distinct = np.array([len(np.unique(X[there[:, f], f])) for f in range(968)])
    assert (distinct[np.isinf(plant.steps)] == 1).all()
    assert (distinct[plant.steps == 0] > 1000).all()
    coarse = np.isfinite(plant.steps) & (plant.steps > 0)
    assert distinct[coarse].min() >= 2 and distinct[coarse].max() <= 250


def test_the_positives_are_0_58_per_cent():
    y = np.concatenate([dm.bosch_like(seed, 200_000)[1] for seed in (11, 12)])
    assert abs(y.mean() - 0.0058) < 0.0005


def test_the_label_follows_values_and_absence_both_ways(built, plant):
    X, y = dm.bosch_like(5, 200_000)
    rate = y.mean()
    a, e = X[:, plant.told[0]], X[:, plant.told[4]]
    # column a: absent stands for a high value; column e: for a low one
    assert y[np.isnan(a)].mean() > 2 * y[a < plant.mu[plant.told[0]]].mean()
    assert y[np.isnan(e)].mean() < 0.5 * y[e > plant.mu[plant.told[4]]].mean()
    went = ~np.isnan(X[:, plant.first[plant.told_stations[0]]:][:, :1]).ravel()
    assert rate > 0 and y[went].mean() != y[~went].mean()


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
def test_the_same_seed_gives_the_same_rows(built, seed):
    X, y = built[seed]
    again_X, again_y = dm.bosch_like(seed, REHEARSAL)
    assert np.array_equal(X, again_X, equal_nan=True)
    assert np.array_equal(y, again_y)
    other_X, _ = built[7]
    assert not np.array_equal(X, other_X, equal_nan=True)
