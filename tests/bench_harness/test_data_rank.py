"""``mslr_like`` (benchmarks/data_rank.py): the published shape for any
seed, the same rows for the same seed, whole groups and the full width under
``--rehearse-rows``."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import data_rank  # noqa: E402


@pytest.fixture(scope="module")
def dataset():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mslr-web30k-ndcg.json")) as fh:
        return json.load(fh)["dataset"]


@pytest.mark.parametrize("seed", [0, 7, 2147491001, 3000000019])
def test_group_sizes_are_the_published_ones_for_any_seed(dataset, seed):
    sizes = data_rank.group_sizes(
        dataset["query_groups"], dataset["rows"], dataset["group_size"]["min"],
        dataset["group_size"]["max"], np.random.default_rng(seed))
    assert len(sizes) == 18_919 and sizes.sum() == 2_270_296
    assert sizes.min() == 1 and sizes.max() == 1_251
    assert (sizes == 1).sum() >= 1 and (sizes == 1_251).sum() >= 1
    assert np.median(sizes) < sizes.mean() == pytest.approx(120.0, abs=0.01)
    assert np.quantile(sizes, 0.99) > 4 * sizes.mean()  # heavy-tailed


def test_full_size_has_the_published_shape(dataset):
    X, y, qid, held = data_rank.make(dataset, 3000000019, held_groups=50)
    assert X.shape == (2_270_296, 136) and X.dtype == np.float32
    assert held[0].shape[1] == 136 and len(np.unique(held[2])) == 50
    assert (np.diff(qid) >= 0).all() and qid[-1] == 18_918
    sizes = np.bincount(qid)
    assert sizes.min() == 1 and sizes.max() == 1_251
    share = np.bincount(y.astype(int), minlength=5) / len(y)
    np.testing.assert_allclose(share, data_rank.GRADE_SHARES, atol=0.005)
    distinct = [len(np.unique(X[::5, f])) for f in range(136)]
    counts = [f for f in range(136) if distinct[f] < 256
              and (X[::5, f] == np.floor(X[::5, f])).all()]
    assert len(counts) == data_rank.COUNT_COLUMNS >= 136 // 4
    assert min(distinct[f] for f in range(136) if f not in counts) > 100_000
    for f in range(data_rank.QUERY_COLUMNS):  # the same over a query
        assert (X[1:, f] == X[:-1, f])[qid[1:] == qid[:-1]].all()


@pytest.mark.parametrize("rows", [4000, 20000])
def test_a_rehearsal_keeps_whole_groups_and_the_width(dataset, rows):
    X, y, qid, held = data_rank.make(dataset, 11, rows=rows)
    full = data_rank.group_sizes(18_919, 2_270_296, 1, 1_251,
                                 np.random.default_rng(
                                     np.random.SeedSequence(11).spawn(3)[0]))
    sizes = np.bincount(qid)
    np.testing.assert_array_equal(sizes, full[:len(sizes)])
    assert rows - full[len(sizes)] < len(X) == sizes.sum() <= rows
    assert X.shape[1] == 136 and held is None
    assert set(np.unique(y)) <= {0.0, 1.0, 2.0, 3.0, 4.0}


def test_the_same_seed_gives_the_same_rows_and_another_seed_others(dataset):
    a = data_rank.make(dataset, 5, rows=30000, held_groups=3)
    b = data_rank.make(dataset, 5, rows=30000, held_groups=3)
    c = data_rank.make(dataset, 6, rows=30000, held_groups=3)
    for x, z in zip(a[:3] + a[3], b[:3] + b[3]):
        np.testing.assert_array_equal(x, z)
    assert a[0].shape != c[0].shape or not np.array_equal(a[0], c[0])
