"""Learning-to-rank (reference: tests/python/test_ranking.py,
testing/data.py:813 make_ltr)."""
import numpy as np
import pytest

import xgboost_tpu as xtb
from xgboost_tpu.metric import ndcg
from xgboost_tpu.testing.data import make_ltr


@pytest.fixture(scope="module")
def ltr():
    X, y, qid = make_ltr(40, 30, 8, seed=0)
    return X, y, qid


@pytest.mark.parametrize("obj", ["rank:ndcg", "rank:pairwise", "rank:map"])
@pytest.mark.parametrize("method", ["topk", "mean"])
def test_rank_objectives_improve(ltr, obj, method):
    X, y, qid = ltr
    d = xtb.DMatrix(X, label=y, qid=qid)
    res = {}
    # defaults mirror the reference (ranking_utils.h): topk truncates at
    # k=32, mean samples 1 random different-label pair per doc per round
    xtb.train({"objective": obj, "max_depth": 4, "eta": 0.3,
               "lambdarank_pair_method": method}, d, 20,
              evals=[(d, "t")], evals_result=res, verbose_eval=False)
    metric = list(res["t"].keys())[0]
    vals = res["t"][metric]
    assert np.isfinite(vals).all()
    assert vals[-1] > vals[0]  # ndcg/map are maximized


def test_rank_requires_groups(ltr):
    X, y, _ = ltr
    d = xtb.DMatrix(X, label=y)  # no qid: degenerates to one big group
    bst = xtb.train({"objective": "rank:ndcg", "max_depth": 3}, d, 3,
                    verbose_eval=False)
    assert np.isfinite(bst.predict(d)).all()


def test_ranker_sklearn_with_eval(ltr):
    X, y, qid = ltr
    half = len(y) // 2
    rk = xtb.XGBRanker(n_estimators=10, max_depth=3)
    rk.fit(X[:half], y[:half], qid=qid[:half],
           eval_set=[(X[half:], y[half:])], eval_qid=[qid[half:]])
    assert rk.evals_result_  # eval history recorded
    d = xtb.DMatrix(X, label=y, qid=qid)
    score = ndcg(rk.predict(X), y, group_ptr=d.info.group_ptr)
    assert score > 0.85


def test_ndcg_at_k_metric(ltr):
    X, y, qid = ltr
    d = xtb.DMatrix(X, label=y, qid=qid)
    res = {}
    xtb.train({"objective": "rank:ndcg", "eval_metric": ["ndcg@5", "map@5"],
               "max_depth": 3}, d, 5, evals=[(d, "t")], evals_result=res,
              verbose_eval=False)
    assert "ndcg@5" in res["t"] and "map@5" in res["t"]


def test_metric_name_suffix_parsing():
    """``base[@n][-]`` parsing (reference: ranking_utils.cc:138
    ParseMetricName): truncation + the minus convention for degenerate
    groups (rank_metric.cc:382,:443)."""
    from xgboost_tpu.metric import create_metric

    s = np.array([0.9, 0.1, 0.8, 0.2], np.float64)
    # group 2 has no relevant doc: ndcg scores it 1 by default, 0 with '-'
    y = np.array([2.0, 1.0, 0.0, 0.0], np.float64)
    gp = np.array([0, 2, 4])
    for name, want in [("ndcg@2", 1.0), ("ndcg@2-", 0.5),
                       ("map", 1.0), ("map-", 0.5)]:
        fn, reported = create_metric(name)
        assert reported == name
        got = fn(s, y, None, group_ptr=gp)
        np.testing.assert_allclose(got, want, err_msg=name)

    fn, _ = create_metric("error@0.3")
    assert fn(np.array([0.4, 0.2]), np.array([1.0, 0.0]), None) == 0.0

    with pytest.raises(ValueError, match="Unknown metric"):
        create_metric("nope@2")


def test_aucpr_grouped_ranking_variant():
    """aucpr with query groups = mean of per-group PR areas over valid
    groups (auc.cc ranking Curve path), not one pooled curve."""
    from xgboost_tpu.metric import aucpr

    rng = np.random.default_rng(0)
    n_g, g_sz = 8, 30
    y = (rng.random(n_g * g_sz) < 0.3).astype(np.float64)
    s = y * 0.5 + rng.random(n_g * g_sz) * 0.5  # informative scores
    gp = np.arange(0, n_g * g_sz + 1, g_sz)
    grouped = aucpr(s, y, group_ptr=gp)
    pooled = aucpr(s, y)
    per_group = np.mean([aucpr(s[lo:hi], y[lo:hi])
                         for lo, hi in zip(gp[:-1], gp[1:])])
    np.testing.assert_allclose(grouped, per_group, rtol=1e-12)
    assert grouped != pooled  # actually a different quantity


def test_metric_suffix_validation_and_group_weights():
    from xgboost_tpu.metric import aucpr, create_metric

    # '-' only exists for rank metrics; '@' needs a number
    for bad in ("rmse-", "auc-", "error@0.3-", "ndcg@-"):
        with pytest.raises(ValueError):
            create_metric(bad)

    # grouped aucpr accepts per-group weights (the ndcg/map convention)
    rng = np.random.default_rng(2)
    y = (rng.random(60) < 0.4).astype(np.float64)
    s = y * 0.4 + rng.random(60) * 0.6
    gp = np.array([0, 20, 40, 60])
    wg = np.array([1.0, 2.0, 3.0])
    got = aucpr(s, y, weights=wg, group_ptr=gp)
    per = [aucpr(s[lo:hi], y[lo:hi]) for lo, hi in zip(gp[:-1], gp[1:])]
    np.testing.assert_allclose(got, np.average(per, weights=wg), rtol=1e-12)


def test_device_rank_parity():
    """Segment-vectorized device metrics (metric/device_rank.py) vs the
    python-loop host oracles, including @k and minus variants, group and
    per-row weights, all-irrelevant groups, and a size-1 group."""
    from xgboost_tpu.metric import map_metric, ndcg, precision_at

    rng = np.random.default_rng(5)
    G = 300
    sizes = rng.integers(1, 40, size=G)
    sizes[7] = 1
    ptr = np.concatenate([[0], np.cumsum(sizes)])
    R = ptr[-1]
    preds = rng.normal(size=R).astype(np.float32)
    labels = rng.integers(0, 5, size=R).astype(np.float32)
    labels[ptr[3]:ptr[4]] = 0.0          # all-irrelevant group
    gw = rng.uniform(0.5, 2.0, size=G).astype(np.float32)
    rw = rng.uniform(0.5, 2.0, size=R).astype(np.float32)

    for at in (0, 5):
        for minus in (False, True):
            for w in (None, gw, rw):
                for fn in (ndcg, map_metric):
                    host = fn(preds, labels, weights=w, group_ptr=ptr, at=at,
                              minus=minus, use_device_rank=False)
                    dev = fn(preds, labels, weights=w, group_ptr=ptr, at=at,
                             minus=minus, use_device_rank=True)
                    np.testing.assert_allclose(dev, host, rtol=2e-5,
                                               err_msg=f"{fn.__name__}@{at}"
                                               f" minus={minus}")
    for w in (None, gw, rw):
        host = precision_at(preds, labels, weights=w, group_ptr=ptr, at=7,
                            use_device_rank=False)
        dev = precision_at(preds, labels, weights=w, group_ptr=ptr, at=7,
                           use_device_rank=True)
        np.testing.assert_allclose(dev, host, rtol=2e-5)


def test_device_rank_mslr_scale_speed():
    """VERDICT r4 #6 bar: 30k groups x 100k docs evaluates in < 1 s/round
    once compiled (the python loop takes ~30s+ here)."""
    import time

    from xgboost_tpu.metric import ndcg

    rng = np.random.default_rng(6)
    G = 30_000
    sizes = rng.integers(1, 7, size=G)
    ptr = np.concatenate([[0], np.cumsum(sizes)])
    R = int(ptr[-1])
    preds = rng.normal(size=R).astype(np.float32)
    labels = rng.integers(0, 5, size=R).astype(np.float32)

    v1 = ndcg(preds, labels, group_ptr=ptr, at=10)   # warm-up (compile)
    t0 = time.perf_counter()
    v2 = ndcg(preds, labels, group_ptr=ptr, at=10)
    dt = time.perf_counter() - t0
    assert v1 == v2
    assert 0.0 < v2 <= 1.0
    assert dt < 1.0, f"device ndcg took {dt:.2f}s at MSLR scale"


def test_rank_mean_multi_pair_normalized(ltr):
    """mean method with num_pair > 1: gradients are averaged over the
    sampled pairs (1/n_pairs, lambdarank_obj.cc:230), so more pairs reduce
    sampling noise without inflating the step size — and training still
    improves the metric."""
    X, y, qid = ltr
    d = xtb.DMatrix(X, label=y, qid=qid)
    res = {}
    xtb.train({"objective": "rank:ndcg", "max_depth": 4, "eta": 0.3,
               "lambdarank_pair_method": "mean",
               "lambdarank_num_pair_per_sample": 4}, d, 20,
              evals=[(d, "t")], evals_result=res, verbose_eval=False)
    vals = res["t"]["ndcg"]
    assert np.isfinite(vals).all() and vals[-1] > vals[0]

    # the 1/n_pairs normalization bounds the per-round gradient magnitude:
    # a 4-pair gradient must not be ~4x the 1-pair gradient
    import jax.numpy as jnp

    from xgboost_tpu.objective import create_objective

    ptr = np.concatenate([[0], np.cumsum(np.bincount(qid))])
    g = {}
    for npair in (1, 4):
        obj = create_objective("rank:ndcg", {
            "lambdarank_pair_method": "mean",
            "lambdarank_num_pair_per_sample": npair})
        obj.set_group_info(ptr)
        gp = obj.get_gradient(jnp.zeros(len(y)), jnp.asarray(y), None, 0)
        g[npair] = float(jnp.abs(gp[:, 0, 0]).sum())
    assert g[4] < 2.0 * g[1], g


# ---- the chip's form of the top-k gradient (objective/ranking.py
# _lambda_gradients_topk over make_topk_layout's grid), called with arrays on
# the CPU, against the numpy float64 reference of the benchmark
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RAGGED = [1, 2, 5, 31, 32, 33, 40, 300, 7, 12, 1, 64]  # 1, 2, under k, over k
EQUAL_LABELS = 8  # the group of 7 whose labels are all equal


def ragged_case(scores: str, seed: int = 3):
    rng = np.random.default_rng(seed)
    ptr = np.concatenate([[0], np.cumsum(RAGGED)])
    R = int(ptr[-1])
    y = rng.integers(0, 5, R).astype(np.float32)
    y[ptr[EQUAL_LABELS]:ptr[EQUAL_LABELS + 1]] = 2.0
    pred = {"distinct": rng.normal(size=R),
            "tied": rng.integers(0, 4, R) * 0.25,
            "round0": np.full(R, 0.5)}[scores].astype(np.float32)
    return ptr, y, pred


def loop_layout(group_ptr):
    """make_group_layout as it was: a Python loop over groups."""
    sizes = np.diff(group_ptr)
    G, S = len(sizes), int(sizes.max())
    idx = np.zeros((G, S), np.int32)
    mask = np.zeros((G, S), bool)
    inv = np.zeros(int(group_ptr[-1]), np.int32)
    for g in range(G):
        rows = np.arange(group_ptr[g], group_ptr[g + 1])
        idx[g, :sizes[g]] = rows
        mask[g, :sizes[g]] = True
        inv[rows] = g * S + np.arange(sizes[g])
    return idx, mask, inv


@pytest.mark.parametrize("k", [32, 8])
@pytest.mark.parametrize("scores", ["distinct", "tied", "round0"])
def test_chip_form_of_topk_gradient_matches_the_numpy_reference(scores, k):
    import jax.numpy as jnp

    from benchmarks import reference_rank
    from xgboost_tpu.objective.ranking import (_lambda_gradients_topk,
                                               make_topk_layout)

    ptr, y, pred = ragged_case(scores)
    R, pad = len(y), 20  # the margin is padded past the last group
    g, h = _lambda_gradients_topk(
        jnp.asarray(np.pad(pred, (0, pad))), make_topk_layout(ptr, y, k), k=k,
        ndcg_weight=True, score_norm=True, group_norm=True)
    g, h = np.asarray(g), np.asarray(h)
    assert g.shape == (R + pad,) and not g[R:].any() and not h[R:].any()
    g_ref, h_ref = reference_rank.lambdarank_gpair(pred, y.astype(np.float64),
                                                   ptr, k)
    # float32 rounding of a sum of up to 300 terms, against the largest
    np.testing.assert_allclose(g[:R], g_ref, rtol=0, atol=2e-6 * np.abs(g_ref).max())
    np.testing.assert_allclose(h[:R], h_ref, rtol=0, atol=2e-6 * np.abs(h_ref).max())
    for lone in (0, 10):  # groups of one document have no pair
        assert g[ptr[lone]] == 0 and h[ptr[lone]] == 0
    same = slice(ptr[EQUAL_LABELS], ptr[EQUAL_LABELS + 1])
    assert not g[same].any() and not h[same].any()
    assert np.abs(g_ref).max() > 0


@pytest.mark.parametrize("sizes", [RAGGED, [5], [1, 1, 1], [3, 1251, 2]],
                         ids=["ragged", "one_group", "singletons", "longest"])
def test_group_layout_without_a_loop_equals_the_loop(sizes):
    from xgboost_tpu.objective.ranking import make_group_layout

    ptr = np.concatenate([[0], np.cumsum(sizes)])
    for got, want in zip(make_group_layout(ptr), loop_layout(ptr)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


def test_topk_layout_holds_what_the_labels_fix():
    from xgboost_tpu.objective import ranking

    ptr, y, _ = ragged_case("distinct")
    lay = ranking.make_topk_layout(ptr, np.pad(y, (0, 9)), 32)  # padded labels
    n_blocks, gb, S = lay.gain.shape
    assert S == 300 and n_blocks * gb >= len(RAGGED)
    idx, mask, inv = loop_layout(ptr)
    G = len(RAGGED)
    np.testing.assert_array_equal(lay.slot, inv)
    np.testing.assert_array_equal(lay.gain.reshape(-1, S)[:G],
                                  np.where(mask, 2.0 ** y[idx] - 1, 0))
    plain = ranking.make_topk_layout(ptr, y, 32, exp_gain=False)
    np.testing.assert_array_equal(plain.gain.reshape(-1, S)[:G],
                                  np.where(mask, y[idx], 0))
    np.testing.assert_array_equal(lay.count.reshape(-1)[:G], RAGGED)
    np.testing.assert_array_equal(lay.start.reshape(-1)[:G], ptr[:-1])
    assert not lay.count.reshape(-1)[G:].any()
    for g in range(G):
        gain = np.sort(2.0 ** y[ptr[g]:ptr[g + 1]] - 1)[::-1]
        want = max(np.sum(gain / np.log2(2 + np.arange(len(gain)))), 1e-10)
        assert lay.idcg.reshape(-1)[g] == pytest.approx(want, rel=1e-6)
    # blocks: many short groups share a block, the grid never far over 2^22
    many = np.arange(0, 5000 * 40 + 1, 40)
    wide = ranking.make_topk_layout(many, np.zeros(many[-1], np.float32), 32)
    n_blocks, gb, S = wide.gain.shape
    assert S == 40 and gb * 32 * S <= ranking._PAIR_CELLS_A_BLOCK
    assert 5000 <= n_blocks * gb < 5000 + n_blocks


def test_objective_builds_the_grid_once_and_counts_it(monkeypatch):
    import jax.numpy as jnp

    from xgboost_tpu.objective import create_objective, ranking
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import recent

    monkeypatch.setattr(ranking, "_native_lambdarank_ok", lambda: False)
    built = []
    real = ranking.make_topk_layout
    monkeypatch.setattr(ranking, "make_topk_layout",
                        lambda *a: built.append(a[3]) or real(*a))
    flight.clear()
    ptr, y, pred = ragged_case("distinct")
    labels = jnp.asarray(y)
    obj = create_objective("rank:ndcg", {})
    obj.set_group_info(ptr, labels)
    first = obj.get_gradient(jnp.asarray(pred), labels, None, 0)
    again = obj.get_gradient(jnp.asarray(pred), labels, None, 1)
    assert built == [True] and first.shape == (len(y), 1, 2)
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    (rec,) = recent("objective.group_layout")
    S, G = max(RAGGED), len(RAGGED)
    assert rec["rank.groups"] == G and rec["rank.docs"] == sum(RAGGED)
    assert rec["rank.slots"] == G * S
    assert rec["rank.pair_cells"] == G * 32 * S
    # a caller that gave no labels (or other ones) gets its grid at the
    # first gradient
    other = create_objective("rank:ndcg", {})
    other.set_group_info(ptr)
    late = other.get_gradient(jnp.asarray(pred), labels, None, 0)
    np.testing.assert_array_equal(np.asarray(late), np.asarray(first))
    assert built == [True, True]


def test_rank_ndcg_trains_on_the_chips_form(ltr, monkeypatch):
    """xtb.train through the grid form (the native kernel gated off where
    the objective asks for it) lands where the CPU's native path lands."""
    from xgboost_tpu.objective import ranking

    X, y, qid = ltr
    params = {"objective": "rank:ndcg", "max_depth": 4, "eta": 0.3}

    def margins():
        d = xtb.DMatrix(X, label=y, qid=qid)
        bst = xtb.train(params, d, 5, verbose_eval=False)
        return bst.predict(d, output_margin=True)

    usual = margins()
    monkeypatch.setattr(ranking, "_native_lambdarank_ok", lambda: False)
    grid = margins()
    assert np.isfinite(grid).all()
    np.testing.assert_allclose(grid, usual, rtol=1e-3, atol=1e-4)
