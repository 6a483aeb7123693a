"""Global best-first (lossguide) growth — tree/bestfirst.py
(reference: src/tree/driver.h priority queue; round-1 verdict Weak #10:
per-level budget approximation + depth-10 heap cap)."""
import math

import numpy as np
import pytest

import xgboost_tpu as xtb


def _skewed_data(n=4000, seed=0):
    """Data that rewards a deep chain on one feature: best-first should
    follow the gain, not the level structure."""
    rng = np.random.default_rng(seed)
    X = rng.uniform(0, 1, size=(n, 4)).astype(np.float32)
    # piecewise-constant staircase in x0 with many steps -> deep chain
    y = np.floor(X[:, 0] * 40).astype(np.float32)
    return X, y


def test_bestfirst_exceeds_depth_ten():
    """With max_depth=0 (unbounded) and a leaf budget, lossguide can grow
    past the round-1 heap cap of 10 levels."""
    X, y = _skewed_data()
    bst = xtb.train({"objective": "reg:squarederror", "max_depth": 0,
                     "grow_policy": "lossguide", "max_leaves": 40,
                     "eta": 1.0, "max_bin": 64},
                    xtb.DMatrix(X, label=y), 1, verbose_eval=False)
    t = bst.trees[0]
    assert t.num_leaves <= 40
    assert t.max_depth > 10, t.max_depth  # impossible in the heap layout
    # and it actually fits the staircase
    p = bst.predict(xtb.DMatrix(X))
    assert np.mean((p - y) ** 2) < np.var(y) * 0.05


def test_bestfirst_budget_and_quality():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(3000, 8)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(np.float32)
    d = xtb.DMatrix(X, label=y)
    res = {}
    bst = xtb.train({"objective": "binary:logistic", "grow_policy": "lossguide",
                     "max_leaves": 16, "max_depth": 0, "eta": 0.3,
                     "eval_metric": "logloss"},
                    d, 10, evals=[(d, "t")], evals_result=res,
                    verbose_eval=False)
    for t in bst.trees:
        assert t.num_leaves <= 16
    assert res["t"]["logloss"][-1] < res["t"]["logloss"][0]


def test_bestfirst_respects_max_depth():
    X, y = _skewed_data(seed=2)
    bst = xtb.train({"objective": "reg:squarederror", "max_depth": 4,
                     "grow_policy": "lossguide", "max_leaves": 64,
                     "max_bin": 64},
                    xtb.DMatrix(X, label=y), 1, verbose_eval=False)
    assert bst.trees[0].max_depth <= 4


def test_bestfirst_matches_depthwise_on_balanced_data():
    """With a generous budget, best-first should reach the quality of
    depthwise on data with no depth skew."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(2000, 6)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] ** 2 > 0.5).astype(np.float32)
    d1 = xtb.DMatrix(X, label=y)
    d2 = xtb.DMatrix(X, label=y)
    b_dw = xtb.train({"objective": "binary:logistic", "max_depth": 5,
                      "eta": 0.3}, d1, 8, verbose_eval=False)
    b_bf = xtb.train({"objective": "binary:logistic", "grow_policy":
                      "lossguide", "max_leaves": 32, "max_depth": 0,
                      "eta": 0.3}, d2, 8, verbose_eval=False)
    p1 = b_dw.predict(d1)
    p2 = b_bf.predict(d2)
    ll1 = -np.mean(y * np.log(np.clip(p1, 1e-7, 1))
                   + (1 - y) * np.log(np.clip(1 - p1, 1e-7, 1)))
    ll2 = -np.mean(y * np.log(np.clip(p2, 1e-7, 1))
                   + (1 - y) * np.log(np.clip(1 - p2, 1e-7, 1)))
    assert ll2 < ll1 * 1.25, (ll1, ll2)


def test_bestfirst_save_load_and_adaptive():
    """Serialization round-trip + adaptive (quantile) leaves on the
    best-first path."""
    X, y = _skewed_data(n=1500, seed=4)
    d = xtb.DMatrix(X, label=y)
    bst = xtb.train({"objective": "reg:quantileerror", "quantile_alpha": 0.5,
                     "grow_policy": "lossguide", "max_leaves": 12,
                     "max_depth": 0, "max_bin": 64},
                    d, 4, verbose_eval=False)
    p = bst.predict(d)
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        fn = td + "/bf.json"
        bst.save_model(fn)
        b2 = xtb.Booster()
        b2.load_model(fn)
        np.testing.assert_array_equal(b2.predict(xtb.DMatrix(X)), p)


def test_lossguide_distributed_global_bestfirst(eight_devices):
    """Global best-first lossguide under an 8-device mesh (GSPMD hist psum)
    and 2-process parallelism (host AllReduceHist per expansion): the
    driver queue is GLOBAL across shards (driver.h:30), growth is
    deterministic per configuration, ranks agree bitwise, and model quality
    matches single-device.  (Cross-configuration bitwise identity is not
    promised — f32 reduction grouping differs by device count, as in the
    reference's single- vs multi-GPU models.)"""
    import threading

    from xgboost_tpu import collective
    from xgboost_tpu.metric import logloss
    from xgboost_tpu.testing.data import make_binary

    X, y = make_binary(2048, 6, seed=3)
    params = {"objective": "binary:logistic", "grow_policy": "lossguide",
              "max_leaves": 24, "max_depth": 0, "eta": 0.4, "max_bin": 32}

    b1 = xtb.train(params, xtb.DMatrix(X, label=y), 3, verbose_eval=False)
    ll1 = logloss(b1.predict(xtb.DMatrix(X)), y)
    # single-device lossguide really is best-first: some tree goes deeper
    # than balanced log2(max_leaves) growth would
    assert any(t.max_depth > 5 for t in b1.trees)

    # 8-device mesh: deterministic (two identical runs) + same quality
    b8a = xtb.train({**params, "n_devices": 8}, xtb.DMatrix(X, label=y), 3,
                    verbose_eval=False)
    b8b = xtb.train({**params, "n_devices": 8}, xtb.DMatrix(X, label=y), 3,
                    verbose_eval=False)
    d8a = "".join(b8a.get_dump(dump_format="json"))
    assert d8a == "".join(b8b.get_dump(dump_format="json"))
    assert any(t.max_depth > 5 for t in b8a.trees)  # unbounded depth
    ll8 = logloss(b8a.predict(xtb.DMatrix(X)), y)
    assert abs(ll8 - ll1) < 0.02, (ll8, ll1)

    # 2 processes (in-memory thread backend), disjoint contiguous shards
    results, errors = {}, {}

    def worker(rank):
        try:
            with collective.CommunicatorContext(
                    dmlc_communicator="in-memory", in_memory_world_size=2,
                    in_memory_rank=rank, in_memory_group="bf2"):
                _grp = collective._TLS.backend._group
                lo, hi = (0, 1024) if rank == 0 else (1024, 2048)
                d = xtb.DMatrix(X[lo:hi], label=y[lo:hi])
                b = xtb.train(params, d, 3, verbose_eval=False)
                results[rank] = ("".join(b.get_dump(dump_format="json")),
                                 bytes(b.save_raw()))
        except Exception as e:  # noqa: BLE001
            errors[rank] = e
            try:
                _grp.barrier.abort()
            except Exception:
                pass

    threads = [threading.Thread(target=worker, args=(r,), daemon=True)
               for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads), "worker deadlocked"
    assert not errors, errors
    assert results[0] == results[1]  # ranks bitwise-identical
    b2 = xtb.Booster()
    b2.load_model(results[0][1])
    assert any(t.max_depth > 5 for t in b2.trees)
    ll2 = logloss(b2.predict(xtb.DMatrix(X)), y)
    assert abs(ll2 - ll1) < 0.02, (ll2, ll1)


def test_lossguide_distributed_adaptive_leaves_rank_identical():
    """Adaptive-leaf refit (UpdateTreeLeaf) under process parallelism must
    quantile the GLOBAL leaf population — ranks would otherwise refit from
    their local shards and diverge."""
    import threading

    from xgboost_tpu import collective
    from xgboost_tpu.testing.data import make_binary

    X, y01 = make_binary(1024, 5, seed=9)
    rng = np.random.default_rng(9)
    y = (X[:, 0] + 0.3 * rng.normal(size=len(X))).astype(np.float32)
    params = {"objective": "reg:absoluteerror", "grow_policy": "lossguide",
              "max_leaves": 8, "max_depth": 0, "eta": 0.5, "max_bin": 32}

    results, errors = {}, {}

    def worker(rank):
        try:
            with collective.CommunicatorContext(
                    dmlc_communicator="in-memory", in_memory_world_size=2,
                    in_memory_rank=rank, in_memory_group="bfad"):
                _grp = collective._TLS.backend._group
                lo, hi = (0, 512) if rank == 0 else (512, 1024)
                d = xtb.DMatrix(X[lo:hi], label=y[lo:hi])
                b = xtb.train(params, d, 2, verbose_eval=False)
                results[rank] = bytes(b.save_raw())
        except Exception as e:  # noqa: BLE001
            errors[rank] = e
            try:
                _grp.barrier.abort()
            except Exception:
                pass

    threads = [threading.Thread(target=worker, args=(r,), daemon=True)
               for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads), "worker deadlocked"
    assert not errors, errors
    assert results[0] == results[1]


# ---- the pass: evaluate ahead, commit in order (PR 32) --------------------
def _page(rows=20000, F=6, seed=0, max_bin=64):
    """A binned page and a gradient pair with hessians of their own, as the
    grower takes them, and as benchmarks/reference_bestfirst.py does."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, F)).astype(np.float32)
    s = X[:, 0] * X[:, 1] + np.sin(3 * X[:, 2]) + 0.5 * rng.normal(size=rows)
    g = (1 / (1 + np.exp(-0.5 * rng.normal(size=rows))) - (s > 0)).astype(
        np.float32)
    h = np.maximum(np.abs(g) * (1 - np.abs(g)), 1e-3).astype(np.float32)
    ell = xtb.QuantileDMatrix(X, label=(s > 0).astype(np.float32),
                              max_bin=max_bin)._ellpack
    R = ell.bins.shape[0]
    gpair = np.zeros((R, 2), np.float32)
    gpair[:rows, 0], gpair[:rows, 1] = g, h
    return dict(ell=ell, rows=rows, g=g, h=h, gpair=jnp.asarray(gpair),
                valid=jnp.asarray(np.arange(R) < rows))


@pytest.fixture(scope="module")
def page():
    return _page()


def _grow(page, monkeypatch, *, pairs, max_leaves, max_depth=0, mcw=1.0,
          gamma=0.0, list_share=None, **grower_kw):
    """``list_share``: under the chip's histogram (the one-hot matmul, whose
    pass scans a list of rows), with ``_LIST_SHARE`` forced to it ("const":
    left as it is); None: the CPU backend's kernels, every pass the page."""
    from xgboost_tpu.ops.split import SplitParams
    from xgboost_tpu.tree import bestfirst

    monkeypatch.setattr(bestfirst, "_PAIRS", pairs)
    if list_share is not None:
        monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
        if list_share != "const":
            monkeypatch.setattr(bestfirst, "_LIST_SHARE", list_share)
    params = SplitParams(eta=0.1, gamma=gamma, min_child_weight=mcw,
                         lambda_=1.0, alpha=0.0, max_delta_step=0.0)
    grower = bestfirst.BestFirstGrower(max_depth, params,
                                       max_leaves=max_leaves, **grower_kw)
    ell = page["ell"]
    grown = grower.grow(ell.bins, page["gpair"], page["valid"], ell.cuts_pad,
                        ell.n_bins)
    return grower.to_regtree(grown, ell.cuts_pad)[0], grown


def _serial(page, **kw):
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import reference_bestfirst

    ell = page["ell"]
    bins_fr = np.ascontiguousarray(np.asarray(ell.bins)[:page["rows"]].T)
    return reference_bestfirst.grow_serial(
        bins_fr, page["g"].astype(np.float64), page["h"].astype(np.float64),
        np.asarray(ell.n_bins, np.int64), lam=1.0, **kw)


def _same_structure(tree, ref):
    inner = np.asarray(ref.left) >= 0
    return (tree.n_nodes == len(ref.left)
            and np.array_equal(tree.left_children, ref.left)
            and np.array_equal(tree.right_children, ref.right)
            and np.array_equal(tree.parents, ref.parent)
            and np.array_equal(tree.split_indices[inner],
                               np.asarray(ref.feat)[inner])
            and np.array_equal(tree.split_bins[inner],
                               np.asarray(ref.bin)[inner]))


@pytest.mark.parametrize("pairs", [1, 4, 16])
@pytest.mark.parametrize("max_depth,mcw,gamma", [
    (0, 1.0, 0.0), (0, 100.0, 0.0), (5, 1.0, 0.0), (5, 100.0, 0.0),
    (0, 1.0, 30.0)], ids=["plain", "mcw100", "depth5", "depth5-mcw100",
                          "gamma30"])
@pytest.mark.parametrize("max_leaves", [2, 3, 31, 255])
def test_the_tree_is_the_serial_drivers(page, monkeypatch, max_leaves,
                                        max_depth, mcw, gamma, pairs):
    """Structure-equal to the float64 serial driver of the reference (pop
    the best open leaf, split, evaluate, push), whatever the budget, the
    depth bound, min_child_weight, a gamma that stops growth early, and the
    number of pairs a pass evaluates."""
    tree, _ = _grow(page, monkeypatch, pairs=pairs, max_leaves=max_leaves,
                    max_depth=max_depth, mcw=mcw, gamma=gamma)
    ref = _serial(page, max_leaves=max_leaves, max_depth=max_depth, mcw=mcw,
                  gamma=gamma)
    assert _same_structure(tree, ref), (tree.n_nodes, len(ref.left))
    if gamma and max_leaves == 255:
        assert tree.num_leaves < max_leaves  # gamma stopped it, not the budget


@pytest.mark.parametrize("list_share", [0.0, 1.0, "const"],
                         ids=["never", "always", "const"])
@pytest.mark.parametrize("pairs", [4, 16])
@pytest.mark.parametrize("max_depth,mcw,gamma", [
    (0, 1.0, 0.0), (0, 100.0, 0.0), (5, 1.0, 0.0), (5, 100.0, 0.0),
    (0, 1.0, 30.0)], ids=["plain", "mcw100", "depth5", "depth5-mcw100",
                          "gamma30"])
@pytest.mark.parametrize("max_leaves", [2, 3, 31, 255])
def test_the_tree_is_the_serial_drivers_whatever_a_pass_scans(
        page, monkeypatch, max_leaves, max_depth, mcw, gamma, pairs,
        list_share):
    """The same, under the chip's histogram, whose pass scans the list of
    its built children's rows where they are at most ``_LIST_SHARE`` of the
    page: no pass taking the list (0: but the empty ones), every pass (1:
    the root's too), and the constant as it stands."""
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import recent

    flight.clear()
    tree, _ = _grow(page, monkeypatch, pairs=pairs, max_leaves=max_leaves,
                    max_depth=max_depth, mcw=mcw, gamma=gamma,
                    list_share=list_share)
    ref = _serial(page, max_leaves=max_leaves, max_depth=max_depth, mcw=mcw,
                  gamma=gamma)
    assert _same_structure(tree, ref), (tree.n_nodes, len(ref.left))
    passes = recent("grow.bestfirst_pass")
    rows = passes[0]["rows"]
    assert passes[0]["scanned"] == rows  # the root's: every chunk, or the page
    for p in passes[1:]:
        if list_share == 1.0 or not p["pairs"]:
            assert p["scanned"] < rows and p["scanned"] % 2048 == 0
        elif list_share == 0.0:
            assert p["scanned"] == rows
        if not p["pairs"]:
            assert p["scanned"] == 0  # an empty list runs no chunk
    if list_share == "const" and max_leaves == 255 and not (
            gamma or max_depth):
        took = [p["scanned"] < rows for p in passes[1:]]
        assert any(took) and not all(took)


def test_the_chips_route_and_lookup_give_the_same_tree(page, monkeypatch):
    """Under the device's branches (the one-hot matmul, so the dense
    packed-table route and the select in ``_lookup``) the tree and every
    row's leaf are what the CPU's forms give."""
    tree, grown = _grow(page, monkeypatch, pairs=4, max_leaves=31)
    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    monkeypatch.setenv("XTB_NO_NATIVE_SPLIT", "1")
    import jax

    jax.clear_caches()
    try:
        dense, grown_dense = _grow(page, monkeypatch, pairs=4, max_leaves=31)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert np.array_equal(dense.left_children, tree.left_children)
    assert np.array_equal(dense.split_indices, tree.split_indices)
    assert np.array_equal(dense.split_bins, tree.split_bins)
    assert np.array_equal(np.asarray(grown_dense.pos), np.asarray(grown.pos))


def test_every_row_ends_on_its_leaf_in_pop_order(page, monkeypatch):
    """Rows below a leaf whose evaluated split was never committed go back
    to the leaf: ``pos`` agrees with a walk of the finished tree."""
    tree, grown = _grow(page, monkeypatch, pairs=16, max_leaves=31)
    bins = np.asarray(page["ell"].bins)[:page["rows"]]
    node = np.zeros(page["rows"], np.int64)
    for _ in range(tree.max_depth):
        inner = tree.left_children[node] >= 0
        go_left = bins[np.arange(len(node)), tree.split_indices[node]] \
            <= tree.split_bins[node]
        kid = np.where(go_left, tree.left_children[node],
                       tree.right_children[node])
        node = np.where(inner, kid, node)
    pos = np.asarray(grown.pos)
    assert np.array_equal(pos[:page["rows"]], node)
    assert (pos[page["rows"]:] == -1).all()
    assert (tree.left_children[node] == -1).all()


def test_a_pass_commits_and_the_round_span_counts(monkeypatch):
    """Spans and counters: one ``grow.bestfirst_pass`` a pass with what it
    evaluated and committed, the sums on the round's span; a tree takes
    its depth and one in passes at the least and fewer than it has splits;
    passes that do nothing come last, and only to fill the schedule of a tree
    that spent its budget (``bestfirst._SPARE``)."""
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import recent
    from xgboost_tpu.tree import bestfirst

    flight.clear()
    rng = np.random.default_rng(5)
    X = rng.normal(size=(4000, 5)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(np.float32)
    d = xtb.QuantileDMatrix(X, label=y, max_bin=32)
    bst = xtb.train({"objective": "binary:logistic", "max_bin": 32,
                     "grow_policy": "lossguide", "max_leaves": 24,
                     "max_depth": 0}, d, 2, verbose_eval=False)
    rounds = recent("train.round")
    passes = recent("grow.bestfirst_pass")
    assert len(rounds) == 2
    for r, tree in zip(rounds, bst.trees):
        mine = [p for p in passes if p["round"] == r["round"]]
        # (a tree short of its budget was sent one pass beyond its last read)
        assert tree.max_depth + 1 <= len(mine) < tree.num_leaves - 1
        assert r["bestfirst.passes"] == len(mine) + (tree.num_leaves < 24)
        idle = [not (p["pairs"] or p["committed"]) for p in mine[1:]]
        assert idle == sorted(idle)
        if any(idle):
            assert tree.num_leaves == 24 and len(mine) == math.ceil(
                bestfirst._least_passes(24, 23) * (1 + bestfirst._SPARE)) == 9
        assert r["bestfirst.pairs_committed"] == tree.num_leaves - 1 \
            == sum(p["committed"] for p in mine)
        assert r["bestfirst.pairs_evaluated"] == sum(p["pairs"] for p in mine) \
            >= r["bestfirst.pairs_committed"]
        assert r["bestfirst.hist_rows"] == r["bestfirst.passes"] * mine[0]["rows"] \
            == sum(p["scanned"] for p in mine) + (
                tree.num_leaves < 24) * mine[0]["rows"]
        assert r["bestfirst.listed_passes"] == 0  # the CPU's kernels: the page
        assert mine[0]["pairs"] == 0 and mine[0]["committed"] == 0  # the root
        assert all(p["width"] == 2 * 23 for p in mine)  # a pair a split
    # the one read a pass is inside its span; _finish is waited for once
    waits = recent("grow.wait_device")
    inside = [w for w in waits if w.get("parent") == "grow.bestfirst_pass"]
    # (and the pass sent ahead of a tree that stopped short is read after)
    short = sum(t.num_leaves < 24 for t in bst.trees)
    assert len(inside) == len(passes) and len(waits) == len(inside) + 2 + short
    copies = recent("grow.to_host")
    assert len(copies) == 2 and all(c["copies"] == 13 for c in copies)


def test_a_tree_that_stops_early_costs_the_passes_it_used(monkeypatch):
    """Where ``gamma`` ends growth short of the budget the program runs the
    passes that the tree needed and the one sent ahead of the last read, not
    a schedule's."""
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import recent
    from xgboost_tpu.tree import bestfirst

    flight.clear()
    rng = np.random.default_rng(5)
    X = rng.normal(size=(4000, 5)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(np.float32)
    d = xtb.QuantileDMatrix(X, label=y, max_bin=32)
    real, runs = bestfirst.level_step_bestfirst, []
    monkeypatch.setattr(
        bestfirst, "level_step_bestfirst",
        lambda *a, **kw: runs.append(1) or real(*a, **kw))
    bst = xtb.train({"objective": "binary:logistic", "max_bin": 32,
                     "grow_policy": "lossguide", "max_leaves": 255,
                     "max_depth": 0, "gamma": 60.0}, d, 1, verbose_eval=False)
    (tree,), (r,) = bst.trees, recent("train.round")
    assert 1 < tree.num_leaves < 20
    assert tree.max_depth + 1 <= r["bestfirst.passes"] - 1 <= tree.num_leaves
    assert r["bestfirst.passes"] == len(runs) == len(
        recent("grow.bestfirst_pass")) + 1
    assert r["bestfirst.hist_rows"] == len(runs) * recent(
        "grow.bestfirst_pass")[0]["rows"]


def test_a_listed_pass_costs_its_rows_and_an_empty_one_none(monkeypatch):
    """Under the chip's histogram ``hist_rows`` is what the passes scanned:
    the page in the root's pass, whole chunks of the list in a pass whose
    built children hold at most ``_LIST_SHARE`` of the rows, nothing in the
    passes that fill the schedule of a finished tree; ``listed_passes``
    counts the passes that took the list, and the four integers of a pass
    are still one read."""
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import recent
    from xgboost_tpu.tree import bestfirst

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    monkeypatch.setattr(bestfirst, "_LIST_SHARE", 0.25)
    flight.clear()
    rng = np.random.default_rng(5)
    X = rng.normal(size=(4000, 5)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(np.float32)
    d = xtb.QuantileDMatrix(X, label=y, max_bin=32)
    bst = xtb.train({"objective": "binary:logistic", "max_bin": 32,
                     "grow_policy": "lossguide", "max_leaves": 24,
                     "max_depth": 0}, d, 2, verbose_eval=False)
    passes = recent("grow.bestfirst_pass")
    seen = 0
    for r, tree in zip(recent("train.round"), bst.trees):
        mine = [p for p in passes if p["round"] == r["round"]]
        rows = mine[0]["rows"]
        assert mine[0]["scanned"] == rows
        for p in mine[1:]:
            assert p["scanned"] == rows or (
                p["scanned"] % 2048 == 0 and p["scanned"] <= rows // 4 + 2047)
            assert (p["scanned"] == 0) == (p["pairs"] == 0)
        short = tree.num_leaves < 24  # one pass sent ahead, read after: empty
        assert r["bestfirst.hist_rows"] == sum(p["scanned"] for p in mine)
        assert r["bestfirst.listed_passes"] == short + sum(
            p["scanned"] < rows for p in mine) > 0
        assert r["bestfirst.hist_rows"] < r["bestfirst.passes"] * rows
        seen += tree.num_leaves == 24 and not mine[-1]["pairs"]
    assert seen  # a tree that spent its budget early: empty passes were run
    inside = [w for w in recent("grow.wait_device")
              if w.get("parent") == "grow.bestfirst_pass"]
    assert len(inside) == len(passes)


def test_across_processes_each_lists_its_own_rows(monkeypatch):
    """Rows in two processes under the chip's histogram: each lists the rows
    of the built children that it holds (``_expand_alone``) and the
    allreduced histograms are the ones the two pages scanned whole give, so
    both ranks grow the trees they grow with no pass taking the list."""
    import threading

    from xgboost_tpu import collective
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import recent
    from xgboost_tpu.testing.data import make_binary
    from xgboost_tpu.tree import bestfirst

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    X, y = make_binary(6000, 5, seed=4)
    params = {"objective": "binary:logistic", "grow_policy": "lossguide",
              "max_leaves": 12, "max_depth": 0, "eta": 0.4, "max_bin": 16}

    def two_ranks(group):
        results, errors = {}, {}

        def worker(rank):
            try:
                with collective.CommunicatorContext(
                        dmlc_communicator="in-memory", in_memory_world_size=2,
                        in_memory_rank=rank, in_memory_group=group):
                    members = collective._TLS.backend._group
                    lo, hi = (0, 2500) if rank == 0 else (2500, 6000)
                    b = xtb.train(params, xtb.DMatrix(X[lo:hi], label=y[lo:hi]),
                                  2, verbose_eval=False)
                    # (a leaf's value moves in its last bits with the rows
                    # that share a chunk: the trees' shape is what is held)
                    results[rank] = [
                        (t.left_children.tolist(), t.split_indices.tolist(),
                         t.split_bins.tolist()) for t in b.trees]
            except Exception as e:  # noqa: BLE001
                errors[rank] = e
                members.barrier.abort()

        threads = [threading.Thread(target=worker, args=(r,), daemon=True)
                   for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads), "worker deadlocked"
        assert not errors, errors
        assert results[0] == results[1]
        return results[0]

    flight.clear()
    listed = two_ranks("bflist")
    took = [r["bestfirst.listed_passes"] for r in recent("train.round")]
    assert len(took) == 4 and min(took) > 3  # a rank a round: past the empty ones
    monkeypatch.setattr(bestfirst, "_LIST_SHARE", 0.0)
    assert two_ranks("bfpage") == listed


@pytest.mark.parametrize("params", [
    {"colsample_bynode": 0.5}, {"colsample_bylevel": 0.6},
    {"monotone_constraints": "(1,0,-1,0,0)"},
    {"interaction_constraints": [[0, 1], [2, 3, 4]]}],
    ids=["bynode", "bylevel", "monotone", "interaction"])
def test_options_ride_the_one_loop_whatever_a_pass_holds(monkeypatch, params):
    """Column draws (keyed by a node's way down from the root), monotone
    bounds and interaction sets give one model whether a pass evaluates one
    pair or sixteen."""
    from xgboost_tpu.tree import bestfirst

    rng = np.random.default_rng(11)
    X = rng.normal(size=(3000, 5)).astype(np.float32)
    y = (X[:, 0] - X[:, 2] + X[:, 1] * X[:, 3] > 0).astype(np.float32)
    dumps = []
    for pairs in (1, 16):
        monkeypatch.setattr(bestfirst, "_PAIRS", pairs)
        bst = xtb.train({"objective": "binary:logistic", "max_bin": 32,
                         "grow_policy": "lossguide", "max_leaves": 20,
                         "max_depth": 0, "seed": 3, **params},
                        xtb.DMatrix(X, label=y), 2, verbose_eval=False)
        dumps.append("".join(bst.get_dump(dump_format="json")))
    assert dumps[0] == dumps[1]
