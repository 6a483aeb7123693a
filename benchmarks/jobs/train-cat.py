"""Job kind ``train-cat``: one ``xtb.train`` call on a resident matrix with
categorical columns, under xgboost's native categorical splits.

The window, its clocks and the allocator's holes are job ``train``'s
(``jobs/train.py``, loaded by its file as the harness loads it); ``setup``
makes airline-shaped rows from the seed (``benchmarks/data_cat.py``) and
builds the ``QuantileDMatrix`` with the configuration's ``feature_types``;
``compare`` holds what the timed call produced against
``benchmarks/reference_cat.py``: the binned page (a code its own bin, past
``max_bin``), the sketch of the numeric columns, every node's sums with the
rows routed by set membership, the split scan with both categorical
branches and ``max_cat_threshold``, the sets the categorical splits chose,
and the margin.
"""
from __future__ import annotations

import importlib.util
import json
import os
import tempfile
import time

import numpy as np

from benchmarks import data_cat, reference, reference_cat

_spec = importlib.util.spec_from_file_location(
    "benchmarks.jobs.train",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py"))
train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train)

MAKERS = {"airline_like": data_cat.airline_like}


def window(st, seconds: float, tracer=None) -> None:
    """Job ``train``'s window, then the categorical counters of the rounds
    the program's span ring holds, to the log."""
    from xgboost_tpu.telemetry.spans import recent

    train.window(st, seconds, tracer)
    rounds = recent("train.round")
    got = {k: sum(r.get(k, 0) for r in rounds)
           for k in ("splits", "splits.categorical", "splits.cat_onehot")}
    st.log(f"categorical splits: {got['splits.categorical']} of "
           f"{got['splits']} splits in the {len(rounds)} rounds the ring "
           f"holds, {got['splits.cat_onehot']} of them one category against "
           f"the rest; the widest categorical column "
           f"{max((r.get('cat.bins_max', 0) for r in rounds), default=0)} "
           f"bins")


def bins_past_max_bin(max_bin: int) -> bool:
    """Whether the program gives a categorical column more categories than
    ``max_bin`` a bin each: a column of ``max_bin + 2`` codes sketched the
    way the matrix is (milliseconds, no data made)."""
    from xgboost_tpu.data import quantile

    codes = np.arange(max_bin + 2, dtype=np.float32)[:, None]
    try:
        cuts = quantile.sketch_dense(codes, max_bin, use_device=False,
                                     cat_mask=np.array([True]))
    except ValueError:
        return False
    return cuts.n_bins(0) == max_bin + 2


def setup(cell: dict, seed: int, env: dict):
    import jax

    import xgboost_tpu as xtb

    cfg, log = cell["config"], env["log"]
    ds, params = cfg["dataset"], cfg["params"]
    if not bins_past_max_bin(int(params["max_bin"])):
        # reached where this cell's files are laid over an older program,
        # which refuses a categorical column of more categories than
        # max_bin (Origin and Dest have about 300): the run ends before any
        # data is made, in the seconds the imports take.
        raise SystemExit(
            "benchmarks/jobs/train-cat.py: this program does not bin a "
            "categorical column of more categories than max_bin "
            f"({params['max_bin']}) a category a bin: it cannot run this "
            "cell")
    rows = int(env.get("rehearse_rows") or ds["rows"])
    held = train.SAMPLE_ROWS if not env.get("rehearse_rows") else max(
        rows // 10, 1)
    st = train.State()
    st.cell, st.seed, st.rows, st.log = cell, int(seed), rows, log
    t0 = time.perf_counter()
    X_all, y_all = MAKERS[ds["maker"]](seed, rows + held)
    st.X, st.y = X_all[:rows], y_all[:rows]
    st.X_held, st.y_held = X_all[rows:], y_all[rows:]
    st.clocks["data_s"] = time.perf_counter() - t0
    log(f"data: {st.X.shape} float32, {st.y.mean():.4f} positive, and "
        f"{held} held-out rows from seed {seed} in "
        f"{st.clocks['data_s']:.2f}s")

    st.params = dict(params)
    if env.get("rehearse_rows"):
        st.params.pop("device", None)  # the program refuses device=tpu here
    st.kept = train.make_holes(cell["traffic"].get("allocator_holes", []), log)
    train.allocator("before QuantileDMatrix", log)
    t0 = time.perf_counter()
    st.dtrain = xtb.QuantileDMatrix(
        st.X, label=st.y, max_bin=int(st.params["max_bin"]),
        feature_types=list(ds["feature_types"]), enable_categorical=True)
    jax.block_until_ready(st.dtrain._ellpack.bins)
    st.clocks["dmatrix_s"] = time.perf_counter() - t0
    page = st.dtrain._ellpack
    log(f"QuantileDMatrix: {st.clocks['dmatrix_s']:.2f}s, bins "
        f"{page.bins.dtype}{tuple(page.bins.shape)}, width {page.bin_width}, "
        f"bins by column {page.cuts.n_bins_array().tolist()}")
    train.allocator("after QuantileDMatrix", log)
    return st


def compare(st, env: dict, lower_precision: bool = False,
            faults: bool = False) -> dict:
    """Numbers for ``correct``, each under the name the limits file uses."""
    log = env["log"]
    t0 = time.perf_counter()
    cfg = st.cell["config"]
    p, g = cfg["params"], cfg["guarantees"]
    cache = st.bst._get_cache(st.dtrain)
    page = st.dtrain._ellpack
    cuts, sentinel = page.cuts, int(page.bin_width)
    bins = np.asarray(cache.bins)[:st.rows]
    margin = np.asarray(cache.margin)[:st.rows, 0]
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "model.json")
        st.bst.save_model(path)
        with open(path) as fh:
            model = json.load(fh)
    idx = np.sort(np.random.default_rng(st.seed + 1).choice(
        st.rows, size=min(train.SAMPLE_ROWS, st.rows), replace=False))
    if int(bins.max()) > sentinel:
        raise SystemExit(f"the binned page holds a symbol above its "
                         f"sentinel {sentinel}")
    page_fr = np.ascontiguousarray(bins.T)
    del bins
    sample_bins = np.take(page_fr, idx, axis=1)
    warm = int(st.cell["traffic"]["warm_rounds"])
    trees = reference_cat.model_trees(model)
    st.failed = sum(not t.finite() for t in trees[warm:])
    if len(trees) != warm + st.attempted:
        raise SystemExit(f"{warm} warm and {st.attempted} window rounds "
                         f"left {len(trees)} trees")
    types = cfg["dataset"]["feature_types"]
    out = reference_cat.compare_training(
        st.X, st.y, model, np.asarray(cuts.cut_ptrs, np.int64),
        np.asarray(cuts.cut_values, np.float32), page_fr, sentinel, idx,
        sample_bins, margin[idx],
        cat_cols=[f for f, t in enumerate(types) if t == "c"],
        numeric_cols=[f for f, t in enumerate(types) if t == "q"],
        max_bin=int(p["max_bin"]), max_depth=int(p["max_depth"]),
        eta=float(p["eta"]), lam=float(g["lambda"]),
        mcw=float(g["min_child_weight"]),
        onehot_max=int(p["max_cat_to_onehot"]),
        threshold=int(p["max_cat_threshold"]),
        base_margin=float(np.log(p["base_score"] / (1.0 - p["base_score"]))),
        follow=train.FOLLOW_TREES, split_tree=warm,
        lower_precision=lower_precision, faults=faults, log=log)
    n_codes = int(np.diff(cuts.cut_ptrs).max())
    aucs = [reference.auc(reference_cat.walk(trees[:n], st.X_held, 0.0,
                                             n_codes), st.y_held)
            for n in (1, 3, len(trees))]
    log(f"held-out AUC on {len(st.X_held)} rows (numpy walk) after 1, 3 and "
        f"all {len(trees)} trees: " + " ".join(f"{a:.4f}" for a in aucs)
        + f"; the comparison took {time.perf_counter() - t0:.1f}s")
    return out
