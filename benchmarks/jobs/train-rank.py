"""Job kind ``train-rank``: one ``xtb.train`` call on a resident matrix that
carries query groups (``qid``), under a ranking objective.

The window, its clocks and the allocator's holes are job ``train``'s
(``jobs/train.py``, loaded by its file as the harness loads it); ``setup``
makes documents in ragged query groups from the seed and builds the
``QuantileDMatrix`` with ``qid``; ``compare`` holds what the timed call
produced against ``benchmarks/reference_rank.py``: the pairwise gradient
over the groups in the place of the logistic one, the sketch judged on the
continuous columns, the binning on all.
"""
from __future__ import annotations

import importlib.util
import json
import os
import tempfile
import time

import numpy as np

from benchmarks import data_rank, reference, reference_rank, work_rank

_spec = importlib.util.spec_from_file_location(
    "benchmarks.jobs.train",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py"))
train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train)

window = train.window
HELD_GROUPS = 200  # further queries from the same seed, for the logged NDCG


def setup(cell: dict, seed: int, env: dict):
    import jax

    import xgboost_tpu as xtb

    cfg, log = cell["config"], env["log"]
    st = train.State()
    st.cell, st.seed, st.log = cell, int(seed), log
    t0 = time.perf_counter()
    st.X, st.y, st.qid, st.held = data_rank.make(
        cfg["dataset"], seed, rows=env.get("rehearse_rows"),
        held_groups=HELD_GROUPS)
    st.rows = len(st.X)
    st.clocks["data_s"] = time.perf_counter() - t0
    sizes = np.bincount(st.qid)
    st.group_ptr = np.concatenate([[0], np.cumsum(sizes)])
    st.clocks["rank_docs"] = st.rows
    st.clocks["rank_pairs"] = work_rank.pair_count(
        sizes, int(cfg["guarantees"]["lambdarank_num_pair_per_sample"]))
    log(f"data: {st.X.shape} float32 in {len(sizes)} query groups of "
        f"{sizes.min()} to {sizes.max()} documents, grades "
        + "/".join(f"{s:.1%}" for s in np.bincount(st.y.astype(int)) / st.rows)
        + f", and {len(st.held[1])} held-out documents, from seed {seed} in "
        f"{st.clocks['data_s']:.2f}s")

    st.params = dict(cfg["params"])
    if env.get("rehearse_rows"):
        st.params.pop("device", None)  # the program refuses device=tpu here
    st.kept = train.make_holes(cell["traffic"].get("allocator_holes", []), log)
    train.allocator("before QuantileDMatrix", log)
    t0 = time.perf_counter()
    st.dtrain = xtb.QuantileDMatrix(st.X, label=st.y, qid=st.qid,
                                    max_bin=int(st.params["max_bin"]))
    jax.block_until_ready(st.dtrain._ellpack.bins)
    st.clocks["dmatrix_s"] = time.perf_counter() - t0
    bins = st.dtrain._ellpack.bins
    log(f"QuantileDMatrix: {st.clocks['dmatrix_s']:.2f}s, bins "
        f"{bins.dtype}{tuple(bins.shape)}")
    train.allocator("after QuantileDMatrix", log)
    return st


def compare(st, env: dict, lower_precision: bool = False,
            faults: bool = False) -> dict:
    """Numbers for ``correct``, each under the name the limits file uses."""
    log = env["log"]
    cfg = st.cell["config"]
    p, g = cfg["params"], cfg["guarantees"]
    cache = st.bst._get_cache(st.dtrain)
    cuts = st.dtrain._ellpack.cuts
    page = np.asarray(cache.bins)[:st.rows]
    margin = np.asarray(cache.margin)[:st.rows, 0]
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "model.json")
        st.bst.save_model(path)
        with open(path) as fh:
            model = json.load(fh)
    idx = np.sort(np.random.default_rng(st.seed + 1).choice(
        st.rows, size=min(train.SAMPLE_ROWS, st.rows), replace=False))
    if int(page.max()) > 255:
        raise SystemExit("the binned page holds a symbol above 255 on a "
                         "row that has no missing value")
    sample_bins = page[idx].astype(np.int64)
    page_fr = np.ascontiguousarray(page.T.astype(np.uint8))
    del page
    warm = int(st.cell["traffic"]["warm_rounds"])
    trees = reference.model_trees(model)
    st.failed = sum(not t.finite() for t in trees[warm:])
    if len(trees) != warm + st.attempted:
        raise SystemExit(f"{warm} warm and {st.attempted} window rounds "
                         f"left {len(trees)} trees")
    base = float(p["base_score"])  # a ranking margin has no link
    out = reference_rank.compare_ranking(
        st.X, st.y, st.group_ptr, model, np.asarray(cuts.cut_ptrs, np.int64),
        np.asarray(cuts.cut_values, np.float32), page_fr, idx, sample_bins,
        margin[idx], max_bin=int(p["max_bin"]), max_depth=int(p["max_depth"]),
        eta=float(p["eta"]), lam=float(g["lambda"]),
        mcw=float(g["min_child_weight"]), base_margin=base,
        k=int(g["lambdarank_num_pair_per_sample"]),
        follow=train.FOLLOW_TREES, split_tree=warm,
        continuous=np.arange(data_rank.COUNT_COLUMNS, st.X.shape[1]),
        lower_precision=lower_precision, faults=faults, log=log)
    Xh, yh, qh = st.held
    held_ptr = np.concatenate([[0], np.cumsum(np.bincount(qh - qh[0]))])
    log(f"held-out NDCG@10 over {len(held_ptr) - 1} queries (numpy walk) "
        "after 0, 1, 2 and all " + f"{len(trees)} trees: " + " ".join(
            f"{reference_rank.ndcg_at(reference.walk(trees[:n], Xh, base), yh, held_ptr):.4f}"
            for n in (0, 1, 2, len(trees))))
    return out
