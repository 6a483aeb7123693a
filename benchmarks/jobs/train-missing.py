"""Job kind ``train-missing``: one ``xtb.train`` call on a resident matrix
most of whose entries are absent (NaN), under ``scale_pos_weight``.

The window, its clocks and the allocator's holes are job ``train``'s
(``jobs/train.py``, loaded by its file as the harness loads it); ``setup``
makes Bosch-shaped rows from the seed (``benchmarks/data_missing.py``), takes
``scale_pos_weight`` from their labels as gbm-bench does (rows over
positives) and builds the ``QuantileDMatrix``; ``compare`` holds what the
timed call produced against ``benchmarks/reference_missing.py``: the binned
page with its sentinel counted, the sketch on the present values of the
continuous columns, every node's sums with the absent rows in them, the
split scan with both directions on offer, the direction each split gave its
absent rows, and the margin with NaN routed by ``default_left``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import tempfile
import time

import numpy as np

from benchmarks import data_missing, reference, reference_missing

_spec = importlib.util.spec_from_file_location(
    "benchmarks.jobs.train",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py"))
train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train)

window = train.window
MAKERS = {"bosch_like": data_missing.bosch_like}


def setup(cell: dict, seed: int, env: dict):
    import jax

    import xgboost_tpu as xtb

    from xgboost_tpu.data import ellpack

    cfg, log = cell["config"], env["log"]
    ds = cfg["dataset"]
    rows = int(env.get("rehearse_rows") or ds["rows"])
    if (rows * int(ds["features"]) > 1 << 29
            and not hasattr(ellpack, "_in_row_blocks")):
        # reached where this cell's files are laid over an older program (a
        # check tries a new cell on the parent commit first): such a program
        # bins the matrix whole, which at this size needs 14.5 GB of
        # temporaries beside the 3.7 GB input and does not compile for the
        # chip (PERF.md section 6, PR 34), after two minutes of set-up.  It
        # ends at once instead.
        raise SystemExit(
            "benchmarks/jobs/train-missing.py: this program has no "
            "xgboost_tpu.data.ellpack._in_row_blocks (binning a block of "
            "rows at a time): it cannot run this cell")
    # of the held-out fifth only what the logged AUC reads is made
    held = min(max(int(ds["rows_held_out"] * rows / ds["rows"]), 1),
               train.SAMPLE_ROWS)
    st = train.State()
    st.cell, st.seed, st.rows, st.log = cell, int(seed), rows, log
    t0 = time.perf_counter()
    X_all, y_all = MAKERS[ds["maker"]](seed, rows + held)
    st.X, st.y = X_all[:rows], y_all[:rows]
    st.X_held, st.y_held = X_all[rows:], y_all[rows:]
    st.clocks["data_s"] = time.perf_counter() - t0
    positives = int(np.count_nonzero(st.y))
    if not positives:
        raise SystemExit(f"seed {seed} made no positive among {rows} rows")
    log(f"data: {st.X.shape} float32 with NaN where a part did not pass, "
        f"{positives} positives ({positives / rows:.4%}), and {held} held-out "
        f"rows from seed {seed} in {st.clocks['data_s']:.2f}s")

    st.params = dict(cfg["params"])
    st.spw = rows / positives  # gbm-bench's configure, from the rows themselves
    st.params["scale_pos_weight"] = st.spw
    if env.get("rehearse_rows"):
        st.params.pop("device", None)  # the program refuses device=tpu here
    st.kept = train.make_holes(cell["traffic"].get("allocator_holes", []), log)
    train.allocator("before QuantileDMatrix", log)
    t0 = time.perf_counter()
    st.dtrain = xtb.QuantileDMatrix(st.X, label=st.y,
                                    max_bin=int(st.params["max_bin"]))
    jax.block_until_ready(st.dtrain._ellpack.bins)
    st.clocks["dmatrix_s"] = time.perf_counter() - t0
    bins = st.dtrain._ellpack.bins
    log(f"QuantileDMatrix: {st.clocks['dmatrix_s']:.2f}s, bins "
        f"{bins.dtype}{tuple(bins.shape)}, scale_pos_weight "
        f"{st.spw:.3f}")
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
    trees = reference_missing.model_trees(model)
    st.failed = sum(not t.finite() for t in trees[warm:])
    if len(trees) != warm + st.attempted:
        raise SystemExit(f"{warm} warm and {st.attempted} window rounds "
                         f"left {len(trees)} trees")
    out = reference_missing.compare_training(
        st.X, st.y, model, np.asarray(cuts.cut_ptrs, np.int64),
        np.asarray(cuts.cut_values, np.float32), page_fr, sentinel, idx,
        sample_bins, margin[idx], max_bin=int(p["max_bin"]),
        max_depth=int(p["max_depth"]), eta=float(p["eta"]),
        lam=float(g["lambda"]), mcw=float(g["min_child_weight"]),
        spw=st.spw,
        base_margin=float(np.log(p["base_score"] / (1.0 - p["base_score"]))),
        follow=train.FOLLOW_TREES, split_tree=warm,
        continuous=data_missing.continuous_columns(),
        lower_precision=lower_precision, faults=faults, log=log)
    aucs = [reference.auc(reference_missing.walk(trees[:n], st.X_held, 0.0),
                          st.y_held) for n in (1, 2, 3, len(trees))]
    log(f"held-out AUC on {len(st.X_held)} rows (numpy walk, NaN by "
        f"default_left) after 1, 2, 3 and all {len(trees)} trees: "
        + " ".join(f"{a:.4f}" for a in aucs)
        + f"; the comparison took {time.perf_counter() - t0:.1f}s")
    return out
