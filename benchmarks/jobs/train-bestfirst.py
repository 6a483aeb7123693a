"""Job kind ``train-bestfirst``: one ``xtb.train`` call on a resident matrix
under ``grow_policy=lossguide`` with a leaf budget.

Set-up, the window, its clocks and the allocator's holes are job
``train``'s (``jobs/train.py``, loaded by its file as the harness loads it).
``window`` adds, in a traced run and after the window has closed, the bytes
that the traced rounds' own trees had to read (``work_bestfirst.py``), for
``expand_roofline``; ``compare`` holds what the timed call produced against
``benchmarks/reference_bestfirst.py``: the sums, the leaves and the margin by
``reference.py``'s measures, the splits down to the tree's own depth, and
whether the leaves that were split are the ones the serial driver splits.
"""
from __future__ import annotations

import importlib.util
import json
import os
import tempfile

import numpy as np

from benchmarks import reference, reference_bestfirst, work_bestfirst

_spec = importlib.util.spec_from_file_location(
    "benchmarks.jobs.train",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py"))
train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train)


def setup(cell: dict, seed: int, env: dict):
    from xgboost_tpu.tree import bestfirst

    if not hasattr(bestfirst, "level_step_bestfirst"):
        # reached where this cell's files are laid over an older program (a
        # check tries a new cell on the parent commit first, with the
        # benchmark as the change leaves it): such a program grows the tree
        # in max_leaves - 1 expansions, each a pass over all rows (69.5 s a
        # tree at this size, PERF.md section 6), so two warm rounds and a
        # window would run for minutes.  It ends at once instead.
        raise SystemExit(
            "benchmarks/jobs/train-bestfirst.py: this program has no "
            "xgboost_tpu.tree.bestfirst.level_step_bestfirst (the best-first "
            "pass): it cannot run this cell")
    return train.setup(cell, seed, env)


def _model(st) -> dict:
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "model.json")
        st.bst.save_model(path)
        with open(path) as fh:
            return json.load(fh)


def window(st, seconds: float, tracer=None) -> None:
    train.window(st, seconds, tracer)
    if tracer is None:
        return
    warm = int(st.cell["traffic"]["warm_rounds"])
    traced = len(st.clocks["traced_round_s"])
    walker = reference.Walker(st.X)
    smaller = [work_bestfirst.smaller_child_rows(t, walker.leaves(t))
               for t in reference.model_trees(_model(st))[warm:warm + traced]]
    st.clocks["bestfirst_smaller_rows"] = smaller
    st.log(f"rows of the smaller child, summed over the splits of each "
           f"traced round's tree: {smaller} (x rows: "
           + " ".join(f"{s / st.rows:.3f}" for s in smaller) + ")")


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
    model = _model(st)
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
    log("leaves and depth of the trees: " + " ".join(
        f"{int((~t.inner).sum())}/{int(t.depth.max())}" for t in trees))
    out = reference_bestfirst.compare_bestfirst(
        st.X, st.y, model, np.asarray(cuts.cut_ptrs, np.int64),
        np.asarray(cuts.cut_values, np.float32), page_fr, idx, sample_bins,
        margin[idx], max_bin=int(p["max_bin"]),
        max_leaves=int(p["max_leaves"]), max_depth=int(p["max_depth"]),
        eta=float(p["eta"]), lam=float(g["lambda"]),
        mcw=float(g["min_child_weight"]), gamma=float(g["gamma"]),
        base_margin=float(np.log(p["base_score"] / (1.0 - p["base_score"]))),
        follow=train.FOLLOW_TREES, split_tree=warm,
        lower_precision=lower_precision, faults=faults, log=log)
    held = reference.walk(trees, st.X_held[:train.SAMPLE_ROWS], 0.0)
    log(f"held-out AUC of the {len(trees)} trees on {len(held)} rows "
        f"(numpy walk): "
        f"{reference.auc(held, st.y_held[:train.SAMPLE_ROWS]):.4f}")
    return out
