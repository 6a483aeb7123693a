"""Job kind ``train``: one ``xtb.train`` call on a resident matrix.

``setup`` makes the rows from the seed and builds the ``QuantileDMatrix``;
``window`` makes the one training call, whose first ``warm_rounds`` rounds
compile or load every program of the cell and belong to set-up, and whose
later rounds are the measured window; ``compare`` holds what that very call
produced (its trees, its cuts and bins, the margin it ended with) against
the numpy reference.
"""
from __future__ import annotations

import json
import os
import tempfile
import time

import numpy as np

from benchmarks import data, reference

SAMPLE_ROWS = 200_000
FOLLOW_TREES = 3  # the reference follows the first three rounds


def allocator(note: str, log) -> None:
    """What the device's allocator holds at this point of a run.  The
    largest free block after QuantileDMatrix tells where the page landed:
    a round's speed has followed it in every process read (PERF.md §6)."""
    import jax

    m = jax.devices()[0].memory_stats() or {}
    log(f"device memory {note}: " + ", ".join(
        f"{k} {m[k]}" for k in ("bytes_in_use", "num_allocs",
                                "largest_free_block_bytes", "bytes_reserved")
        if k in m))


def make_holes(sizes, log) -> list:
    """Free blocks of ``sizes`` ([bytes, count] pairs) in the device's
    allocator, each between two kept 256-byte blocks so that none merges
    with its neighbour.  The program's small buffers then fit a hole
    whichever of them is freed first, the large free block is split by large
    buffers only, and the page lands at one address in every process: a round
    has followed that address by 3.5% (PERF.md §6).  Returns the kept blocks."""
    import jax

    kept, holes = [jax.device_put(np.zeros(256, np.uint8))], []
    for size, count in sizes:
        for _ in range(int(count)):
            holes.append(jax.device_put(np.zeros(int(size), np.uint8)))
            kept.append(jax.device_put(np.zeros(256, np.uint8)))
    jax.block_until_ready(holes + kept)
    log(f"{len(holes)} holes of {sum(h.nbytes for h in holes)} bytes made in "
        f"the device's allocator")
    return kept


class State:
    """What one run of a cell carries from set-up to the comparison."""

    def __init__(self) -> None:
        self.clocks: dict = {}


def setup(cell: dict, seed: int, env: dict) -> State:
    import jax

    import xgboost_tpu as xtb

    cfg, log = cell["config"], env["log"]
    ds = cfg["dataset"]
    rows = int(env.get("rehearse_rows") or ds["rows"])
    held = max(int(ds["rows_held_out"] * rows / ds["rows"]), 1)
    st = State()
    st.cell, st.seed, st.rows, st.log = cell, int(seed), rows, log
    t0 = time.perf_counter()
    X_all, y_all = data.make(ds, seed, rows=rows + held)
    st.X, st.y = X_all[:rows], y_all[:rows]
    st.X_held, st.y_held = X_all[rows:], y_all[rows:]
    st.clocks["data_s"] = time.perf_counter() - t0
    log(f"data: {st.X.shape} float32 and {held} held-out rows from seed "
        f"{seed} in {st.clocks['data_s']:.2f}s")

    st.params = dict(cfg["params"])
    if env.get("rehearse_rows"):
        st.params.pop("device", None)  # the program refuses device=tpu here
    st.kept = make_holes(cell["traffic"].get("allocator_holes", []), log)
    allocator("before QuantileDMatrix", log)
    t0 = time.perf_counter()
    st.dtrain = xtb.QuantileDMatrix(st.X, label=st.y,
                                    max_bin=int(st.params["max_bin"]))
    jax.block_until_ready(st.dtrain._ellpack.bins)
    st.clocks["dmatrix_s"] = time.perf_counter() - t0
    bins = st.dtrain._ellpack.bins
    log(f"QuantileDMatrix: {st.clocks['dmatrix_s']:.2f}s, bins "
        f"{bins.dtype}{tuple(bins.shape)}")
    allocator("after QuantileDMatrix", log)
    return st


def _round_clock(st: State, seconds: float, tracer):
    """The benchmark's one TrainingCallback: reads the clock as each round
    ends, opens and closes the window, and brackets the traced rounds."""
    import jax

    from xgboost_tpu.callback import TrainingCallback

    traffic = st.cell["traffic"]
    warm = int(traffic["warm_rounds"])
    traced = int(traffic["traced_rounds"]) if tracer else 0
    least_untraced = int(traffic["untraced_rounds_min"]) if tracer else 1
    c = st.clocks
    c.update(round_s=[], traced_round_s=[], paused_s=0.0, warm_round_ends=[],
             train_called_at=time.perf_counter())

    def drain(model):
        jax.block_until_ready(model._get_cache(st.dtrain).margin)

    class RoundClock(TrainingCallback):
        def after_iteration(self, model, epoch, evals_log) -> bool:
            now = time.perf_counter()
            done = epoch + 1 - warm  # window rounds finished
            if done <= 0:
                c["warm_round_ends"].append(now - c["train_called_at"])
            if done < 0:
                return False
            if done == 0:
                drain(model)
                if tracer:
                    tracer.start()
                c["opened_at"] = self.mark = time.perf_counter()
                return False
            if done <= traced:
                c["traced_round_s"].append(now - self.mark)
                if done == traced:
                    drain(model)
                    c["traced_window_s"] = time.perf_counter() - c["opened_at"]
                    tracer.stop()
                    c["paused_s"] = (time.perf_counter() - c["opened_at"]
                                     - c["traced_window_s"])
                self.mark = time.perf_counter()
                return False
            c["round_s"].append(now - self.mark)
            self.mark = now
            spent = now - c["opened_at"] - c["paused_s"]
            if spent < seconds or len(c["round_s"]) < least_untraced:
                return False
            drain(model)
            c["closed_at"] = time.perf_counter()
            c["window_rounds"] = done
            return True

    return RoundClock()


def window(st: State, seconds: float, tracer=None) -> None:
    """The one training call.  ``clocks['opened_at']`` is where set-up ends."""
    import xgboost_tpu as xtb

    st.bst = xtb.train(st.params, st.dtrain, 1_000_000, verbose_eval=False,
                       callbacks=[_round_clock(st, seconds, tracer)])
    c = st.clocks
    st.log("rounds " + " ".join(f"{t:.3f}" for t in c["round_s"]) + " s")
    c["window_s"] = c["closed_at"] - c["opened_at"]
    st.attempted = int(c["window_rounds"])
    untraced = c["round_s"]
    c["round_mean_s"] = sum(untraced) / len(untraced)
    c["round_max_s"] = max(untraced)
    c["rows"] = st.rows
    c["row_rounds"] = st.rows * st.attempted


def compare(st: State, env: dict, lower_precision: bool = False,
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
        st.rows, size=min(SAMPLE_ROWS, st.rows), replace=False))
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
    out = reference.compare_training(
        st.X, st.y, model, np.asarray(cuts.cut_ptrs, np.int64),
        np.asarray(cuts.cut_values, np.float32), page_fr, idx, sample_bins,
        margin[idx], max_bin=int(p["max_bin"]), max_depth=int(p["max_depth"]),
        eta=float(p["eta"]), lam=float(g["lambda"]),
        mcw=float(g["min_child_weight"]),
        base_margin=float(np.log(p["base_score"] / (1.0 - p["base_score"]))),
        follow=FOLLOW_TREES, split_tree=warm,
        lower_precision=lower_precision, faults=faults, log=log)
    held = reference.walk(trees, st.X_held[:SAMPLE_ROWS], 0.0)
    log(f"held-out AUC of the {len(trees)} trees on {len(held)} rows "
        f"(numpy walk): {reference.auc(held, st.y_held[:SAMPLE_ROWS]):.4f}")
    return out
