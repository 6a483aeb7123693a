"""The three per-layer metrics of the cell with absent entries and the eight
the benchmark has without a list of cells, read in the new cell from a
recorded ring and a recorded trace; the work count behind
``level_present_roofline``.  All arithmetic, no device."""
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run, work, work_missing  # noqa: E402

CELL = "bosch-d8.train"
NEW = ("page_missing_pct", "split_default_left_pct", "level_present_roofline")
ROWS, FEATURES, DEPTH = 946_997, 968, 8
CELLS = ROWS * FEATURES
MISSING = 741_000_000


def ring(rounds, page=None, counters=None):
    """Empties the program's span ring and leaves in it one ``dmatrix.build``
    whose ``dmatrix.bin`` carries ``page`` and ``rounds`` round spans, each
    with a wait, a ``grow.to_host`` and ``counters`` summed on it."""
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import count_in_round, span, step_span

    flight.clear()
    with span("dmatrix.build"):
        with span("dmatrix.sketch"):
            pass
        with span("dmatrix.bin") as binning:
            binning.args.update(page or {})
    for i in range(rounds):
        with step_span("train.round", i):
            with span("grow.wait_device"):
                pass
            with span("grow.to_host", copies=12) as copying:
                copying.args.update(counters or {})
            count_in_round(**(counters or {}))


def context(module_s, rounds=6, warm=2, traced=2):
    cell = run.load_cell(CELL)
    clocks = {"rows": ROWS, "round_mean_s": 6.8, "round_max_s": 6.9,
              "round_s": [6.8] * (rounds - warm - traced),
              "traced_round_s": [6.8] * traced, "dmatrix_s": 110.0,
              "setup_s": 170.0, "row_rounds": ROWS * (rounds - warm),
              "window_s": 27.2, "window_rounds": rounds - warm}
    lines = []
    return {"cell": cell, "config": cell["config"], "clocks": clocks,
            "trace": module_s and {"busy_s": 13.5, "window_s": 13.6,
                                   "module_s": module_s},
            "device_kind": "TPU v5 lite", "log": lines.append, "lines": lines}


MODULES = {"jit_level_step": 1.1, "jit_level_step_padded": 12.0,
           "jit_leaf_margin_delta": 0.02}
PAGE = {"bins.cells": CELLS, "bins.missing": MISSING}
SPLITS = {"splits": 255, "splits.default_left": 120}


def test_manifest_gives_the_new_cell_the_three_and_only_it():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    got = {m["name"]: m for m in manifest["per_layer"]}
    for name, source, better, layer in (
            ("page_missing_pct", "program_counter", "lower",
             "data: sketch + binning"),
            ("split_default_left_pct", "program_counter", "higher",
             "level step"),
            ("level_present_roofline", "device_trace", "higher",
             "level step")):
        m = got[name]
        assert (m["unit"], m["source"], m["better"], m["layer"], m["moves"],
                m["workloads"]) == ("%", source, better, layer, "train_rate",
                                    [CELL])
    cell = run.load_cell(CELL)
    names = [m["name"] for m in run.cell_metrics(cell, "per_layer")]
    assert set(NEW) <= set(names) and len(names) == 11
    assert {m["name"] for m in run.cell_metrics(cell, "end_to_end")} == {
        "train_rate", "setup_s"}
    mine = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(mine) == 1 and mine[0]["chips"] == 1
    cfg = cell["config"]
    assert cfg["reduced"] == [] and cfg["dataset"]["rows"] == ROWS
    assert cfg["dataset"]["features"] == FEATURES
    assert work.config_shape(cfg) == (ROWS, FEATURES, DEPTH, 1)
    assert cell["traffic"]["job"] == "train-missing"
    old = run.load_json(ROOT, "benchmarks", "traffic", "train-resident.json")
    for key in ("warm_rounds", "evals", "closed_by", "traced_rounds",
                "untraced_rounds_min", "allocator_holes"):
        assert cell["traffic"][key] == old[key]
    # no other cell reads the three
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            theirs = run.cell_metrics(run.load_cell(other["name"]), "per_layer")
            assert not set(NEW) & {m["name"] for m in theirs}


def test_every_per_layer_metric_of_the_cell_reads_a_number():
    ring(6, PAGE, SPLITS)
    ctx = context(MODULES)
    got = run.read_metrics(ctx["cell"], "per_layer", ctx)
    assert set(got) == {m["name"] for m in run.cell_metrics(
        ctx["cell"], "per_layer")}
    assert all(math.isfinite(v["value"]) for v in got.values())
    assert got["page_missing_pct"]["value"] == pytest.approx(
        100.0 * MISSING / CELLS)
    assert 80 < got["page_missing_pct"]["value"] < 82
    # two untraced rounds of the window, a tree each
    assert got["split_default_left_pct"]["value"] == pytest.approx(
        100.0 * 240 / 510)
    present = 1.0 - MISSING / CELLS
    visited = ROWS * (1 + (DEPTH - 1) / 2)
    need = visited * (FEATURES * present + 8) / 819e9
    assert got["level_present_roofline"]["value"] == pytest.approx(
        100 * need / (13.1 / 2))
    assert "bound by hbm_bytes_per_s" in "\n".join(ctx["lines"])
    # it counts less than the dense page's share does, so it reads under it
    assert (0 < got["level_present_roofline"]["value"]
            < got["level_roofline"]["value"] < 100)
    assert got["level_roofline"]["value"] == pytest.approx(
        100 * visited * (FEATURES + 8) / 819e9 / (13.1 / 2))


@pytest.mark.parametrize("present", [1.0, 0.19, 0.0])
def test_the_present_entries_cost_no_more_than_the_dense_page(present):
    dense = work.level_bytes(ROWS, FEATURES, DEPTH)
    got = work_missing.level_present_bytes(ROWS, FEATURES, DEPTH, present)
    assert got <= dense and (got == dense) == (present == 1.0)
    assert got >= work.visited_rows(ROWS, DEPTH) * work.GPAIR_BYTES
    assert work_missing.level_present_flops(
        ROWS, FEATURES, DEPTH, present) == pytest.approx(
            present * work.level_flops(ROWS, FEATURES, DEPTH))


@pytest.mark.parametrize("page,counters,lacking", [
    (None, SPLITS, {"page_missing_pct", "level_present_roofline"}),
    (PAGE, None, {"split_default_left_pct"}),
    (PAGE, {"splits": 0, "splits.default_left": 0},
     {"split_default_left_pct"}),
    (None, None, set(NEW)),
], ids=["no-page-counters", "no-split-counters", "no-split", "the-parent"])
def test_a_program_without_the_counters_gives_nothing(page, counters, lacking):
    ring(6, page, counters)
    ctx = context(MODULES)
    got = run.read_metrics(ctx["cell"], "per_layer", ctx)
    assert set(NEW) - set(got) == lacking
    assert {"level_roofline", "round_host_s", "to_host_ms"} <= set(got)


def test_an_untraced_run_reads_the_counters_and_not_the_trace():
    ring(6, PAGE, SPLITS)
    untraced = context(None)
    got = run.read_metrics(untraced["cell"], "per_layer", untraced)
    assert "level_present_roofline" not in got and "level_roofline" not in got
    assert {"page_missing_pct", "split_default_left_pct"} <= set(got)


def test_the_program_counts_its_page_and_its_splits():
    """The counters as the program itself leaves them in the ring: a matrix
    with NaN through QuantileDMatrix and three rounds."""
    import numpy as np

    import xgboost_tpu as xtb
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import recent

    flight.clear()
    rng = np.random.default_rng(3)
    X = rng.normal(size=(3000, 7)).astype(np.float32)
    X[rng.random(X.shape) < 0.4] = np.nan
    X[:, 6] = np.nan
    y = (np.nan_to_num(X[:, 0]) > 0).astype(np.float32)
    d = xtb.QuantileDMatrix(X, label=y, max_bin=32)
    bst = xtb.train({"objective": "binary:logistic", "max_depth": 4,
                     "max_bin": 32}, d, 3, verbose_eval=False)
    (binned,) = recent("dmatrix.bin")
    assert binned["bins.cells"] == X.size
    assert binned["bins.missing"] == int(np.isnan(X).sum())
    trees = bst.trees
    copied = recent("grow.to_host")
    rounds = recent("train.round")
    assert len(copied) == len(rounds) == len(trees) == 3
    for tree, span, whole in zip(trees, copied, rounds):
        inner = tree.left_children != -1
        assert span["splits"] == whole["splits"] == int(inner.sum()) > 0
        assert (span["splits.default_left"] == whole["splits.default_left"]
                == int((tree.default_left & inner).sum()))
