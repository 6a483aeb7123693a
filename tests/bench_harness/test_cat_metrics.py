"""The two per-layer metrics of the categorical cell and the eight the
benchmark has without a list of cells, read in the new cell from a recorded
ring and a recorded trace; the work count behind ``cat_level_roofline``;
the counters as the program leaves them.  All arithmetic, no device."""
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run, work, work_cat  # noqa: E402

CELL = "airline-cat-d6.train"
NEW = ("cat_split_pct", "cat_level_roofline")
ROWS, FEATURES, DEPTH = 40_000_000, 8, 6
BITS = [4, 5, 3, 8, 5, 9, 9, 8]  # Month .. Distance: ceil(log2(bins))
MODULES = {"jit_level_step": 0.16, "jit_level_step_padded": 1.6,
           "jit_leaf_margin_delta": 0.6}
SPLITS = {"splits": 63, "splits.categorical": 50, "splits.cat_onehot": 0}


def ring(rounds, counters=None):
    """Empties the program's span ring and leaves in it one
    ``dmatrix.build`` and ``rounds`` round spans, each with a wait, a
    ``grow.to_host`` and ``counters`` summed on it."""
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import count_in_round, span, step_span

    flight.clear()
    with span("dmatrix.build"):
        with span("dmatrix.sketch"):
            pass
        with span("dmatrix.bin"):
            pass
    for i in range(rounds):
        with step_span("train.round", i):
            with span("grow.wait_device"):
                pass
            with span("grow.to_host", copies=12):
                pass
            count_in_round(**(counters or {}))


def context(module_s, rounds=12, warm=2, traced=2):
    cell = run.load_cell(CELL)
    clocks = {"rows": ROWS, "round_mean_s": 1.2, "round_max_s": 1.3,
              "round_s": [1.2] * (rounds - warm - traced),
              "traced_round_s": [1.2] * traced, "dmatrix_s": 40.0,
              "setup_s": 100.0, "row_rounds": ROWS * (rounds - warm),
              "window_s": 10.8, "window_rounds": rounds - warm}
    lines = []
    return {"cell": cell, "config": cell["config"], "clocks": clocks,
            "trace": module_s and {"busy_s": 2.38, "window_s": 2.4,
                                   "module_s": module_s},
            "device_kind": "TPU v5 lite", "log": lines.append, "lines": lines}


def test_manifest_gives_the_new_cell_the_two_and_only_it():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    got = {m["name"]: m for m in manifest["per_layer"]}
    for name, source in (("cat_split_pct", "program_counter"),
                         ("cat_level_roofline", "device_trace")):
        m = got[name]
        assert (m["unit"], m["source"], m["better"], m["layer"], m["moves"],
                m["workloads"]) == ("%", source, "higher", "level step",
                                    "train_rate", [CELL])
    cell = run.load_cell(CELL)
    names = {m["name"] for m in run.cell_metrics(cell, "per_layer")}
    no_list = {m["name"] for m in manifest["per_layer"]
               if "workloads" not in m}
    assert names == set(NEW) | no_list and len(no_list) == 8
    mine = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(mine) == 1 and mine[0]["chips"] == 1
    cfg = cell["config"]
    assert cfg["reduced"] == ["rows"] and cfg["dataset"]["rows"] == ROWS
    assert work.config_shape(cfg) == (ROWS, FEATURES, DEPTH, 1)
    assert cell["traffic"]["job"] == "train-cat"
    old = run.load_json(ROOT, "benchmarks", "traffic", "train-resident.json")
    for key in ("warm_rounds", "evals", "closed_by", "traced_rounds",
                "untraced_rounds_min", "allocator_holes"):
        assert cell["traffic"][key] == old[key]
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            theirs = run.cell_metrics(run.load_cell(other["name"]), "per_layer")
            assert not set(NEW) & {m["name"] for m in theirs}


def test_every_per_layer_metric_of_the_cell_reads_a_number():
    ring(12, SPLITS)
    ctx = context(MODULES)
    got = run.read_metrics(ctx["cell"], "per_layer", ctx)
    assert set(got) == {m["name"] for m in run.cell_metrics(
        ctx["cell"], "per_layer")}
    assert all(math.isfinite(v["value"]) for v in got.values())
    assert got["cat_split_pct"]["value"] == pytest.approx(100.0 * 50 / 63)
    visited = ROWS * (1 + (DEPTH - 1) / 2)
    need = visited * (sum(BITS) / 8 + 8) / 819e9
    assert got["cat_level_roofline"]["value"] == pytest.approx(
        100 * need / (1.76 / 2))
    assert "bound by hbm_bytes_per_s" in "\n".join(ctx["lines"])
    # a code of 300 categories takes 9 bits where work.py counts 8 for all
    assert (got["cat_level_roofline"]["value"]
            < got["level_roofline"]["value"] < 100)


def test_the_bits_a_column_needs():
    cfg = run.load_cell(CELL)["config"]
    assert work_cat.column_bits(cfg) == BITS
    assert work_cat.level_bytes(cfg, ROWS) < work.level_bytes(
        ROWS, FEATURES, DEPTH)
    assert work_cat.level_flops(cfg, ROWS) == pytest.approx(
        work.level_flops(ROWS, FEATURES, DEPTH))


@pytest.mark.parametrize("counters,trace,lacking", [
    (None, MODULES, {"cat_split_pct"}),
    ({"splits": 0, "splits.categorical": 0}, MODULES, {"cat_split_pct"}),
    (SPLITS, None, {"cat_level_roofline"}),
    (None, None, set(NEW)),
], ids=["the-parent", "no-split", "untraced", "nothing"])
def test_a_run_without_what_a_metric_reads_gives_nothing(counters, trace,
                                                         lacking):
    ring(12, counters)
    ctx = context(trace)
    got = run.read_metrics(ctx["cell"], "per_layer", ctx)
    assert set(NEW) - set(got) == lacking
    assert {"round_host_s", "to_host_ms"} <= set(got)


def test_the_program_counts_its_categorical_splits():
    """The counters as the program leaves them in the ring: a column of 3
    categories (one-hot under max_cat_to_onehot=4), one of 300 past
    max_bin=32, a numeric one; three rounds."""
    import numpy as np

    import xgboost_tpu as xtb
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import recent

    flight.clear()
    rng = np.random.default_rng(3)
    R = 4000
    a, b = rng.integers(0, 3, R), rng.integers(0, 300, R)
    X = np.stack([a, b, rng.normal(size=R)], axis=1).astype(np.float32)
    latent = rng.normal(size=3)[a] + rng.normal(size=300)[b] + X[:, 2]
    y = (latent > 0).astype(np.float32)
    d = xtb.QuantileDMatrix(X, label=y, max_bin=32,
                            feature_types=["c", "c", "q"],
                            enable_categorical=True)
    bst = xtb.train({"objective": "binary:logistic", "max_depth": 4,
                     "max_bin": 32}, d, 3, verbose_eval=False)
    rounds = recent("train.round")
    assert len(rounds) == len(bst.trees) == 3
    for tree, whole in zip(bst.trees, rounds):
        inner = tree.left_children != -1
        cat = inner & (tree.split_type == 1)
        assert whole["splits"] == int(inner.sum())
        assert whole["splits.categorical"] == int(cat.sum()) > 0
        assert whole["splits.cat_onehot"] == int(
            (cat & (tree.split_indices == 0)).sum())
        assert whole["cat.bins_max"] == 300
    assert sum(r["splits.cat_onehot"] for r in rounds) > 0
