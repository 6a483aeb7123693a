"""The best-first cell's three per-layer metrics and the eight the benchmark
had without a list of cells, read in the new cell from a context built by
hand; the work count behind ``expand_roofline``; the reference's replayed
queue on trees small enough to follow by hand.  All arithmetic, no device."""
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reference, reference_bestfirst, run, work, work_bestfirst  # noqa: E402

CELL = "higgs-leafwise-255.train"
NEW = ("hist_row_visits", "expand_unused_pct", "expand_roofline")
ROWS = 10_500_000


def ring(rounds, counters):
    """Empties the program's span ring and leaves ``rounds`` round spans in
    it, each with ``counters`` among its arguments and one wait inside."""
    from xgboost_tpu.telemetry import flight
    from xgboost_tpu.telemetry.spans import count_in_round, span, step_span

    flight.clear()
    for i in range(rounds):
        with step_span("train.round", i):
            with span("grow.wait_device"):
                pass
            with span("grow.to_host", copies=13):
                pass
            count_in_round(**counters)


def context(module_s, rounds=6, warm=2, traced=2):
    cell = run.load_cell(CELL)
    clocks = {"rows": ROWS, "round_mean_s": 6.0, "round_max_s": 6.1,
              "round_s": [6.0] * (rounds - warm - traced),
              "traced_round_s": [6.0] * traced, "dmatrix_s": 30.0,
              "setup_s": 70.0, "row_rounds": ROWS * (rounds - warm),
              "window_s": 24.0, "window_rounds": rounds - warm,
              "bestfirst_smaller_rows": [21_000_000, 26_250_000]}
    lines = []
    return {"cell": cell, "config": cell["config"], "clocks": clocks,
            "trace": module_s and {"busy_s": 11.9, "window_s": 12.0,
                                   "module_s": module_s},
            "device_kind": "TPU v5 lite", "log": lines.append, "lines": lines}


MODULES = {"jit_level_step_bestfirst": 11.0, "jit__finish": 0.04,
           "jit_leaf_margin_delta": 0.17}
COUNTERS = {"bestfirst.passes": 30, "bestfirst.pairs_evaluated": 300,
            "bestfirst.pairs_committed": 254,
            "bestfirst.hist_rows": 30 * 10_500_096}


def test_manifest_gives_the_new_cell_the_three_and_only_it():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    got = {m["name"]: m for m in manifest["per_layer"]}
    for name, unit, source, better in (
            ("hist_row_visits", "x_rows", "program_counter", "lower"),
            ("expand_unused_pct", "%", "program_counter", "lower"),
            ("expand_roofline", "%", "device_trace", "higher")):
        m = got[name]
        assert (m["unit"], m["source"], m["better"], m["layer"], m["moves"],
                m["workloads"]) == (unit, source, better, "best-first pass",
                                    "train_rate", [CELL])
    assert [m["name"] for m in manifest["per_layer"]][-3:] == list(NEW)
    cell = run.load_cell(CELL)
    names = [m["name"] for m in run.cell_metrics(cell, "per_layer")]
    assert names[-3:] == list(NEW) and len(names) == 11
    assert {m["name"] for m in run.cell_metrics(cell, "end_to_end")} == {
        "train_rate", "setup_s"}
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["workloads"][-1]["chips"] == 1
    cfg = cell["config"]
    assert cfg["reduced"] == [] and cfg["dataset"]["rows"] == ROWS
    assert cfg["params"] == {
        "objective": "binary:logistic", "tree_method": "hist",
        "grow_policy": "lossguide", "max_depth": 0, "max_leaves": 255,
        "min_child_weight": 100, "eta": 0.1, "max_bin": 256,
        "base_score": 0.5, "device": "tpu"}
    assert cell["traffic"]["job"] == "train-bestfirst"
    old = run.load_json(ROOT, "benchmarks", "traffic", "train-resident.json")
    for key in ("warm_rounds", "evals", "closed_by", "traced_rounds",
                "untraced_rounds_min", "allocator_holes"):
        assert cell["traffic"][key] == old[key]
    # no other cell reads the three
    for other in manifest["workloads"][:-1]:
        theirs = run.cell_metrics(run.load_cell(other["name"]), "per_layer")
        assert not set(NEW) & {m["name"] for m in theirs}


def test_every_per_layer_metric_of_the_cell_reads_a_number():
    ring(6, COUNTERS)
    ctx = context(MODULES)
    got = run.read_metrics(ctx["cell"], "per_layer", ctx)
    # the ring of this test holds no dmatrix.build: its span says nothing
    assert set(got) == {m["name"] for m in run.cell_metrics(
        ctx["cell"], "per_layer")} - {"sketch_s"}
    assert all(math.isfinite(v["value"]) for v in got.values())
    # two untraced rounds of the window, a tree each
    assert got["hist_row_visits"]["value"] == pytest.approx(
        30 * 10_500_096 / ROWS)
    assert got["expand_unused_pct"]["value"] == pytest.approx(
        100 * (1 - 254 / 300))
    need = (2 * ROWS + 21_000_000 + 26_250_000) * (28 + 8) / 819e9
    assert got["expand_roofline"]["value"] == pytest.approx(100 * need / 11.0)
    assert 0 < got["expand_roofline"]["value"] < 1
    assert "bound by hbm_bytes_per_s" in "\n".join(ctx["lines"])
    # the level step's share reads the pass through its prefix, against half
    # a root pass: work.config_shape takes max_depth 0
    level = work.level_bytes(ROWS, 28, 0) / 819e9
    assert level == pytest.approx(ROWS / 2 * 36 / 819e9)
    assert got["level_roofline"]["value"] == pytest.approx(100 * level / 5.5)
    assert got["level_roofline"]["value"] < got["expand_roofline"]["value"]
    assert got["to_host_ms"]["value"] >= 0 and got["round_host_s"]["value"] >= 0


def test_a_program_without_the_counters_or_the_pass_gives_nothing():
    ring(6, {})
    ctx = context({"jit_level_step": 1.0, "jit__apply_split": 9.0})
    got = run.read_metrics(ctx["cell"], "per_layer", ctx)
    assert not set(NEW) & set(got)
    assert {"level_roofline", "round_host_s", "to_host_ms"} <= set(got)
    untraced = context(None)
    ring(6, COUNTERS)
    got = run.read_metrics(untraced["cell"], "per_layer", untraced)
    assert "expand_roofline" not in got and "hist_row_visits" in got


def tree_of(left, right, feat=None):
    n = len(left)
    return reference.Tree({
        "left_children": left, "right_children": right,
        "split_indices": feat or [0] * n, "split_conditions": [0.0] * n,
        "base_weights": [0.0] * n, "sum_hessian": [1.0] * n})


def test_the_work_of_a_tree_is_its_root_and_every_splits_smaller_child():
    #        0
    #      1   2        rows on the leaves: 3:5, 4:1, 5:2, 6:8
    #     3 4 5 6
    t = tree_of([1, 3, 5, -1, -1, -1, -1], [2, 4, 6, -1, -1, -1, -1])
    leaf = np.repeat([3, 4, 5, 6], [5, 1, 2, 8])
    smaller = work_bestfirst.smaller_child_rows(t, leaf)
    assert smaller == min(6, 10) + min(5, 1) + min(2, 8)
    assert work_bestfirst.tree_bytes(16, smaller, 28) == (16 + 9) * 36
    assert work_bestfirst.tree_flops(16, smaller, 28) == (16 + 9) * 56
    # never more than half the rows a level: under a level-wise count's floor
    assert smaller <= 16 / 2 * 2
    chain = tree_of([1, -1, 3, -1, -1], [2, -1, 4, -1, -1])
    assert work_bestfirst.smaller_child_rows(
        chain, np.repeat([1, 3, 4], [1, 2, 7])) == 1 + 2


def test_order_gap_replays_the_queue():
    gap = reference_bestfirst.order_gap
    #  0 -> (1, 2); the program then split 1 -> (3, 4)
    parent = [-1, 0, 0, 1, 1]
    # in order: 1 was the best open leaf
    assert gap(parent, np.array([10.0, 5.0, 3.0, 1.0, 1.0]), 2, 2, 1e-6) == 0.0
    # out of order: 2 offered 8 when 1 (gain 5) was split
    got = gap(parent, np.array([10.0, 5.0, 8.0, 1.0, 1.0]), 2, 2, 1e-6)
    assert got == pytest.approx((8 * (1 - 1e-4) - 5) / (10 + 8))
    # a tie inside the room gives nothing away
    assert gap(parent, np.array([10.0, 5.0, 5.0004, 1.0, 1.0]), 2, 2, 1e-6) == 0.0
    # fewer splits than the budget while a leaf could be split
    got = gap(parent, np.array([10.0, 5.0, 3.0, 1.0, 1.0]), 2, 3, 1e-6)
    assert got == pytest.approx((3 + 1 + 1) / 15)
    # ... but not where nothing is left above the floor, or below the depth
    assert gap(parent, np.array([10.0, 5.0, 0.0, -np.inf, 1e-9]), 2, 3, 1e-6) == 0.0
    assert gap(parent, np.array([10.0, 5.0, 3.0, 1.0, 1.0]), 2, 3, 1e-6,
               may_split=np.array([1, 1, 0, 0, 0], bool)) == 0.0
    # children that are not (2s+1, 2s+2) of one parent: no tree in pop order
    assert gap([-1, 0, 0, 2, 1], np.ones(5), 2, 2, 1e-6) == 1.0


def test_the_serial_driver_and_its_planted_faults():
    rng = np.random.default_rng(3)
    R, F, B = 4000, 4, 32
    bins = rng.integers(0, B, size=(F, R)).astype(np.uint8)
    g = np.where(bins[0] < 10, -1.0, 0.4) + 0.3 * (bins[1] > 20) \
        + 0.2 * rng.normal(size=R)
    h = np.ones(R)
    n_bins = np.full(F, B, np.int64)
    grow = reference_bestfirst.grow_serial
    kw = dict(max_leaves=12, lam=1.0, mcw=5.0)
    sound = grow(bins, g, h, n_bins, **kw)
    assert sound.leaves == 12 and sound.order[0] == 0
    assert all(sound.left[p] == 2 * s + 1 and sound.right[p] == 2 * s + 2
               for s, p in enumerate(sound.order))
    got = reference_bestfirst.grown_gaps(sound, max_leaves=12, gamma=0.0)
    assert got == {"order_gap": 0.0, "leaves_gap": 0.0}
    by_id = grow(bins, g, h, n_bins, order="id", **kw)
    assert by_id.order == sorted(by_id.order) != sound.order
    topk = grow(bins, g, h, n_bins, commit=4, **kw)
    nosub = grow(bins, g, h, n_bins, no_sibling_at=0, **kw)
    for t in (by_id, topk, nosub):
        assert reference_bestfirst.grown_gaps(
            t, max_leaves=12, gamma=0.0)["order_gap"] > 1e-5
    short = grow(bins, g, h, n_bins, **{**kw, "max_leaves": 11})
    assert reference_bestfirst.grown_gaps(
        short, max_leaves=12, gamma=0.0)["leaves_gap"] == pytest.approx(1 / 12)
    assert grow(bins, g, h, n_bins, max_depth=2, **kw).leaves == 4
    assert grow(bins, g, h, n_bins, gamma=1e9, **kw).leaves == 1
