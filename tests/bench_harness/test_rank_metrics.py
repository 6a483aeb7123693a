"""The ranking cell's three per-layer metrics and the eight the benchmark
had, read in the ranking cell from a context built by hand; the work count
behind ``gradient_roofline``; and the reference's gradient against a double
loop over a group's pairs.  All arithmetic, no device."""
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reference_rank, run, work, work_rank  # noqa: E402

CELL = "mslr-web30k-ndcg.train"
NEW = ("gradient_s", "gradient_roofline", "rank_pad_pct")
DOCS, PAIRS = 2_270_296, 62_000_000


def context(module_s, run_ring):
    cell = run.load_cell(CELL)
    r = run_ring
    clocks = {"rows": DOCS, "rank_docs": DOCS, "rank_pairs": PAIRS,
              "round_mean_s": 3.0, "round_max_s": 3.1, "round_s": r["round_s"],
              "traced_round_s": [3.0] * r["traced"], "dmatrix_s": 9.0,
              "setup_s": 70.0, "row_rounds": DOCS * 4, "window_s": 12.0,
              "window_rounds": r["rounds"] - r["warm"]}
    lines = []
    return {"cell": cell, "config": cell["config"], "clocks": clocks,
            "trace": module_s and {"busy_s": 5.9, "window_s": 6.0,
                                   "module_s": module_s},
            "device_kind": "TPU v5 lite", "log": lines.append, "lines": lines}


MODULES = {"jit_level_step": 0.8, "jit_level_step_padded": 3.6,
           "jit__lambda_gradients_topk": 1.4, "jit_leaf_margin_delta": 0.04}


def test_manifest_gives_the_ranking_cell_the_three_and_only_it():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    got = {m["name"]: m for m in manifest["per_layer"]}
    for name, unit, source, better in (
            ("gradient_s", "s", "device_trace", "lower"),
            ("gradient_roofline", "%", "device_trace", "higher"),
            ("rank_pad_pct", "%", "program_counter", "lower")):
        m = got[name]
        assert (m["unit"], m["source"], m["better"], m["layer"], m["moves"],
                m["workloads"]) == (unit, source, better,
                                    "objective: gradient", "train_rate", [CELL])
    cell = run.load_cell(CELL)
    assert [m["name"] for m in run.cell_metrics(cell, "per_layer")][-3:] == list(NEW)
    assert len(run.cell_metrics(cell, "per_layer")) == 11
    assert {m["name"] for m in run.cell_metrics(cell, "end_to_end")} == {
        "train_rate", "setup_s"}
    cfg = cell["config"]
    assert cfg["reduced"] == [] and cfg["dataset"]["rows"] == DOCS
    assert cfg["params"]["objective"] == "rank:ndcg"


def test_every_per_layer_metric_reads_in_the_ranking_cell(run_ring):
    ctx = context(MODULES, run_ring)
    got = run.read_metrics(ctx["cell"], "per_layer", ctx)
    # the ring of this test holds no ranking layout: its counter says nothing
    assert set(got) == {m["name"] for m in ctx["cell"]["manifest"]["per_layer"]
                        } - {"rank_pad_pct"}
    assert all(math.isfinite(v["value"]) for v in got.values())
    assert got["gradient_s"]["value"] == pytest.approx(0.7)
    need = DOCS * 16 / 819e9
    assert got["gradient_roofline"]["value"] == pytest.approx(100 * need / 0.7)
    assert 0 < got["gradient_roofline"]["value"] < 1
    assert "bound by hbm_bytes_per_s" in "\n".join(ctx["lines"])
    level = work.level_bytes(DOCS, 136, 6) / 819e9
    assert got["level_roofline"]["value"] == pytest.approx(100 * level / 2.2)
    untraced = run.read_metrics(ctx["cell"], "per_layer", context(None, run_ring))
    assert not {"gradient_s", "gradient_roofline", "level_roofline",
                "device_idle_pct"} & set(untraced)
    other = {"jit_level_step": 1.0, "jit_sigmoid": 0.1}  # another objective
    got = run.read_metrics(ctx["cell"], "per_layer", context(other, run_ring))
    assert "gradient_s" not in got and "gradient_roofline" not in got


def test_rank_pad_pct_reads_the_layout_spans_counters(run_ring, monkeypatch):
    from xgboost_tpu.objective import create_objective, ranking

    reader = run.load_module("metrics", "rank_pad_pct")
    ctx = context(MODULES, run_ring)
    assert reader.read(ctx) is None and "no objective.group_layout" in ctx["lines"][-1]
    monkeypatch.setattr(ranking, "_native_lambdarank_ok", lambda: False)
    sizes = [1, 4, 10, 5]
    obj = create_objective("rank:ndcg", {})
    obj.set_group_info(np.concatenate([[0], np.cumsum(sizes)]),
                       np.zeros(sum(sizes), np.float32))
    assert reader.read(ctx) == pytest.approx(100 * (1 - 20 / 40))
    assert "rank.docs 20" in ctx["lines"][-1]


def test_pairs_and_bytes_are_counted_from_the_groups_that_exist():
    assert work_rank.pair_count([1], 32) == 0
    assert work_rank.pair_count([2, 3], 32) == 1 + 3
    assert work_rank.pair_count([40], 32) == sum(40 - 1 - i for i in range(32))
    sizes = np.array([1, 2, 31, 32, 33, 300, 1251])
    brute = sum(1 for n in sizes for i in range(min(32, n))
                for j in range(i + 1, n))
    assert work_rank.pair_count(sizes, 32) == brute
    assert brute < np.sum(np.minimum(32, sizes) * sizes)  # under the grid's
    assert work_rank.gradient_bytes(10) == 160
    assert work_rank.gradient_flops(brute) == work_rank.OPS_A_PAIR * brute


def pair_by_pair(score, y, k):
    """One group, a double loop over its pairs: the description, literally."""
    n = len(score)
    order = sorted(range(n), key=lambda r: -score[r])  # stable
    gain = [2.0 ** y[r] - 1 for r in order]
    idcg = sum(g / math.log2(2 + p) for p, g in enumerate(sorted(gain)[::-1]))
    g, h = np.zeros(n), np.zeros(n)
    lam_sum = 0.0
    spread = max(score) != min(score)
    for i in range(min(k, n)):
        for j in range(i + 1, n):
            a, b = order[i], order[j]
            if y[a] == y[b]:
                continue
            hi, lo = (a, b) if y[a] > y[b] else (b, a)
            delta = abs((gain[i] - gain[j]) * (1 / math.log2(2 + i)
                                               - 1 / math.log2(2 + j))) / idcg
            if spread:
                delta /= abs(score[hi] - score[lo]) + 0.01
            p = 1 / (1 + math.exp(-(score[hi] - score[lo])))
            lam = (p - 1) * delta
            g[hi] += lam
            g[lo] -= lam
            for r in (hi, lo):
                h[r] += 2 * max(p * (1 - p), 1e-16) * delta
            lam_sum += -2 * lam
    scale = math.log2(1 + lam_sum) / lam_sum if lam_sum > 0 else 1.0
    return g * scale, h * scale


@pytest.mark.parametrize("n,k", [(2, 32), (9, 32), (40, 32), (40, 4)])
def test_reference_gradient_is_the_published_description_pair_by_pair(n, k):
    rng = np.random.default_rng(n + k)
    y = rng.integers(0, 5, n).astype(np.float64)
    for score in (rng.normal(size=n), np.round(rng.normal(size=n)), np.full(n, 0.5)):
        g, h = reference_rank.lambdarank_gpair(score, y, np.array([0, n]), k)
        g_want, h_want = pair_by_pair(list(score), list(y), k)
        np.testing.assert_allclose(g, g_want, rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(h, h_want, rtol=1e-9, atol=1e-12)
        assert abs(g.sum()) < 1e-9 * max(np.abs(g).sum(), 1)  # pairs cancel


def test_reference_ndcg_is_one_for_the_ideal_order_and_skips_empty_groups():
    y = np.array([3.0, 1, 0, 2, 0, 0, 0, 1.0, 4])
    ptr = np.array([0, 4, 7, 9])  # the middle group has no relevant document
    assert reference_rank.ndcg_at(y, y, ptr) == 1.0
    worst = reference_rank.ndcg_at(-y, y, ptr)
    assert 0 < worst < 1
