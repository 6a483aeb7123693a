"""The trace reduction on a hand-built trace, the byte count at the two
shapes of the benchmark, the table of peaks, and the readers that turn them
into shares: all arithmetic, no device."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run, trace, work  # noqa: E402

MS = 1e6  # nanoseconds


def planes():
    # two level programs and a margin update; a while op that nests its body
    ops = [("while.1", 0 * MS, 40 * MS), ("fusion.7", 5 * MS, 10 * MS),
           ("fusion.7", 20 * MS, 10 * MS), ("while.1", 50 * MS, 30 * MS),
           ("add.3", 90 * MS, 5 * MS)]
    modules = [("jit_level_step(17)", 0 * MS, 40 * MS),
               ("jit_level_step_padded(23)", 50 * MS, 30 * MS),
               ("jit_leaf_margin_delta(5)", 90 * MS, 5 * MS)]
    return {"/device:TPU:0": {trace.OPS_LINE: ops, trace.MODULES_LINE: modules,
                              "Steps": []}}


def test_busy_union_counts_nested_and_overlapping_once():
    assert trace.busy_union_ns([("a", 0, 10), ("b", 2, 3), ("c", 8, 6),
                                ("d", 20, 5), ("e", 20, 0)]) == 14 + 5


def test_reduction_of_a_hand_built_trace():
    got = trace.reduce_planes(planes(), window_s=0.100)
    assert got["busy_s"] == pytest.approx(0.075)
    assert got["window_s"] == 0.100 and got["chips"] == 1
    assert got["module_s"] == {"jit_level_step": pytest.approx(0.040),
                               "jit_level_step_padded": pytest.approx(0.030),
                               "jit_leaf_margin_delta": pytest.approx(0.005)}
    assert got["device_ops"][0] == ["while.1", pytest.approx(0.070)]
    gaps = dict(got["idle_gaps"])
    assert gaps == {"after jit_level_step": pytest.approx(0.010),
                    "after jit_level_step_padded": pytest.approx(0.010)}
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_no_device_operation_reduces_to_nothing():
    assert trace.reduce_planes({"/device:TPU:0": {trace.OPS_LINE: []}}, 1.0) is None
    assert trace.reduce_planes({}, 1.0) is None


@pytest.mark.parametrize("depth,level,whole", [(6, 1.323e9, 1.617e9),
                                               (8, 1.701e9, 1.995e9)])
def test_necessary_bytes_at_the_two_shapes(depth, level, whole):
    assert work.level_bytes(10_500_000, 28, depth) == pytest.approx(level, rel=1e-3)
    assert work.round_bytes(10_500_000, 28, depth) == pytest.approx(whole, rel=1e-3)
    peaks = work.load_peaks("TPU v5 lite")
    least, binds = work.least_seconds(work.round_bytes(10_500_000, 28, depth),
                                      work.round_flops(10_500_000, 28, depth),
                                      peaks)
    assert binds == "hbm_bytes_per_s"
    assert least == pytest.approx(whole / 819e9, rel=1e-3)


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        work.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.load_peaks("_source")
    with pytest.raises(KeyError):
        work.load_peaks("cpu")


def context(trace_summary):
    cell = run.load_cell("higgs-d6.train")
    clocks = {"rows": 10_500_000, "round_mean_s": 4.0, "round_max_s": 4.5,
              "traced_round_s": [4.1, 4.2], "dmatrix_s": 5.0, "setup_s": 40.0,
              "row_rounds": 10_500_000 * 7, "window_s": 28.0}
    return {"cell": cell, "config": cell["config"], "clocks": clocks,
            "trace": trace_summary, "device_kind": "TPU v5 lite",
            "log": lambda s: None}


def test_readers_compute_shares_from_the_one_byte_function():
    summary = {"busy_s": 6.0, "window_s": 8.0,
               "module_s": {"jit_level_step": 1.0, "jit_level_step_padded": 5.0,
                            "jit_leaf_margin_delta": 0.1}}
    ctx = context(summary)
    cell = ctx["cell"]
    got = run.read_metrics(cell, "per_layer", ctx)
    assert set(got) == {m["name"] for m in cell["manifest"]["per_layer"]}
    assert got["device_idle_pct"]["value"] == pytest.approx(25.0)
    need_level = work.level_bytes(10_500_000, 28, 6) / 819e9
    assert got["level_roofline"]["value"] == pytest.approx(100 * need_level / 3.0)
    need_round = work.round_bytes(10_500_000, 28, 6) / 819e9
    assert got["round_mfu"]["value"] == pytest.approx(100 * need_round / 4.0)
    assert got["round_max_s"] == {"value": 4.5, "unit": "s"}
    e2e = run.read_metrics(cell, "end_to_end", ctx)
    assert e2e["train_rate"]["value"] == pytest.approx(10.5 * 7 / 28.0)
    assert e2e["setup_s"]["value"] == 40.0


def test_a_reader_with_nothing_to_read_is_left_out_not_zero():
    got = run.read_metrics(context(None)["cell"], "per_layer", context(None))
    assert "level_roofline" not in got and "device_idle_pct" not in got
    empty = {"busy_s": 1.0, "window_s": 2.0, "module_s": {"jit_other": 1.0}}
    got = run.read_metrics(context(empty)["cell"], "per_layer", context(empty))
    assert "level_roofline" not in got and "round_mfu" in got


def test_judge_holds_every_number_to_its_limit():
    rows = run.judge({"a": 1e-6, "b": 2.0, "c": float("nan")},
                     {"a": 1e-5, "b": 1.0, "c": 1.0, "d": 1.0})
    assert [r[3] for r in rows] == [True, False, False, False]
