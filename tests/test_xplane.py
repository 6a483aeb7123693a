"""telemetry/xplane.py: the program's reader of its own names in a profile.

Three layers: the arithmetic on hand-built event lists (nested ``while``,
overlapping spans); a profile taken here on the CPU, which has the program's
spans on a host plane (and no device plane: the CPU's operations are host
events); and a recorded profile of a toy train on a TPU v5e
(tests/data/tpu_v5e_toy_train.xplane.pb.gz: 100,000 x 28 rows, depth 4, rounds
2 and 3 and one QuantileDMatrix build, taken by PR 26's builder with the
benchmark's ProfileOptions), which has the scopes."""
import os

import numpy as np
import pytest

import jax

import xgboost_tpu as xtb
from xgboost_tpu.telemetry import xplane

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_v5e_toy_train.xplane.pb.gz")
MS = 1e6  # ns


def op(name, start_ms, dur_ms, op_name=""):
    return (name, start_ms * MS, dur_ms * MS, op_name)


# --------------------------------------------------------------- arithmetic
def test_scope_of_takes_the_outermost_scope():
    assert xplane.scope_of("jit(level_step)/jit(main)/hist/while/body/dot") == "hist"
    assert xplane.scope_of("jit(f)/route/jit(take_along_axis)/gather:") == "route"
    assert xplane.scope_of("jit(sigmoid)/div:") == xplane.UNSCOPED
    assert xplane.scope_of("") == xplane.UNSCOPED


def test_nested_while_is_counted_once_and_inherits_its_body_scope():
    ops = [
        op("while.3", 0, 10),                        # XLA names no scope on it
        op("fusion.1", 0, 4, "jit(f)/hist/dot_general"),
        op("copy.1", 4, 1),                          # unnamed, inside the while
        op("fusion.2", 5, 4, "jit(f)/hist/add"),     # 1 ms of the while is its own
        op("fusion.11", 10, 6, "jit(f)/route/gather"),
        op("fusion.9", 16, 2, "jit(f)/split/argsort"),
        op("copy.7", 18, 1),                         # unnamed at top level
    ]
    got = xplane.seconds_by_scope(ops)
    assert got == pytest.approx({"hist": 0.010, "route": 0.006,
                                 "split": 0.002, xplane.UNSCOPED: 0.001})
    assert sum(got.values()) == pytest.approx(0.019)  # every ns once


def test_named_parent_gives_its_scope_to_unnamed_children():
    ops = [op("while.1", 0, 8, "jit(f)/bin/while"),
           op("copy.2", 1, 2), op("fusion.3", 3, 2, "jit(f)/bin/lt")]
    assert xplane.seconds_by_scope(ops) == pytest.approx({"bin": 0.008})


def test_self_seconds_overlapping_children_cover_once():
    spans = [("train.round", 0, 100 * MS),
             ("update.update_tree", 10 * MS, 60 * MS),
             ("grow.wait_device", 20 * MS, 30 * MS),
             ("grow.to_host", 50 * MS, 10 * MS),
             # two children of the round that overlap (another thread's clock
             # skew): their union, 25 ms, is covered once
             ("eval.predict", 72 * MS, 15 * MS),
             ("eval.eval_set", 80 * MS, 17 * MS)]
    got = xplane.self_seconds(spans)
    assert got["train.round"]["self_s"] == pytest.approx(0.100 - 0.060 - 0.025)
    assert got["update.update_tree"]["self_s"] == pytest.approx(0.020)
    assert got["grow.wait_device"] == {"count": 1, "total_s": pytest.approx(0.030),
                                       "self_s": pytest.approx(0.030)}


def test_idle_gaps_and_the_span_live_as_each_opened():
    ops = [op("a", 0, 10), op("b", 10.02, 5), op("c", 20, 10)]
    gaps = xplane.idle_gaps(ops, 0, 32 * MS)
    assert gaps == [(pytest.approx(15.02 * MS), pytest.approx(4.98 * MS)),
                    (pytest.approx(30 * MS), pytest.approx(2 * MS))]
    # the 20 us gap is the device's own turn-around: under GAP_NS
    assert sum(d for _, d in xplane.idle_gaps(ops, 0, 32 * MS, least_ns=0)) \
        == pytest.approx(32 * MS - 25 * MS)
    spans = [("train.round", 0, 31 * MS), ("update.gradient", 14 * MS, 3 * MS)]
    assert xplane.innermost(spans, gaps[0][0]) == "update.gradient"
    assert xplane.innermost(spans, gaps[1][0]) == "train.round"
    assert xplane.innermost(spans, 31.5 * MS) == xplane.NO_SPAN


def test_reduce_splits_scopes_by_module_and_launches_by_span():
    devices = {"/device:TPU:0": {
        "ops": [op("while.3", 0, 10), op("f.1", 1, 8, "jit(g)/hist/dot"),
                op("f.2", 10, 5, "jit(g)/route/gather"),
                op("div", 20, 1, "jit(sigmoid)/div:")],
        "modules": [("jit_level_step(77)", 0, 15 * MS, 1),
                    ("jit_sigmoid(78)", 20 * MS, 1 * MS, 2)]}}
    host = {"/host:CPU/python": {
        "spans": [("train.round", 0, 30 * MS),
                  ("update.gradient", 17 * MS, 2 * MS)],
        "steps": [("train.round", 4, 0, 30 * MS)]}}
    out = xplane.reduce(devices, host, launches={1: 0.5 * MS, 2: 18 * MS})
    dev = out["devices"]["/device:TPU:0"]
    assert dev["busy_s"] == pytest.approx(0.016)
    assert dev["module_scope_s"]["jit_level_step"] == pytest.approx(
        {"hist": 0.010, "route": 0.005})
    assert dev["module_scope_s"]["jit_sigmoid"] == pytest.approx(
        {xplane.UNSCOPED: 0.001})
    assert dev["launched_by_span_s"] == pytest.approx(
        {"train.round": 0.015, "update.gradient": 0.001})
    assert dev["idle_by_span_s"] == pytest.approx({"train.round": 0.005})
    assert dev["idle_gap_s"] == pytest.approx(dev["window_s"] - dev["busy_s"])
    assert out["host"]["/host:CPU/python"]["steps"][0]["step_num"] == 4


# ------------------------------------------------- a profile taken on the CPU
SPANS_OF_A_ROUND = {
    "train.round", "train.after_iteration", "update.gradient",
    "update.update_tree", "grow.build_hist+eval_split", "grow.margin",
    "grow.wait_device", "grow.to_host", "tree.from_grown", "eval.eval_set",
    "eval.predict"}


def test_profile_holds_the_programs_spans_with_no_switch(tmp_path):
    """jax.profiler.trace around two rounds, telemetry.enable() never called:
    every span of a round is on a host plane of the profile, and the round
    spans carry their step numbers."""
    from xgboost_tpu.telemetry import spans

    assert not spans.enabled()
    rng = np.random.default_rng(3)
    X = rng.normal(size=(2000, 6)).astype(np.float32)
    d = xtb.DMatrix(X, label=(X[:, 0] > 0).astype(np.float32))
    params = {"objective": "binary:logistic", "max_depth": 3}
    xtb.train(params, d, 1, evals=[(d, "train")], verbose_eval=False)  # warm
    with jax.profiler.trace(str(tmp_path)):
        xtb.train(params, d, 2, evals=[(d, "train")], verbose_eval=False)
    summary = xplane.summarize(str(tmp_path))
    threads = [t for t in summary["host"].values() if t["steps"]]
    assert len(threads) == 1
    assert [(s["name"], s["step_num"]) for s in threads[0]["steps"]] == [
        ("train.round", 0), ("train.round", 1)]
    got = threads[0]["spans"]
    assert SPANS_OF_A_ROUND <= set(got)
    assert got["train.round"]["count"] == 2
    assert got["grow.build_hist+eval_split"]["count"] == 2 * 4
    for rec in got.values():
        assert 0 <= rec["self_s"] <= rec["total_s"]
    # a round's self time is what its children leave
    assert got["train.round"]["self_s"] < got["train.round"]["total_s"]
    assert xplane.render(summary).count("train.round") >= 1


# ------------------------------------------- a profile recorded on a TPU v5e
@pytest.fixture(scope="module")
def recorded():
    return xplane.summarize(RECORDED)


def test_recorded_tpu_profile_scopes_and_modules(recorded):
    (plane, dev), = recorded["devices"].items()
    assert plane == "/device:TPU:0"
    assert {"hist", "split", "record", "route", "margin", "bin"} <= set(dev["scope_s"])
    assert sum(dev["scope_s"].values()) == pytest.approx(dev["busy_s"], rel=1e-6)
    for module in ("jit_level_step", "jit_level_step_padded"):
        by = dev["module_scope_s"][module]
        assert by.get(xplane.UNSCOPED, 0.0) < 0.01 * sum(by.values())
        assert {"hist", "split", "record", "route"} <= set(by)
    assert set(dev["module_scope_s"]["jit_leaf_margin_delta"]) <= {
        "margin", xplane.UNSCOPED}
    assert dev["module_scope_s"]["jit__bin"]["bin"] == pytest.approx(
        dev["module_s"]["jit__bin"], rel=1e-3)
    # the gradient's eager programs are modules of their own, outside every
    # scope: a scope opened outside a jitted function reaches no program
    assert set(dev["module_scope_s"]["jit_sigmoid"]) == {xplane.UNSCOPED}


def test_recorded_tpu_profile_spans_gaps_and_launches(recorded):
    dev = recorded["devices"]["/device:TPU:0"]
    host = recorded["host"]["/host:CPU/python"]
    assert [s["step_num"] for s in host["steps"]] == [2, 3]
    assert {"dmatrix.build", "dmatrix.upload", "dmatrix.sketch", "dmatrix.bin",
            "grow.wait_device", "grow.to_host"} <= set(host["spans"])
    # gaps of GAP_NS or more are nearly all of the idle time, each under a span
    idle = dev["window_s"] - dev["busy_s"]
    assert 0.99 * idle < dev["idle_gap_s"] <= idle
    assert sum(dev["idle_by_span_s"].values()) == pytest.approx(dev["idle_gap_s"])
    assert dev["idle_by_span_s"].get(xplane.NO_SPAN, 0.0) < 0.001 * idle
    # every run of a module was enqueued under some span of the program
    assert sum(dev["launched_by_span_s"].values()) == pytest.approx(
        sum(dev["module_s"].values()), rel=1e-3)
    assert dev["launched_by_span_s"]["dmatrix.bin"] == pytest.approx(
        dev["module_s"]["jit__bin"], rel=1e-2)


def test_command_line_prints_the_tables(capsys):
    assert xplane.main([RECORDED]) == 0
    out = capsys.readouterr().out
    assert "device seconds by scope" in out and "hist" in out
    assert xplane.main([]) == 2
