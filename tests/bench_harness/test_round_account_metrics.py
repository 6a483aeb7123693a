"""The three per-layer metrics that read the program's account of a round's
period (benchmarks/metrics/gc_pause_ms.py, round_unnamed_pct.py,
round_offcpu_ms.py): on records built by hand, on a ring filled by a tiny CPU
train shaped like the train job's window, and on a program that has no
account or no counters: nothing, never nought."""
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

NAMES = ("gc_pause_ms", "round_unnamed_pct", "round_offcpu_ms")
MS = 1_000_000


def context(warm=2, traced=2, rounds=7):
    lines = []
    return {"cell": {"traffic": {"warm_rounds": warm, "traced_rounds": traced}},
            "clocks": {"window_rounds": rounds - warm,
                       "traced_round_s": [1.0] * traced},
            "log": lines.append, "lines": lines}


def hand_ring(rounds=7, counters=True, gc_ms=(0, 0, 0, 0, 3, 1, 40), spun_ms=0,
              wait_clock=True):
    """``rounds`` rounds of 1,000 ms in the program's ring: a round span of
    900 (a tree step of 880 holding a wait of 800, a copy of 10 and a named
    set-up of 30) and callbacks of 50 ms, 40 ms after it.  The wait spins on
    the CPU for ``spun_ms`` and says so (``wait_clock``) or does not."""
    from xgboost_tpu.telemetry import flight

    def put(name, t0, dur, **detail):
        flight.record("span", name, t0_ns=t0, dur_ns=dur, **detail)

    flight.clear()
    for i in range(rounds):
        t0, seq0 = (10 + i) * 1000 * MS, flight.seq()
        grown = {"gc.ns": gc_ms[i] * MS, "gc.collections": 2, "gc.gen2": 0,
                 "ctx_invol": 1, "majflt": 0} if counters else {}
        clock = (lambda ms: {"cpu_ns": ms * MS}) if counters else (lambda ms: {})
        put("grow.setup", t0 + 15 * MS, 30 * MS, round=i, parent="update.update_tree")
        put("grow.wait_device", t0 + 50 * MS, 800 * MS, round=i, parent="update.update_tree",
            **(clock(spun_ms) if wait_clock else {}))
        put("grow.to_host", t0 + 850 * MS, 10 * MS, round=i, parent="update.update_tree")
        put("update.update_tree", t0 + 10 * MS, 880 * MS, round=i, parent="train.round")
        put("train.round", t0, 900 * MS, round=i, seq0=seq0, compiled=0, loaded=0,
            traced=0, **clock(60 + spun_ms), **grown)
        put("train.after_iteration", t0 + 940 * MS, 50 * MS, round=i, compiled=0,
            loaded=0, traced=0, **clock(20))


def readers():
    return {n: run.load_module("metrics", n) for n in NAMES}


def test_manifest_names_the_three_for_the_booster_loop():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    got = {m["name"]: m for m in manifest["per_layer"]}
    for name, unit, source in (("gc_pause_ms", "ms", "program_counter"),
                               ("round_unnamed_pct", "%", "program_span"),
                               ("round_offcpu_ms", "ms", "program_counter")):
        m = got[name]
        assert (m["unit"], m["source"], m["better"], m["layer"], m["moves"]) == (
            unit, source, "lower", "booster loop", "train_rate")
        # every cell it lists reports the metric it moves
        cells = {w["name"] for w in manifest["workloads"]}
        assert set(m.get("workloads", cells)) <= cells
        for cell in m.get("workloads", cells):
            assert "train_rate" in {
                e["name"] for e in run.cell_metrics(run.load_cell(cell), "end_to_end")}


def test_readers_on_hand_built_records():
    hand_ring()
    ctx = context()
    got = {n: r.read(ctx) for n, r in readers().items()}
    # the window's untraced rounds are 4, 5, 6; the last, 6, which no round
    # follows, is left out (and its 40 ms of collection with it)
    assert got["gc_pause_ms"] == pytest.approx((3 + 1) / 2)
    # unnamed: the round's 20 and the tree step's 40 of self time, the
    # callbacks' 50, and 50 between the spans, of the period's 1,000 less 800
    assert got["round_unnamed_pct"] == pytest.approx(100 * 160 / 200)
    # 1,000 less the wait and the copy (810) less 80 on the CPU
    assert got["round_offcpu_ms"] == pytest.approx(110.0)
    log = "\n".join(ctx["lines"])
    assert "2 periods partitioned exactly" in log and "grow.setup 30.000" in log
    assert log.count("partitioned exactly") == 1  # accounted once, read thrice


def test_cpu_burnt_inside_a_wait_is_not_counted_twice():
    hand_ring(gc_ms=(0,) * 7, spun_ms=500)
    ctx = context()
    # the wait spun for 500 of its 800 ms: the remainder is what it was
    assert readers()["round_offcpu_ms"].read(ctx) == pytest.approx(110.0)
    assert "500.000 of it inside the waits" in "\n".join(ctx["lines"])
    assert readers()["gc_pause_ms"].read(context()) == 0.0
    # waits that carry no clock of their own: the subtraction does not hold,
    # and the reading says so: as it comes out, and a line in the log
    hand_ring(gc_ms=(0,) * 7, spun_ms=500, wait_clock=False)
    ctx = context()
    assert readers()["round_offcpu_ms"].read(ctx) == pytest.approx(-390.0)
    assert "is below nought" in ctx["lines"][-1]


def test_records_without_the_counters_give_nothing_but_the_share():
    hand_ring(counters=False)
    ctx = context()
    got = {n: r.read(ctx) for n, r in readers().items()}
    assert got["gc_pause_ms"] is None and got["round_offcpu_ms"] is None
    assert got["round_unnamed_pct"] == pytest.approx(80.0)  # spans alone


def test_a_program_without_the_account_or_the_rounds_gives_nothing(monkeypatch):
    hand_ring(rounds=5)  # the window's rounds 5 and 6 never ran
    ctx = context()
    assert all(r.read(ctx) is None for r in readers().values())
    assert "the ring holds rounds" in "\n".join(ctx["lines"])
    hand_ring()
    from xgboost_tpu.telemetry import spans

    monkeypatch.delattr(spans, "round_account")
    ctx = context()
    assert all(r.read(ctx) is None for r in readers().values())
    assert "no spans.round_account" in ctx["lines"][0]


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_real_train_gives_a_finite_number(run_ring, name):
    r = run_ring
    ctx = context(r["warm"], r["traced"], r["rounds"])
    value = readers()[name].read(ctx)
    assert value is not None and math.isfinite(value) and value >= 0
    if name == "round_unnamed_pct":
        assert value < 100
