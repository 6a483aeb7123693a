"""The three per-layer metrics that read the program's span ring in-process
(benchmarks/metrics/sketch_s.py, round_host_s.py, to_host_ms.py), on a ring
filled by a tiny CPU train shaped like the train job's window."""
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

NAMES = ("sketch_s", "round_host_s", "to_host_ms")


@pytest.fixture
def ctx(run_ring):
    """A context as run.py builds it, after a train whose first rounds are
    set-up and whose next are traced (conftest.py's ``run_ring``)."""
    lines = []
    r = run_ring
    return {"cell": {"traffic": {"warm_rounds": r["warm"],
                                 "traced_rounds": r["traced"]}},
            "warm": r["warm"], "rounds": r["rounds"],
            "clocks": {"window_rounds": r["rounds"] - r["warm"],
                       "dmatrix_s": run_ring["dmatrix_s"],
                       "traced_round_s": [0.0] * r["traced"],
                       "round_s": run_ring["round_s"],
                       "round_max_s": max(run_ring["round_s"])},
            "log": lines.append, "lines": lines}


def readers():
    return {n: run.load_module("metrics", n) for n in NAMES}


def test_manifest_names_the_three_with_program_span_as_source():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    got = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-3:] == list(NAMES)
    for name, unit, layer, moves in (
            ("sketch_s", "s", "data: sketch + binning", "setup_s"),
            ("round_host_s", "s", "booster loop", "train_rate"),
            ("to_host_ms", "ms", "booster loop", "train_rate")):
        m = got[name]
        assert (m["unit"], m["layer"], m["moves"], m["source"], m["better"]) == (
            unit, layer, moves, "program_span", "lower")
        assert "workloads" not in m  # read in every cell that trains


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_a_finite_positive_number(ctx, name):
    value = readers()[name].read(ctx)
    assert value is not None and math.isfinite(value) and value > 0


def test_readers_stay_under_the_host_clocks_around_them(ctx):
    r = readers()
    assert r["sketch_s"].read(ctx) < ctx["clocks"]["dmatrix_s"]
    assert r["round_host_s"].read(ctx) < ctx["clocks"]["round_max_s"]
    assert r["to_host_ms"].read(ctx) * 1e-3 < r["round_host_s"].read(ctx)
    said = "\n".join(ctx["lines"])
    assert "compiled 0, loaded 0, traced 0" in said
    assert f"rounds {ctx['warm']} to {ctx['rounds'] - 1}" in said


def test_untraced_run_reads_every_window_round(ctx):
    """With no traced rounds the window's rounds all count."""
    ctx["clocks"]["traced_round_s"] = []
    got = run.load_module("metrics", "round_host_s").window_spans(ctx)
    assert list(got[0]) == list(range(ctx["warm"], ctx["rounds"]))


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_nothing_on_an_empty_ring(ctx, name):
    from xgboost_tpu.telemetry import flight

    flight.clear()
    assert readers()[name].read(ctx) is None
    assert ctx["lines"]  # and says why


def test_reader_gives_nothing_when_the_ring_lost_the_windows_first_round(ctx, monkeypatch):
    import collections

    from xgboost_tpu.telemetry import flight

    keep = list(flight._ring)[-20:]  # the last round and a half
    monkeypatch.setattr(flight, "_ring", collections.deque(keep, maxlen=20))
    r = readers()
    assert r["round_host_s"].read(ctx) is None
    assert r["to_host_ms"].read(ctx) is None
    assert "the ring holds rounds" in "\n".join(ctx["lines"])
