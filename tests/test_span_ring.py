"""The span ring (telemetry/spans.py ``recent``) and the compile counters
(telemetry/compile.py) that the program keeps with no switch."""
import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import xgboost_tpu as xtb
from xgboost_tpu import telemetry
from xgboost_tpu.telemetry import compile as compile_, flight, spans


def _data(rows=400, cols=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    return xtb.DMatrix(X, label=(X[:, 0] > 0).astype(np.float32))


PARAMS = {"objective": "binary:logistic", "max_depth": 3}


@pytest.fixture
def small_ring(monkeypatch):
    """A ring of 64 records in the place of the process's own."""
    monkeypatch.setattr(flight, "_ring", collections.deque(maxlen=64))
    return flight._ring


def test_recent_carries_parent_round_and_arguments():
    flight.clear()
    xtb.train(PARAMS, _data(), 3, verbose_eval=False)
    recs = spans.recent(round_from=1)
    assert {r["round"] for r in recs} == {1, 2}
    by_name = collections.defaultdict(list)
    for r in recs:
        by_name[r["name"]].append(r)
    assert [r["parent"] for r in by_name["update.gradient"]] == ["train.round"] * 2
    assert {r["parent"] for r in by_name["grow.to_host"]} == {"update.update_tree"}
    assert [r["depth"] for r in by_name["grow.build_hist+eval_split"]] == [0, 1, 2, 3] * 2
    # the slots a level was dispatched at: the interior levels of a depth-3
    # tree share one program, as wide as the widest of them
    assert [r["width"] for r in by_name["grow.build_hist+eval_split"]] == [1, 4, 4, 8] * 2
    assert all(r["copies"] == 12 for r in by_name["grow.to_host"])
    assert all("parent" not in r for r in by_name["train.round"])
    assert [r["round"] for r in by_name["train.after_iteration"]] == [1, 2]
    # a child lies inside its parent, on one clock
    rnd = by_name["train.round"][0]
    for r in recs:
        if r["round"] == 1 and r.get("parent") == "train.round":
            assert rnd["t0_ns"] <= r["t0_ns"]
            assert r["t0_ns"] + r["dur_ns"] <= rnd["t0_ns"] + rnd["dur_ns"]
    # by name, with no round asked: spans outside any round too
    d = xtb.QuantileDMatrix(np.zeros((64, 2), np.float32), label=np.zeros(64))
    assert d is not None
    build = spans.recent("dmatrix.build")[-1]
    assert "round" not in build and "parent" not in build
    assert [r["name"] for r in spans.recent() if r.get("parent") == "dmatrix.build"][-3:] \
        == ["dmatrix.upload", "dmatrix.sketch", "dmatrix.bin"]


def test_recent_on_a_wrapped_ring_returns_whole_rounds_only(small_ring):
    xtb.train(PARAMS, _data(), 8, verbose_eval=False)
    assert len(small_ring) == 64 and small_ring[0]["seq"] > 0  # it wrapped
    recs = spans.recent(round_from=0)
    rounds = sorted({r["round"] for r in recs})
    assert rounds and rounds[-1] == 7 and rounds[0] > 0  # fewer rounds ...
    assert rounds == list(range(rounds[0], 8))
    # (the boundary that closes a round's period is missing after the last,
    # and a collection of the oldest generation may fall into any)
    whole = collections.Counter(
        r["round"] for r in recs
        if r["name"] not in ("train.boundary", "host.gc"))
    assert len(set(whole.values())) == 1  # ... and every one of them whole
    per_round = whole[7]
    assert [r["round"] for r in recs if r["name"] == "train.boundary"] \
        == rounds[:-1]
    # the oldest round still in the ring has lost its first records
    oldest = small_ring[0].get("detail", {}).get("round")
    assert oldest is not None and oldest < rounds[0]
    # round, before_iteration, after_iteration; prepare, sync_margin,
    # gradient; update_tree, sample, class_gradient, setup; four levels;
    # margin, wait_device, to_host, from_grown
    assert per_round == 3 + 3 + 4 + 4 + 4
    assert spans.recent(round_from=8) == []
    assert spans.recent("train.round", round_from=6) == [
        r for r in recs if r["name"] == "train.round" and r["round"] >= 6]


def test_recent_is_empty_on_an_empty_ring(small_ring):
    assert spans.recent() == [] and spans.recent(round_from=0) == []


def test_second_identical_train_compiles_and_traces_nothing():
    d = _data(seed=4)
    xtb.train(PARAMS, d, 3, evals=[(d, "t")], verbose_eval=False)
    before = (telemetry.compiles_total(), telemetry.loads_total(),
              telemetry.traces_total())
    flight.clear()
    xtb.train(PARAMS, d, 3, evals=[(d, "t")], verbose_eval=False)
    assert (telemetry.compiles_total(), telemetry.loads_total(),
            telemetry.traces_total()) == before
    rounds = [r for r in spans.recent(round_from=0)
              if r["name"] in ("train.round", "train.after_iteration")]
    assert len(rounds) == 6
    assert all(r["compiled"] == r["loaded"] == r["traced"] == 0 for r in rounds)


def test_a_load_from_the_persistent_cache_is_not_a_compile(tmp_path):
    """Cold: compiled; the in-memory caches dropped and the same programs
    asked again against the persistent cache in tmp_path: loaded, and
    compiles_total() stands still."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()

        def f(x):
            return jnp.tanh(x) * 3.0 + jnp.arange(37.0)

        x = jnp.ones(37)
        flight.clear()
        with compile_.counting(spans.span("t_ring.cold", round=0)):
            jax.jit(f)(x).block_until_ready()
        jax.clear_caches()
        c0, l0 = telemetry.compiles_total(), telemetry.loads_total()
        with telemetry.compile_delta() as w:
            with compile_.counting(spans.span("t_ring.warm", round=1)):
                jax.jit(f)(x).block_until_ready()
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
    cold, = spans.recent("t_ring.cold")
    warm, = spans.recent("t_ring.warm")
    assert cold["compiled"] >= 1 and cold["loaded"] == 0 and cold["traced"] >= 1
    assert warm["compiled"] == 0 and warm["loaded"] >= 1 and warm["traced"] >= 1
    assert w.count == 0 and telemetry.compiles_total() == c0
    assert telemetry.loads_total() > l0
    events = [(e["name"], e["detail"].get("round")) for e in flight.events()
              if e["kind"] == "compile"]
    assert ("xla.compiled", 0) in events and ("xla.loaded", 1) in events
    assert ("xla.compiled", 1) not in events
    prom = telemetry.render_prometheus()
    assert 'xtb_compiles_total{kind="loaded"}' in prom
    assert 'xtb_compiles_total{kind="compiled"}' in prom
    assert "xtb_traces_total" in prom


def test_telemetry_callback_counts_dispatches_and_host_syncs():
    cb = telemetry.TelemetryCallback(enable_spans=False)
    was = spans.enabled()
    spans.disable()
    try:
        xtb.train(PARAMS, _data(seed=5), 3, callbacks=[cb], verbose_eval=False)
    finally:
        spans.enable(was)
    for rec in cb.history:
        # gradient + four levels (depth 3) + margin; one wait + twelve copies
        assert rec["dispatches"] == 1 + 4 + 1
        assert rec["host_syncs"] == 1 + 12
        assert rec["phases"]["grow.build_hist+eval_split"]["count"] == 4
        assert rec["phases"]["train.round"]["seconds"] > 0
