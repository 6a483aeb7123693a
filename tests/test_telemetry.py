"""Unified telemetry subsystem (xgboost_tpu/telemetry/): registry families,
span tracer, JSONL trace writer, Prometheus exposition, retrace accounting,
and the TelemetryCallback — plus the two SLO guard tests the ISSUE pins:
zero recompiles on a second identical train(), and a flag-off span that
touches the ring and the profiler annotation only."""
import json
import os
import threading

import numpy as np
import pytest

import xgboost_tpu as xtb
from xgboost_tpu import telemetry
from xgboost_tpu.telemetry import spans as _spans
from xgboost_tpu.telemetry import trace as _trace
from xgboost_tpu.telemetry.registry import Registry


@pytest.fixture(autouse=True)
def _spans_off_after():
    """Span enabling is process-wide: restore the pre-test flag so telemetry
    tests cannot leak instrumentation overhead into the rest of the suite."""
    was = _spans.enabled()
    tr = _trace.path()
    yield
    _spans.enable(was)
    _trace.configure(tr)


def _data(r=300, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(r, f)).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float32)
    return xtb.DMatrix(X, label=y)


# ====================================================================
# registry

def test_counter_gauge_basic():
    reg = Registry()
    c = reg.counter("t_total", "help", ("op",))
    c.labels("a").inc()
    c.labels("a").inc(2.5)
    c.labels(op="b").inc()
    assert c.get("a") == 3.5 and c.get("b") == 1
    g = reg.gauge("t_gauge", "help")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.get() == 6
    with pytest.raises(ValueError):
        c.labels("a").inc(-1)  # counters only go up
    with pytest.raises(ValueError):
        c.labels("a", "b")  # wrong label arity


def test_registry_get_or_create_idempotent_and_type_checked():
    reg = Registry()
    c1 = reg.counter("t_x", "h", ("l",))
    assert reg.counter("t_x", "h", ("l",)) is c1
    with pytest.raises(ValueError):
        reg.gauge("t_x")  # same name, different kind
    with pytest.raises(ValueError):
        reg.counter("t_x", "h", ("other",))  # same name, different labels
    with pytest.raises(ValueError):
        reg.counter("bad name!")
    with pytest.raises(ValueError):
        reg.counter("2xx_total")  # exposition format: no leading digit
    with pytest.raises(ValueError):
        # explicit +Inf bound would duplicate the overflow le="+Inf" sample
        reg.histogram("t_inf", "h", buckets=(1.0, float("inf")))


def test_histogram_buckets_and_prometheus_render():
    reg = Registry()
    h = reg.histogram("t_seconds", "h", ("phase",), buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.labels("p").observe(v)
    text = reg.render_prometheus()
    assert '# TYPE t_seconds histogram' in text
    # cumulative le counts: 1 under 0.1, 3 under 1, 4 under 10, 5 total
    assert 't_seconds_bucket{phase="p",le="0.1"} 1' in text
    assert 't_seconds_bucket{phase="p",le="1"} 3' in text
    assert 't_seconds_bucket{phase="p",le="10"} 4' in text
    assert 't_seconds_bucket{phase="p",le="+Inf"} 5' in text
    assert 't_seconds_count{phase="p"} 5' in text
    (_, (count, total)), = h.snapshot_sums().items()
    assert count == 5 and total == pytest.approx(56.05)


def test_registry_thread_safety():
    reg = Registry()
    c = reg.counter("t_mt", "h", ("w",))

    def work(i):
        child = c.labels(str(i % 4))
        for _ in range(500):
            child.inc()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(c.get(str(w)) for w in range(4)) == 4000


def test_prometheus_label_escaping():
    reg = Registry()
    reg.counter("t_esc", "h", ("p",)).labels('a"b\\c\nd').inc()
    line = [l for l in reg.render_prometheus().splitlines()
            if l.startswith("t_esc{")][0]
    assert line == 't_esc{p="a\\"b\\\\c\\nd"} 1'


# ====================================================================
# spans

def test_span_flag_off_ring_and_annotation_only(tmp_path, monkeypatch):
    """The flag-off guard: a span still opens its profiler annotation and
    leaves its record in the flight ring (both free), and touches neither
    the registry histogram nor the JSONL file the flag gates."""
    from xgboost_tpu.telemetry import flight

    opened = []

    class Annotation:
        def __init__(self, name, **kw):
            self.seen = [name, kw, "new"]
            opened.append(self.seen)

        def __enter__(self):
            self.seen[2] = "entered"

        def __exit__(self, *exc):
            self.seen[2] = "exited"

    monkeypatch.setattr(_spans._profiler, "TraceAnnotation", Annotation)
    path = tmp_path / "off.jsonl"
    _trace.configure(str(path))  # a destination alone turns the flag on ...
    _spans.disable()             # ... and this turns it off again
    before = _spans.phase_totals()
    children = dict(_spans._children)
    with _spans.span("guard.outer"):
        with _spans.span("guard.phase", depth=2):
            pass
    assert opened == [["guard.outer", {}, "exited"],
                      ["guard.phase", {"depth": 2}, "exited"]]
    inner, outer = _spans.recent("guard.phase")[-1], _spans.recent("guard.outer")[-1]
    assert inner["parent"] == "guard.outer" and inner["depth"] == 2
    assert "parent" not in outer and "round" not in outer
    assert outer["t0_ns"] <= inner["t0_ns"]
    assert inner["dur_ns"] <= outer["dur_ns"]
    assert [e["name"] for e in flight.events()[-2:]] == ["guard.phase",
                                                         "guard.outer"]
    # the hooks that ride the loop's top-level spans with no switch: the
    # clocks and the collector's totals (compile.counting), the collector's
    # own record of an old collection, the account and the loop's watch
    import gc

    from xgboost_tpu.telemetry import compile as _compile, pauses
    from xgboost_tpu.telemetry.registry import get_registry

    pauses.install()
    families = [f.name for f in get_registry().families()]
    watch = pauses.RoundWatch()
    for i in range(100, 104):  # rounds no earlier test has left in the ring
        with _compile.counting(watch.top(_spans.step_span("train.round", i))) as sp:
            watch.opened(sp)
            if i == 102:
                gc.collect()
        with _compile.counting(watch.top(_spans.span("train.after_iteration",
                                                      round=i))):
            pass
    watch.finished()
    watch.slow = (102, 0, 90, 30)
    watch.tell()  # the slow round's event and its one line
    assert [e["name"] for e in flight.events()[-1:]] == ["train.slow_round"]
    assert [a["round"] for a in _spans.round_account(100)] == [100, 101, 102, 103]
    assert _spans.recent("train.round")[-1]["cpu_ns"] >= 0
    assert any(r["generation"] == 2 and r.get("round") == 102
               for r in _spans.recent("host.gc"))
    assert ["host.gc", {"generation": 2}, "exited"] in opened  # on a profile too
    assert [f.name for f in get_registry().families()] == families
    assert _spans.phase_totals() == before
    assert _spans._children == children
    assert not path.exists()


def test_span_records_phase_histogram():
    _spans.enable()
    with _spans.span("t_unit.phase"):
        pass
    tot = _spans.phase_totals()["t_unit.phase"]
    assert tot["count"] >= 1 and tot["seconds"] >= 0
    assert 'phase="t_unit.phase"' in telemetry.render_prometheus()


def test_count_in_round_sums_on_the_round_span_and_nowhere_else():
    """Counters a grower adds land among the arguments of the round span
    that is open, summed over the round; outside a round nothing is kept."""
    from xgboost_tpu.telemetry import flight

    flight.clear()
    _spans.count_in_round(passes=9)  # no round open: dropped
    for i in (0, 1):
        with _spans.step_span("train.round", i):
            with _spans.span("update.update_tree"):
                _spans.count_in_round(passes=2, rows=100)
                _spans.count_in_round(passes=3, rows=50)
    _spans.count_in_round(passes=9)
    rounds = _spans.recent("train.round")
    assert [(r["round"], r["passes"], r["rows"]) for r in rounds] == [
        (0, 5, 150), (1, 5, 150)]
    assert "passes" not in _spans.recent("update.update_tree")[0]


def test_monitor_shim_reentrant_and_totals():
    """utils/timer.Monitor: stacked start/stop (the re-entrancy satellite)
    feeding the same phase histogram when telemetry is enabled."""
    from xgboost_tpu.utils.timer import Monitor

    _spans.enable()
    m = Monitor("t_mon")
    m.start("op")
    m.start("op")  # re-entrant: must NOT clobber the first bracket
    m.stop("op")
    m.stop("op")
    m.stop("op")  # unmatched: ignored
    assert m.counts["op"] == 2
    assert m.totals["op"] > 0
    tot = _spans.phase_totals()["t_mon.op"]
    assert tot["count"] >= 2


# ====================================================================
# trace writer

def test_trace_writer_jsonl_shape(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    _trace.configure(path)
    _spans.enable()
    try:
        with _spans.span("t_trace.alpha"):
            pass
        _spans.record_phase("t_trace.beta", 123_000, 456_000)
    finally:
        _trace.configure(None)
    lines = [json.loads(l) for l in open(path)]
    names = [l["name"] for l in lines]
    assert "t_trace.alpha" in names and "t_trace.beta" in names
    for rec in lines:
        assert rec["ph"] == "X"
        assert set(rec) >= {"name", "ph", "ts", "dur", "pid", "tid"}
        assert rec["pid"] == os.getpid()
    beta = lines[names.index("t_trace.beta")]
    assert beta["ts"] == pytest.approx(123.0) and beta["dur"] == pytest.approx(456.0)


# ====================================================================
# retrace accounting + train() integration

def test_compile_counter_counts_new_program_once():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x * 3 + 1

    x = jnp.arange(7, dtype=jnp.float32)
    f(x)  # ensure compiled before the measured window
    c0 = telemetry.compiles_total()
    f(x)  # cache hit: no compile event
    assert telemetry.compiles_total() == c0
    with telemetry.compile_delta() as w:
        f(jnp.arange(13, dtype=jnp.float32))  # new shape: must compile
    assert w.count >= 1


@pytest.mark.quick
def test_second_identical_train_zero_recompiles():
    """The training no-retrace SLO (ISSUE acceptance): every level program,
    gradient kernel, and eval predict compiled in the first train() must be
    a cache hit in a second identical run."""
    d = _data(seed=3)
    dv = _data(r=100, seed=4)
    p = {"objective": "binary:logistic", "max_depth": 3}
    xtb.train(p, d, 3, evals=[(dv, "val")], verbose_eval=False)
    with telemetry.compile_delta() as w:
        xtb.train(p, d, 3, evals=[(dv, "val")], verbose_eval=False)
    assert w.count == 0, f"second identical train() compiled {w.count} programs"


def test_telemetry_callback_history_and_steady_counter():
    d = _data(seed=5)
    cb = telemetry.TelemetryCallback()
    xtb.train({"objective": "binary:logistic", "max_depth": 3}, d, 4,
              evals=[(d, "train")], callbacks=[cb], verbose_eval=False)
    assert len(cb.history) == 4
    for i, rec in enumerate(cb.history):
        assert rec["round"] == i
        assert rec["seconds"] > 0
        assert len(rec["trees"]) == 1
        t = rec["trees"][0]
        assert t["nodes"] >= 1 and t["leaves"] >= 1 and t["depth"] <= 3
    # phase attribution present once spans are on (round 0 enables them)
    later = cb.history[-1]["phases"]
    assert any("build_hist" in k for k in later)
    assert any(k.startswith("eval.") for k in later)
    assert "update.gradient" in later and "update.update_tree" in later
    # warm-up compiles land in round 0; identical later rounds must not
    # retrace (the steady SLO) — second run of this test is fully warm,
    # so only assert steadiness, not that round 0 compiled
    assert cb.compiles_steady == 0
    assert all(r["compiles"] == 0 for r in cb.history[1:])


def test_telemetry_callback_reused_across_trains_resets_warmup():
    """A reused callback must treat each train() run's first round as
    warm-up: a second run with new shapes compiles its own level programs,
    and those must NOT land in the steady (SLO: 0) counter."""
    d = _data(r=256, f=5, seed=7)
    cb = telemetry.TelemetryCallback()
    xtb.train({"objective": "binary:logistic", "max_depth": 2}, d, 2,
              callbacks=[cb], verbose_eval=False)
    # different depth: fresh level programs -> warm-up compiles in round 0
    xtb.train({"objective": "binary:logistic", "max_depth": 5}, d, 2,
              callbacks=[cb], verbose_eval=False)
    assert len(cb.history) == 4
    assert cb.compiles_steady == 0, (
        f"second run's warm-up misclassified steady: {cb.compiles_steady}")


def test_trace_configure_enables_spans(tmp_path):
    """trace.configure(path) is the programmatic XGBOOST_TPU_TRACE: it must
    turn the span tracer on, or the capture holds only compile events."""
    _spans.disable()
    path = str(tmp_path / "cfg.jsonl")
    _trace.configure(path)
    try:
        assert _spans.enabled()
        with _spans.span("t_cfg.phase"):
            pass
    finally:
        _trace.configure(None)
    assert "t_cfg.phase" in {json.loads(l)["name"] for l in open(path)}


def test_telemetry_callback_under_cv_records_phases():
    """cv() drives the full callback lifecycle (before/after_training), so
    TelemetryCallback's span enabling fires and phases populate."""
    rng = np.random.default_rng(13)
    X = rng.normal(size=(180, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    d = xtb.DMatrix(X, label=y)
    cb = telemetry.TelemetryCallback()
    xtb.cv({"objective": "binary:logistic", "max_depth": 2}, d,
           num_boost_round=2, nfold=2, as_pandas=False, callbacks=[cb])
    assert len(cb.history) == 2
    assert cb.history[0]["phases"], "cv rounds recorded no phase spans"
    assert cb.history[0]["trees"] == []  # the cv aggregate has no .trees


def test_ten_round_train_prometheus_and_trace(tmp_path):
    """The ISSUE-2 end-to-end acceptance: 10 rounds with telemetry enabled
    produce per-phase histogram lines + compiles_total in the Prometheus
    text, and a parseable JSONL trace covering the phase vocabulary."""
    path = str(tmp_path / "train10.jsonl")
    _trace.configure(path)
    _spans.enable()
    try:
        d = _data(r=500, seed=6)
        xtb.train({"objective": "binary:logistic", "max_depth": 3}, d, 10,
                  evals=[(d, "train")], verbose_eval=False)
    finally:
        _trace.configure(None)
    prom = telemetry.render_prometheus()
    assert "xtb_phase_seconds_bucket" in prom
    assert "xtb_compiles_total" in prom
    assert 'phase="update.gradient"' in prom
    names = {json.loads(l)["name"] for l in open(path)}
    joined = "\n".join(names)
    for needle in ("build_hist", "eval_split", "update_tree", "eval."):
        assert needle in joined, f"{needle} missing from trace span names"


# ====================================================================
# serving rebase

def test_serving_metrics_feed_prometheus_registry():
    from xgboost_tpu.serving.metrics import ServingMetrics

    reg = telemetry.get_registry()
    req = reg.counter("xtb_serve_requests_total", "", ("model",))
    base = req.get("t_reg_model")
    m = ServingMetrics()
    m.observe_request("t_reg_model", rows=4, latency_ns=1_000_000)
    m.observe_batch("t_reg_model", rows=4, n_requests=1, exec_ns=2_000_000)
    m.observe_error("t_reg_model")
    m.queue_delta(16)
    m.queue_delta(-16)
    m.compiles_warmup += 2
    m.note_steady_compiles(1)
    snap = m.snapshot()
    assert snap["compiles_warmup"] == 2 and snap["compiles_steady"] == 1
    assert snap["models"]["t_reg_model"]["requests"] == 1
    assert req.get("t_reg_model") == base + 1
    prom = telemetry.render_prometheus()
    assert 'xtb_serve_rows_total{model="t_reg_model"} 4' in prom
    assert 'xtb_serve_errors_total{model="t_reg_model"} 1' in prom
    assert 'xtb_serve_batch_rows_bucket{model="t_reg_model",le="4"} 1' in prom
    assert 'xtb_compiles_steady{scope="serve"}' in prom


def test_trace_configure_truncates_previous_capture(tmp_path):
    """One capture = one process run: re-pointing the writer at a path must
    truncate, not append (perf_counter epochs differ across runs, so mixed
    captures render as garbage in chrome://tracing)."""
    path = str(tmp_path / "t.jsonl")
    _spans.enable()
    _trace.configure(path)
    _spans.record_phase("t_trunc.first", 0, 1000)
    _trace.configure(None)
    _trace.configure(path)  # a fresh capture at the same destination
    _spans.record_phase("t_trunc.second", 0, 1000)
    _trace.configure(None)
    names = [json.loads(l)["name"] for l in open(path)]
    assert names == ["t_trunc.second"]


def test_queue_gauge_sums_across_engines():
    """The process-wide queue gauge accumulates per-engine deltas: engine
    B going idle must not erase engine A's queued rows."""
    from xgboost_tpu.serving.metrics import ServingMetrics

    gauge = telemetry.get_registry().gauge("xtb_serve_queue_rows")
    base = gauge.get()
    a, b = ServingMetrics(), ServingMetrics()
    a.queue_delta(1000)
    b.queue_delta(5)
    b.queue_delta(-5)  # B drains: A's 1000 rows must stay visible
    assert gauge.get() == base + 1000
    a.queue_delta(-1000)
    assert gauge.get() == base


def test_serving_snapshot_shape_stable():
    """BENCH_SERVE.json contract: the snapshot dict shape survives the
    registry rebase bit-for-bit."""
    from xgboost_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.observe_request("m", rows=2, latency_ns=5_000_000)
    m.observe_batch("m", rows=2, n_requests=1, exec_ns=1_000_000)
    snap = m.snapshot()
    assert sorted(snap) == ["compiles_steady", "compiles_warmup", "models",
                            "queue_depth", "queue_peak"]
    assert sorted(snap["models"]["m"]) == [
        "batch_size_hist", "batches", "deadline", "errors", "latency_ms",
        "requests", "rows", "rows_per_s", "shed"]  # +degradation counters
    assert snap["models"]["m"]["shed"] == 0
    assert snap["models"]["m"]["deadline"] == 0
    assert sorted(snap["models"]["m"]["latency_ms"]) == ["p50", "p95", "p99"]


# ====================================================================
# EvaluationMonitor satellites

def test_evaluation_monitor_routes_through_logging(capsys):
    from xgboost_tpu.callback import EvaluationMonitor
    from xgboost_tpu.utils import logging as xlog

    lines = []
    xlog.register_log_callback(lines.append)
    try:
        mon = EvaluationMonitor()
        mon.after_iteration(None, 0, {"train": {"rmse": [0.5]}})
    finally:
        xlog.register_log_callback(None)
    assert lines == ["[0]\ttrain-rmse:0.50000"]
    assert capsys.readouterr().out == ""  # no bare print to stdout


def test_evaluation_monitor_show_stdv_and_tuple_scores():
    from xgboost_tpu.callback import EvaluationMonitor

    lines = []
    mon = EvaluationMonitor(show_stdv=True, logger=lines.append)
    mon.after_iteration(None, 0, {"test": {"rmse": [(0.5, 0.1)]}})
    assert lines == ["[0]\ttest-rmse:0.50000+0.10000"]
    lines.clear()
    mon = EvaluationMonitor(show_stdv=False, logger=lines.append)
    mon.after_iteration(None, 0, {"test": {"rmse": [(0.5, 0.1)]}})
    assert lines == ["[0]\ttest-rmse:0.50000"]


def test_evaluation_monitor_period_flushes_final_round():
    """period > 1 must still log the LAST round's scores (the reference
    caches the off-period line and flushes it in after_training)."""
    from xgboost_tpu.callback import EvaluationMonitor

    lines = []
    mon = EvaluationMonitor(period=5, logger=lines.append)
    for epoch in range(12):
        mon.after_iteration(None, epoch, {"t": {"rmse": [float(epoch)]}})
    mon.after_training(None)
    assert lines[-1] == "[11]\tt-rmse:11.00000"  # final round flushed
    assert [l.split("]")[0] + "]" for l in lines] == ["[0]", "[5]", "[10]",
                                                     "[11]"]


def test_evaluation_monitor_honours_rank():
    from xgboost_tpu.callback import EvaluationMonitor

    lines = []
    mon = EvaluationMonitor(rank=1, logger=lines.append)  # we are rank 0
    mon.after_iteration(None, 0, {"train": {"rmse": [0.5]}})
    assert lines == []


def test_cv_verbose_show_stdv_and_early_stopping():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(240, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    d = xtb.DMatrix(X, label=y)
    lines = []
    from xgboost_tpu.callback import EvaluationMonitor

    out = xtb.cv({"objective": "binary:logistic", "max_depth": 2}, d,
                 num_boost_round=4, nfold=3, as_pandas=False,
                 callbacks=[EvaluationMonitor(show_stdv=True,
                                              logger=lines.append)],
                 early_stopping_rounds=3)
    assert len(out["test-logloss-mean"]) >= 1
    assert lines and "+" in lines[0].split("\t")[1]  # mean+std rendered
