"""Performance observatory (ISSUE 17): sampling profiler, latency
exemplars, endpoint additions, and source staleness.

Quick tier: profiler lifecycle (idempotent start/stop, env gating,
fork-safe module state), folded-stack capture and the merged flame view,
exemplar observe -> render -> snapshot -> merged-render round-trip,
``/healthz`` + ``/flight`` endpoints and the unchanged 404 contract,
``stale="1"`` relabeling, and the headline determinism guarantee:
training with the profiler armed is bitwise-identical to training with
it off (sampling only reads frames).  Slow tier: a real 2-replica fleet
ships folded stacks from both replica processes into one merged view.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from xgboost_tpu.telemetry import distributed, profiler
from xgboost_tpu.telemetry.registry import Registry


@pytest.fixture(autouse=True)
def _profiler_reset():
    """Every test starts and ends with the sampler stopped and empty."""
    profiler.stop()
    profiler.clear()
    yield
    profiler.stop()
    profiler.clear()


# =========================================================================
# lifecycle


def test_start_stop_idempotent():
    assert profiler.start(hz=100) is True
    assert profiler.running()
    assert profiler.start(hz=100) is True  # second start: same sampler
    threads = [t for t in threading.enumerate()
               if t.name == "xtb-prof-sampler"]
    assert len(threads) == 1
    profiler.stop()
    assert not profiler.running()
    profiler.stop()  # second stop is a no-op
    assert not profiler.running()


def test_zero_hz_disables(monkeypatch):
    assert profiler.start(hz=0) is False
    assert not profiler.running()
    monkeypatch.setenv(profiler.ENV_HZ, "0")
    assert profiler.maybe_start() is False
    assert not profiler.running()


def test_configured_hz_parsing(monkeypatch):
    monkeypatch.delenv(profiler.ENV_HZ, raising=False)
    assert profiler.configured_hz() == profiler.DEFAULT_HZ
    monkeypatch.setenv(profiler.ENV_HZ, "2.5")
    assert profiler.configured_hz() == 2.5
    monkeypatch.setenv(profiler.ENV_HZ, "not-a-number")
    assert profiler.configured_hz() == profiler.DEFAULT_HZ
    monkeypatch.setenv(profiler.ENV_HZ, "-3")
    assert profiler.configured_hz() == 0.0


def test_sampler_captures_named_thread_stacks():
    stop = threading.Event()

    def very_distinctive_busy_fn():
        while not stop.is_set():
            sum(range(200))

    t = threading.Thread(target=very_distinctive_busy_fn,
                         name="busy-worker", daemon=True)
    t.start()
    try:
        profiler.start(hz=200)
        deadline = time.monotonic() + 5
        while profiler.samples() < 5 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        profiler.stop()
        stop.set()
        t.join(5)
    snap = profiler.folded_snapshot()
    assert snap is not None and snap["samples"] >= 5
    assert snap["pid"] > 0
    busy = [k for k in snap["stacks"] if k.startswith("busy-worker;")]
    assert busy, f"no busy-worker stacks in {list(snap['stacks'])[:5]}"
    assert any("very_distinctive_busy_fn" in k for k in busy)


def test_folded_snapshot_none_when_never_sampled():
    assert profiler.folded_snapshot() is None
    payload = distributed.snapshot_payload()
    assert "profile" not in payload


def test_clear_resets_but_keeps_sampler():
    profiler.start(hz=200)
    deadline = time.monotonic() + 5
    while profiler.samples() < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    profiler.clear()
    assert profiler.running()
    profiler.stop()


# =========================================================================
# the last ticks: the sampler as a witness of what held the lock


def _wait_for_ticks(n, seconds=10):
    deadline = time.monotonic() + seconds
    while len(profiler.ticks()) < n and time.monotonic() < deadline:
        time.sleep(0.01)
    return profiler.ticks()


def test_ticks_are_bounded_and_carry_the_arming_threads_frames():
    def distinctive_armer_fn():
        assert profiler.start(hz=500)  # the thread that arms is the watched
        return _wait_for_ticks(profiler._MAX_TICKS + 8)

    try:
        got = distinctive_armer_fn()
        assert profiler.samples() > profiler._MAX_TICKS
    finally:
        profiler.stop()
    assert len(got) == profiler._MAX_TICKS == 64
    woke = [t[0] for t in got]
    assert woke == sorted(woke) and woke[-1] <= time.perf_counter_ns()
    assert all(late >= 0 for _, late, _ in got)
    assert all(len(frames) <= profiler._TICK_FRAMES for _, _, frames in got)
    # innermost first: the waiting helper, then its caller
    seen = [frames for _, _, frames in got if frames]
    assert seen and all(f[0].startswith("test_profiler:") for f in seen)
    assert any(f[:2] == ("test_profiler:_wait_for_ticks",
                         "test_profiler:distinctive_armer_fn") for f in seen)
    mid = woke[len(woke) // 2]
    assert [t[0] for t in profiler.ticks(since_ns=mid)] == [w for w in woke if w >= mid]
    assert [t[0] for t in profiler.ticks(until_ns=mid)] == [w for w in woke if w < mid]


def test_a_held_interpreter_lock_shows_as_a_late_tick():
    profiler.start(hz=100)
    try:
        _wait_for_ticks(3)
        profiler.clear()
        t0 = time.perf_counter_ns()
        sum(range(20_000_000))  # one C call: the lock is not let go inside it
        held = time.perf_counter_ns() - t0
        late = max(t[1] for t in _wait_for_ticks(2))
    finally:
        profiler.stop()
    assert held > 50_000_000
    assert late >= held // 2  # the tick that was due inside it woke after it


def test_ticks_survive_clear_and_fork_as_the_stacks_do():
    profiler.start(hz=200)
    try:
        assert _wait_for_ticks(3)
        profiler.clear()  # empties both; the sampler keeps running and refills
        assert profiler.running()
        assert _wait_for_ticks(3) and profiler.samples() >= 3
    finally:
        profiler.stop()
    kept = profiler.ticks()
    # the fork handlers: the lock is held across a fork and released on both
    # sides, the child drops the sampler's handle and keeps the module state
    profiler._lock.acquire()
    profiler._after_fork_child()
    assert not profiler.running()
    assert profiler.ticks() == kept and len(kept) <= profiler._MAX_TICKS
    assert profiler.start(hz=200)  # the child's own sampler appends to it
    try:
        assert len(_wait_for_ticks(len(kept) + 1)) > len(kept) or len(kept) == 64
    finally:
        profiler.stop()


# =========================================================================
# merged flame view


def _fake_profile(pid, stacks):
    return {"pid": pid, "label": "x", "hz": 5.0,
            "samples": sum(stacks.values()), "stacks": stacks}


def test_merged_folded_prefixes_sources(tmp_path):
    m = distributed.get_merged()
    # hermetic against suite order: earlier fleet/distributed tests may
    # have left profile-bearing sources in the merged singleton
    for src in list(m.profiles()):
        m.forget(src)
    m.ingest_payload("replicaA", {
        "profile": _fake_profile(111, {"MainThread;a:f;b:g": 7})})
    m.ingest_payload("replicaB", {
        "profile": _fake_profile(222, {"MainThread;a:f;b:g": 3})})
    try:
        folded = profiler.merged_folded(include_local=False)
        assert folded["replicaA/111;MainThread;a:f;b:g"] == 7
        assert folded["replicaB/222;MainThread;a:f;b:g"] == 3
        text = profiler.render_folded(str(tmp_path / "folded.txt"),
                                      include_local=False)
        assert "10 weighted samples" in text
        lines = (tmp_path / "folded.txt").read_text().splitlines()
        assert "replicaA/111;MainThread;a:f;b:g 7" in lines
        assert "replicaB/222;MainThread;a:f;b:g 3" in lines
    finally:
        m.forget("replicaA")
        m.forget("replicaB")


def test_payload_ships_profile_when_sampled():
    profiler.start(hz=200)
    deadline = time.monotonic() + 5
    while profiler.samples() < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    profiler.stop()
    payload = distributed.snapshot_payload()
    assert payload["profile"]["samples"] >= 2
    json.dumps(payload)  # shippable as-is


# =========================================================================
# latency exemplars


def test_exemplar_renders_on_local_histogram():
    r = Registry()
    h = r.histogram("xtb_t_seconds", "latency", ("model",),
                    buckets=(0.015, 1.0))
    h.labels("m").observe(0.01, exemplar="tr-low")
    h.labels("m").observe(5.0, exemplar="tr-inf")
    text = r.render_prometheus()
    assert ('xtb_t_seconds_bucket{model="m",le="0.015"} 1 '
            '# {trace="tr-low"} 0.01') in text
    assert ('xtb_t_seconds_bucket{model="m",le="+Inf"} 2 '
            '# {trace="tr-inf"} 5') in text
    # the exemplar keeps the max-latency observation per bucket
    h.labels("m").observe(0.012, exemplar="tr-bigger")
    text = r.render_prometheus()
    assert '# {trace="tr-bigger"} 0.012' in text
    assert "tr-low" not in text


def test_exemplar_roundtrip_through_merged_registry():
    def mk(v, trace):
        r = Registry()
        r.histogram("xtb_t_seconds", "latency", ("model",),
                    buckets=(0.015, 1.0)).labels("m").observe(
                        v, exemplar=trace)
        return r.snapshot()

    m = distributed.MergedRegistry()
    m.ingest("r0", mk(0.2, "pid0-a"))
    m.ingest("r1", mk(0.9, "pid1-b"))
    text = m.render_prometheus(include_local=False)
    # per-process rows keep their own exemplars
    assert ('xtb_t_seconds_bucket{proc="r0",model="m",le="1"} 1 '
            '# {trace="pid0-a"} 0.2') in text
    assert ('xtb_t_seconds_bucket{proc="r1",model="m",le="1"} 1 '
            '# {trace="pid1-b"} 0.9') in text
    # the merged row carries the max-value exemplar across sources
    assert ('\nxtb_t_seconds_bucket{model="m",le="1"} 2 '
            '# {trace="pid1-b"} 0.9') in text


def test_histogram_without_exemplars_renders_unchanged():
    r = Registry()
    r.histogram("xtb_t_seconds", "latency", buckets=(1.0,)).observe(0.5)
    text = r.render_prometheus()
    assert '\nxtb_t_seconds_bucket{le="1"} 1\n' in text
    assert "trace=" not in text
    snap = r.snapshot()
    (fam,) = [f for f in snap["families"]
              if f["name"] == "xtb_t_seconds"]
    assert len(fam["children"][0]) == 4  # no 5th exemplar element


# =========================================================================
# endpoints: /healthz, /flight, 404 contract, staleness


def test_healthz_reports_source_staleness():
    m = distributed.MergedRegistry()
    m.ingest("fresh", Registry().snapshot())
    m.ingest("dead", Registry().snapshot())
    m._sources["dead"]["t"] = time.monotonic() - 10_000
    srv = distributed.MetricsServer(0, merged=m,
                                    include_local=False).start()
    try:
        body = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=10).read())
        assert body["status"] == "ok" and body["pid"] > 0
        assert body["stale_after_s"] == pytest.approx(
            3.0 * distributed.ship_interval())
        assert body["sources"]["fresh"]["stale"] is False
        assert body["sources"]["dead"]["stale"] is True
        assert body["sources"]["dead"]["age_s"] > 9_000
    finally:
        srv.close()


def test_flight_endpoint_serves_shipped_rings():
    m = distributed.MergedRegistry()
    m.ingest_payload("replica0", {
        "flight": [{"kind": "event", "name": "unit.flight", "t_mono": 1.0}]})
    srv = distributed.MetricsServer(0, merged=m,
                                    include_local=False).start()
    try:
        rings = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/flight", timeout=10).read())
        assert [e["name"] for e in rings["replica0"]] == ["unit.flight"]
    finally:
        srv.close()


def test_flight_endpoint_includes_local_ring():
    from xgboost_tpu.telemetry import flight

    flight.clear()
    flight.record("event", "unit.localflight")
    srv = distributed.MetricsServer(
        0, merged=distributed.MergedRegistry()).start()
    try:
        rings = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/flight", timeout=10).read())
        assert any(e["name"] == "unit.localflight"
                   for e in rings["driver"])
    finally:
        srv.close()
        flight.clear()


def test_unknown_route_still_404s():
    srv = distributed.MetricsServer(
        0, merged=distributed.MergedRegistry()).start()
    try:
        for route in ("/nope", "/healthz/extra", "/flightpath"):
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}{route}", timeout=10)
    finally:
        srv.close()


def test_stale_source_gets_relabeled():
    m = distributed.MergedRegistry()

    def mk(v):
        r = Registry()
        r.counter("xtb_t_requests_total", "r", ("model",)).labels(
            "m").inc(v)
        return r.snapshot()

    m.ingest("live", mk(2))
    m.ingest("gone", mk(5))
    m._sources["gone"]["t"] = time.monotonic() - 10_000
    text = m.render_prometheus(include_local=False)
    assert ('xtb_t_requests_total{proc="live",model="m"} 2' in text)
    assert ('xtb_t_requests_total{proc="gone",stale="1",model="m"} 5'
            in text)
    # merged still includes the stale source (last-known-value semantics)
    assert '\nxtb_t_requests_total{model="m"} 7' in text


# =========================================================================
# determinism: profiler on == profiler off, bitwise


def test_training_bitwise_identical_with_profiler_on():
    import xgboost_tpu as xtb

    rng = np.random.default_rng(17)
    X = rng.normal(size=(600, 10)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4,
              "seed": 17, "deterministic_histogram": 1}

    def run():
        bst = xtb.train(params, xtb.DMatrix(X, label=y), 4,
                        verbose_eval=False)
        return np.asarray(bst.predict(xtb.DMatrix(X))), bst.save_raw()

    profiler.stop()
    p_off, raw_off = run()
    assert profiler.start(hz=500)  # extreme rate: maximize interference
    try:
        p_on, raw_on = run()
        assert profiler.samples() > 0  # it really sampled during training
    finally:
        profiler.stop()
    assert raw_on == raw_off
    np.testing.assert_array_equal(p_on, p_off)


# =========================================================================
# slow: 2-replica fleet ships folded stacks from both processes


@pytest.mark.slow
def test_fleet_merged_profile_contains_both_replicas(monkeypatch):
    import xgboost_tpu as xtb
    from xgboost_tpu.serving import ServingFleet

    monkeypatch.setenv(profiler.ENV_HZ, "100")
    monkeypatch.setenv(distributed.ENV_INTERVAL, "0.2")
    m = distributed.get_merged()
    for src in list(m.profiles()):  # hermetic against suite order
        m.forget(src)
    rng = np.random.default_rng(5)
    X = rng.normal(size=(400, 8)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    bst = xtb.train({"objective": "binary:logistic", "max_depth": 3,
                     "seed": 5}, xtb.DMatrix(X, label=y), 3,
                    verbose_eval=False)
    with ServingFleet({"profm": bst}, n_replicas=2,
                      warmup_buckets=(64,)) as fleet:
        for _wave in range(3):
            futs = [fleet.submit("profm", X[:64]) for _ in range(12)]
            for f in futs:
                f.result(timeout=60)
            time.sleep(0.3)  # let periodic ships carry profiles
    # the close handshake ships each replica's final payload
    deadline = time.monotonic() + 30
    sources = set()
    while time.monotonic() < deadline:
        profs = distributed.get_merged().profiles()
        sources = {s for s in profs if s.startswith("replica")}
        if len(sources) >= 2:
            break
        time.sleep(0.05)
    assert len(sources) >= 2, f"profiles only from {sources}"
    profs = distributed.get_merged().profiles()
    pids = {profs[s]["pid"] for s in sources}
    assert len(pids) == 2  # genuinely two processes
    folded = profiler.merged_folded(include_local=False)
    for s in sources:
        tag = f"{s}/{profs[s]['pid']};"
        assert any(k.startswith(tag) for k in folded), f"no stacks for {s}"
    # and every shipped stack survived into the collapsed render
    text = profiler.render_folded(include_local=False)
    for s in sources:
        assert f"{s}/{profs[s]['pid']};" in text
