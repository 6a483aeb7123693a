"""Serving fleet: dispatcher policy, shared model store, wire protocol,
warm compile cache, and the multi-process no-loss contracts.

Single-process tiers exercise the unit seams (DispatchQueue shed/expiry
policy, ModelStore publish/snapshot parity, wire framing, program keys);
the multi-process tests pin the fleet-level contracts from
docs/serving.md "Fleet": bitwise parity with the in-process engine on
both request encodings, warm-cache cold-start at a fraction of
cold-cache, and replica death dropping nothing but (at most) nothing —
the in-flight batch reroutes to a live replica.
"""
import os
import signal
import time

import numpy as np
import pytest

import xgboost_tpu as xtb
from xgboost_tpu.reliability import faults
from xgboost_tpu.serving import (ModelStore, ServeConfig, ServingEngine,
                                 ServingFleet, SLOClass)
from xgboost_tpu.serving import wire
from xgboost_tpu.serving.fleet import DispatchQueue, FleetConfig, _Request
from xgboost_tpu.serving.warmcache import WarmProgramCache, program_key


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


def _train(seed=0, n=400, f=8, rounds=5, depth=4):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    bst = xtb.train({"objective": "binary:logistic", "max_depth": depth,
                     "seed": seed}, xtb.DMatrix(X, label=y), rounds,
                    verbose_eval=False)
    return bst, X


def _req(rid, slo, model="m"):
    return _Request(rid, model, {"op": "predict", "id": rid}, b"", slo)


# =========================================================================
# DispatchQueue: SLO-ordered admission, shedding, expiry


def test_queue_priority_order_and_fifo_within_class():
    gold = SLOClass("gold", priority=2)
    free = SLOClass("free", priority=0)
    q = DispatchQueue(max_queue=16)
    order = []
    for rid, slo in [(1, free), (2, gold), (3, free), (4, gold)]:
        assert q.push(_req(rid, slo)) is None
    while True:
        r, _ = q.pop(time.monotonic())
        if r is None:
            break
        order.append(r.id)
    # gold first (FIFO within gold), then free (FIFO within free)
    assert order == [2, 4, 1, 3]


def test_queue_full_sheds_newest_lowest_priority():
    gold = SLOClass("gold", priority=2)
    free = SLOClass("free", priority=0)
    q = DispatchQueue(max_queue=2)
    assert q.push(_req(1, free)) is None
    assert q.push(_req(2, free)) is None
    # a gold request outranks: the NEWEST free request (id 2) is shed
    victim = q.push(_req(3, gold))
    assert victim is not None and victim.id == 2
    assert victim.state == "shed"
    # an equal-priority newcomer does not outrank anyone: it sheds itself
    victim = q.push(_req(4, free))
    assert victim is not None and victim.id == 4
    # queue still serves gold before the surviving free request
    r1, _ = q.pop(time.monotonic())
    r2, _ = q.pop(time.monotonic())
    assert [r1.id, r2.id] == [3, 1]


def test_queue_deadline_expires_in_queue():
    fast = SLOClass("fast", priority=1, deadline_s=0.005)
    slow = SLOClass("slow", priority=0, deadline_s=None)
    q = DispatchQueue(max_queue=8)
    q.push(_req(1, fast))
    q.push(_req(2, slow))
    time.sleep(0.02)
    r, expired = q.pop(time.monotonic())
    assert [e.id for e in expired] == [1]
    assert expired[0].state == "expired"
    assert r.id == 2  # the deadline-free request still serves


def test_queue_pop_skips_cancelled_futures():
    """A caller that timed out cancels its future; the queue must not
    hand the abandoned request to a replica."""
    slo = SLOClass()
    q = DispatchQueue(max_queue=8)
    r1, r2 = _req(1, slo), _req(2, slo)
    q.push(r1)
    q.push(r2)
    assert r1.future.cancel()
    r, _ = q.pop(time.monotonic())
    assert r.id == 2 and r1.state == "done"
    assert len(q) == 0


def test_queue_requeue_front_precedes_fifo():
    slo = SLOClass()
    q = DispatchQueue(max_queue=8)
    q.push(_req(1, slo))
    q.push(_req(2, slo))
    r, _ = q.pop(time.monotonic())
    assert r.id == 1
    q.requeue_front(r)  # rerouted in-flight work goes back to the FRONT
    r, _ = q.pop(time.monotonic())
    assert r.id == 1
    assert len(q) == 1


# =========================================================================
# wire protocol


def _socketpair():
    import socket

    a, b = socket.socketpair()
    return wire.configure(a), wire.configure(b)


def test_wire_raw_roundtrip_bitwise():
    X = np.random.default_rng(0).normal(size=(33, 7)).astype(np.float32)
    fields, payload = wire.encode_raw(X)
    a, b = _socketpair()
    try:
        wire.send_frame(a, dict(fields, op="predict", id=9), payload)
        hdr, body = wire.recv_frame(wire.reader(b))
        assert hdr["id"] == 9
        Y = wire.decode_matrix(hdr, body)
        np.testing.assert_array_equal(X, Y)
    finally:
        a.close()
        b.close()


def test_wire_large_payload_and_eof():
    import threading

    X = np.zeros((4096, 32), np.float32)  # > _INLINE_PAYLOAD: two sendalls
    fields, payload = wire.encode_raw(X)
    a, b = _socketpair()
    try:
        # 512KB overflows the socketpair buffer: send concurrently with
        # the receive (sendall blocks until the peer drains)
        tx = threading.Thread(target=wire.send_frame,
                              args=(a, fields, payload), daemon=True)
        tx.start()
        hdr, body = wire.recv_frame(b)
        tx.join(timeout=30)
        assert not tx.is_alive()
        assert wire.decode_matrix(hdr, body).shape == (4096, 32)
        a.close()
        with pytest.raises(wire.WireError):
            wire.recv_frame(b)  # EOF at frame boundary is still WireError
    finally:
        b.close()


def test_wire_recv_frame_slow_loris_bound():
    """A peer trickling a frame one byte per interval exhausts ONE
    cumulative frame budget (clocked from the first prefix byte), not an
    idle timeout reset on every byte."""
    import socket
    import threading

    X = np.zeros((4, 4), np.float32)
    fields, payload = wire.encode_raw(X)
    a, b = _socketpair()
    c, d = _socketpair()
    try:
        wire.send_frame(a, dict(fields, op="predict", id=1), payload)
        a.shutdown(socket.SHUT_WR)
        blob = b"".join(iter(lambda: b.recv(65536), b""))

        def _trickle():
            try:
                for i in range(len(blob)):
                    c.sendall(blob[i:i + 1])
                    time.sleep(0.02)
            except OSError:
                pass  # the reader gave up and closed: expected

        threading.Thread(target=_trickle, daemon=True).start()
        t0 = time.monotonic()
        with pytest.raises(wire.WireError, match="slow-loris"):
            wire.recv_frame(d, budget_s=0.3)
        assert time.monotonic() - t0 < 5.0
    finally:
        for s in (a, b, c, d):
            s.close()
    # the budget is a trickle bound, not a size bound: an intact frame
    # inside it still parses
    e, f = _socketpair()
    try:
        wire.send_frame(e, dict(fields, op="predict", id=2), payload)
        hdr, body = wire.recv_frame(f, budget_s=30.0)
        assert hdr["id"] == 2
        np.testing.assert_array_equal(wire.decode_matrix(hdr, body), X)
    finally:
        e.close()
        f.close()


def test_wire_arrow_roundtrip_parity():
    pa = pytest.importorskip("pyarrow")
    X = np.random.default_rng(1).normal(size=(50, 5)).astype(np.float32)
    batch = pa.RecordBatch.from_arrays(
        [pa.array(X[:, i]) for i in range(5)],
        names=[f"f{i}" for i in range(5)])
    fields, payload = wire.encode_arrow(batch)
    assert fields["enc"] == wire.ARROW
    Y = wire.decode_matrix(fields, bytes(payload))
    np.testing.assert_array_equal(X, Y)  # bitwise through the IPC stream


def test_wire_arrow_nulls_and_dictionary():
    pa = pytest.importorskip("pyarrow")
    from xgboost_tpu.data.arrow import ipc_batch_to_dense

    batch = pa.RecordBatch.from_arrays(
        [pa.array([1.0, None, 3.0], type=pa.float32()),
         pa.array([1, 2, 3], type=pa.int64())], names=["a", "b"])
    _, payload = wire.encode_arrow(batch)
    Y = ipc_batch_to_dense(bytes(payload))
    assert np.isnan(Y[1, 0]) and Y[2, 1] == 3.0  # nulls -> NaN, ints cast
    dict_batch = pa.RecordBatch.from_arrays(
        [pa.array(["x", "y", "x"]).dictionary_encode()], names=["c"])
    _, payload = wire.encode_arrow(dict_batch)
    with pytest.raises(ValueError, match="dictionary"):
        ipc_batch_to_dense(bytes(payload))


# =========================================================================
# ModelStore: one mmap copy, snapshot parity


def test_modelstore_publish_snapshot_parity(tmp_path):
    bst, X = _train(seed=3)
    store = ModelStore(str(tmp_path))
    v = store.publish("m", bst)
    assert v == 1 and store.entries() == [("m", 1)]
    snap = store.snapshot("m", device=False)
    from xgboost_tpu.serving.snapshot import InferenceSnapshot

    ref = InferenceSnapshot.from_booster(bst)
    for key, a in ref.stacked.items():
        b = snap.stacked[key]
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert snap.num_features == ref.num_features
    assert snap.depth == ref.depth and snap.n_groups == ref.n_groups
    # the arena views are READ-ONLY mmaps: one host copy fleet-wide
    with pytest.raises(ValueError):
        np.asarray(snap.stacked["feat"])[0] = 0


def test_modelstore_engine_predict_bitwise(tmp_path):
    bst, X = _train(seed=4)
    store = ModelStore(str(tmp_path))
    store.publish("m", bst)
    eng = ServingEngine(ServeConfig(use_batcher=False))
    try:
        eng.add_model("ref", bst)
        ref = eng.predict("ref", X, direct=True)
        eng.registry.register_snapshot("m", store.snapshot("m"), 1)
        out = eng.predict("m", X, direct=True)
        np.testing.assert_array_equal(ref, out)
    finally:
        eng.close()


def test_modelstore_versioning_and_missing(tmp_path):
    bst, _ = _train(seed=5, rounds=2)
    bst2, _ = _train(seed=6, rounds=3)
    store = ModelStore(str(tmp_path))
    assert store.publish("m", bst) == 1
    assert store.publish("m", bst2) == 2
    assert store.latest_version("m") == 2
    assert store.snapshot("m", 1).n_trees != store.snapshot("m", 2).n_trees
    with pytest.raises(KeyError):
        store.snapshot("absent")


# =========================================================================
# warm program cache


def test_program_key_is_architecture_not_weights(tmp_path):
    # same architecture, different weights -> SAME program key (a
    # hot-swapped retrain warms instantly); different bucket/depth -> new
    bst_a, _ = _train(seed=7, rounds=3, depth=3)
    bst_b, _ = _train(seed=8, rounds=3, depth=3)
    store = ModelStore(str(tmp_path))
    store.publish("a", bst_a)
    store.publish("b", bst_b)
    sa = store.snapshot("a", device=False)
    sb = store.snapshot("b", device=False)
    assert program_key(sa, 64) == program_key(sb, 64)
    assert program_key(sa, 64) != program_key(sa, 128)
    bst_c, _ = _train(seed=7, rounds=3, depth=5)
    store.publish("c", bst_c)
    sc = store.snapshot("c", device=False)
    assert program_key(sa, 64) != program_key(sc, 64)


def test_program_key_holds_the_device_set(tmp_path, monkeypatch):
    # a serialized executable carries the device set it was compiled
    # against: the same model and bucket in a process with another number
    # of devices, or another kind of device, is another program
    import jax

    from xgboost_tpu.serving import warmcache

    bst, _ = _train(seed=7, rounds=3, depth=3)
    store = ModelStore(str(tmp_path))
    store.publish("a", bst)
    snap = store.snapshot("a", device=False)
    here = program_key(snap, 64)
    monkeypatch.setattr(jax, "device_count", lambda: jax.local_device_count() + 3)
    fewer = program_key(snap, 64)
    assert fewer != here
    monkeypatch.undo()
    assert program_key(snap, 64) == here

    class OtherKind:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(warmcache, "_program_device", lambda: OtherKind())
    assert program_key(snap, 64) != here


def test_configure_persistent_cache_leaves_the_environment_in_charge(
        tmp_path):
    # where JAX_COMPILATION_CACHE_DIR is set, jax's own reading of it stands:
    # the helper names no directory, whatever its caller passes.  Unset, the
    # cache goes to the caller's directory or to .jax_cache/ in the checkout.
    # (In a child: the cache's place is process-wide state.)
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys; from xgboost_tpu.serving.warmcache import "
            "configure_persistent_cache as c; print(c(*sys.argv[1:]))")

    def place(*args, **env):
        base = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
        r = subprocess.run([sys.executable, "-c", code, *args], cwd=root,
                           env={**base, **env}, capture_output=True,
                           text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-1000:]
        return r.stdout.strip().splitlines()[-1]

    assert place(str(tmp_path / "other"),
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "env")) == str(
        tmp_path / "env")
    assert not (tmp_path / "other").exists()
    assert place(str(tmp_path / "mine")) == str(tmp_path / "mine")
    assert (tmp_path / "mine").is_dir()
    assert place() == os.path.join(root, ".jax_cache")


def test_warmcache_attach_and_reload(tmp_path):
    bst, X = _train(seed=9)
    store = ModelStore(str(tmp_path / "store"))
    store.publish("m", bst)
    snap = store.snapshot("m")
    warm = WarmProgramCache(str(tmp_path / "cache"))
    st = warm.attach(snap, (32, 64))
    assert st["compiled"] == 2 and st["hits"] == 0
    assert warm.save()
    # a second "replica" (fresh cache object + fresh snapshot) hits
    snap2 = store.snapshot("m")
    warm2 = WarmProgramCache(str(tmp_path / "cache"))
    st2 = warm2.attach(snap2, (32, 64))
    assert st2["hits"] == 2 and st2["compiled"] == 0
    # and the AOT program computes the same bits as the eager engine path
    eng = ServingEngine(ServeConfig(use_batcher=False))
    try:
        eng.add_model("ref", bst)
        ref = eng.predict("ref", X[:32], direct=True)
        out = np.asarray(snap2.aot_execute(X[:32], False))
        np.testing.assert_array_equal(ref, out[:, 0])
    finally:
        eng.close()


# =========================================================================
# fleet config


def test_fleet_config_validation():
    with pytest.raises(ValueError):
        FleetConfig(n_replicas=0)
    with pytest.raises(ValueError):
        FleetConfig(max_queue=0)
    cfg = FleetConfig(slo_classes={"t": SLOClass("gold", 2, 1.0)})
    assert cfg.resolve_slo("t").priority == 2
    assert cfg.resolve_slo("unknown").priority == 0
    assert cfg.resolve_slo(None).name == "default"
    with pytest.raises(ValueError):
        ServingFleet({}, n_replicas=1).start()  # no models


def test_fleet_refuses_more_chip_replicas_than_can_start(fleet_models,
                                                         monkeypatch):
    """A chip belongs to one process.  The driver learns the replicas'
    platform from the configuration (or the JAX_PLATFORMS they inherit),
    never from JAX, and a fleet that asks for several chip-holding
    replicas fails with the reason before it spawns one."""
    spawned = []
    monkeypatch.setattr(ServingFleet, "_spawn",
                        lambda self, label: spawned.append(label))
    with pytest.raises(ValueError, match="a chip belongs to one process"):
        ServingFleet({"a": fleet_models["a"]}, n_replicas=2,
                     platform="tpu").start()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(ValueError, match="platform 'tpu'"):
        ServingFleet({"a": fleet_models["a"]}, n_replicas=4,
                     n_shards=2).start()
    assert spawned == []
    fleet = ServingFleet({"a": fleet_models["a"]}, n_replicas=1)
    assert fleet._replica_platform() == "tpu"
    fleet._check_chip_replicas()  # one chip-holding replica may start
    monkeypatch.delenv("JAX_PLATFORMS")
    assert fleet._replica_platform() is None  # left to the replica
    assert ServingFleet({"a": fleet_models["a"]}, n_replicas=8,
                        platform="cpu")._replica_platform() == "cpu"


# =========================================================================
# multi-process fleet contracts (slow: real replica processes)


@pytest.fixture(scope="module")
def fleet_models(tmp_path_factory):
    d = tmp_path_factory.mktemp("fleet_models")
    bst_a, X = _train(seed=11, f=8, rounds=6, depth=4)
    bst_b, _ = _train(seed=12, f=8, rounds=4, depth=3)
    pa = str(d / "a.json")
    pb = str(d / "b.json")
    bst_a.save_model(pa)
    bst_b.save_model(pb)
    eng = ServingEngine(ServeConfig(use_batcher=False))
    eng.add_model("a", pa)
    eng.add_model("b", pb)
    ref_a = eng.predict("a", X, direct=True)
    ref_b = eng.predict("b", X, direct=True)
    eng.close()
    return {"a": pa, "b": pb, "X": X, "ref_a": ref_a, "ref_b": ref_b}


@pytest.mark.slow
def test_fleet_end_to_end_parity_and_reroute(fleet_models, tmp_path):
    X = fleet_models["X"]
    cache = str(tmp_path / "cache")
    with ServingFleet({"a": fleet_models["a"], "b": fleet_models["b"]},
                      n_replicas=2, cache_dir=cache, max_respawns=1,
                      warmup_buckets=(64, 512)) as fleet:
        assert fleet.alive_replicas() == 2
        # numpy path: bitwise the in-process engine
        np.testing.assert_array_equal(
            fleet.predict("a", X, timeout=60), fleet_models["ref_a"])
        np.testing.assert_array_equal(
            fleet.predict("b", X, timeout=60), fleet_models["ref_b"])
        # arrow path: bitwise too (zero-copy parity contract)
        try:
            import pyarrow as pa
        except ImportError:
            pa = None
        if pa is not None:
            batch = pa.RecordBatch.from_arrays(
                [pa.array(X[:, i]) for i in range(X.shape[1])],
                names=[f"f{i}" for i in range(X.shape[1])])
            np.testing.assert_array_equal(
                fleet.predict_arrow("a", batch, timeout=60),
                fleet_models["ref_a"])
        # unknown model surfaces the replica's error, typed
        with pytest.raises(KeyError):
            fleet.predict("nope", X[:4], timeout=60)
        # kill one replica mid-stream: nothing is lost — the dead
        # replica's in-flight batch reroutes, queued work drains on the
        # survivor (and later the respawn)
        victim = next(iter(fleet._replicas.values()))
        futs = [fleet.submit("a", X) for _ in range(24)]
        victim.proc.send_signal(signal.SIGKILL)
        for f in futs:
            np.testing.assert_array_equal(f.result(timeout=60),
                                          fleet_models["ref_a"])
        # respawn absorbs back to full strength
        deadline = time.monotonic() + 60
        while fleet.alive_replicas() < 2 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert fleet.alive_replicas() == 2
        np.testing.assert_array_equal(
            fleet.predict("b", X, timeout=60), fleet_models["ref_b"])
        # flight recorder on kill: the dispatcher dumped the SIGKILL'd
        # replica's last shipped ring + final snapshot driver-side (the
        # corpse itself never got the chance), and the failure record
        # points at it
        deadline = time.monotonic() + 30
        while (victim.label not in fleet.flight_dumps
               and time.monotonic() < deadline):
            time.sleep(0.05)
        dump_path = fleet.flight_dumps[victim.label]
        assert os.path.exists(dump_path)
        import json as _json

        dump = _json.load(open(dump_path))
        assert dump["label"] == victim.label
        assert any(e["name"] == "replica.start" for e in dump["events"])
        assert any(f["name"].startswith("xtb_")
                   for f in (dump["snapshot"] or {}).get("families", []))
        with fleet._cv:
            failure_tails = [t for (lb, _rc, t) in fleet._failures
                             if lb == victim.label]
        assert any("flight recorder" in t for t in failure_tails)


@pytest.mark.slow
def test_fleet_coldstart_warm_cache_faster(fleet_models, tmp_path):
    """The persistent-cache contract: a replica starting against a warm
    cache does a fraction of the cold warm-work (the >=10x claim lives in
    BENCH_SERVE.json; the test asserts the mechanism with slack for a
    noisy host: all programs hit, none compiled, and wall at most half)."""
    cache = str(tmp_path / "cache")
    buckets = (64, 512)
    kw = dict(n_replicas=1, cache_dir=cache, warmup_buckets=buckets)
    with ServingFleet({"a": fleet_models["a"]}, **kw) as fleet:
        cold = fleet.replica_info()[0]
    with ServingFleet({"a": fleet_models["a"]}, **kw) as fleet:
        warm = fleet.replica_info()[0]
    assert cold["aot_compiled"] == len(buckets) and cold["aot_hits"] == 0
    assert cold["cache_state"] == "cold"
    assert warm["aot_hits"] == len(buckets) and warm["aot_compiled"] == 0
    assert warm["cache_state"] == "warm"
    assert warm["warmup_s"] < cold["warmup_s"] / 2


def _stalled_first_request(fleet, model, X, seconds):
    """Submit one request whose dispatch-seam delay holds the lone replica
    'busy' (in_flight claimed, nothing on the wire) for ``seconds`` — the
    deterministic window the SLO tests stack the queue in.  Returns the
    (background-submitted) future; join via .result()."""
    import threading

    faults.install({"faults": [{"site": "fleet.dispatch", "kind": "delay",
                                "seconds": seconds, "at": 0, "times": 1}]})
    box = {}
    ev = threading.Event()

    def _bg():
        box["f"] = fleet.submit(model, X)  # blocks in the seam delay
        ev.set()

    t = threading.Thread(target=_bg, daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:  # wait until the stall claimed it
        with fleet._cv:
            busy = any(r.in_flight is not None
                       for r in fleet._replicas.values())
        if busy:
            break
        time.sleep(0.01)
    assert busy, "stalled request never claimed the replica"
    return box, ev


@pytest.mark.slow
def test_fleet_slo_deadline_and_dispatch_fault(fleet_models):
    X = fleet_models["X"][:32]
    classes = {"paid": SLOClass("paid", priority=2, deadline_s=30.0),
               "free": SLOClass("free", priority=0, deadline_s=0.05)}
    with ServingFleet({"a": fleet_models["a"]}, n_replicas=1,
                      warmup_buckets=(64,), slo_classes=classes) as fleet:
        # hold the replica for 1.5s; a free-tier request queued behind the
        # stall outlives its 50ms deadline and must expire with
        # TimeoutError, while the paid-tier request (queued later, higher
        # priority) still serves
        box, ev = _stalled_first_request(fleet, "a", X, 1.5)
        f_free = fleet.submit("a", X, tenant="free")
        f_paid = fleet.submit("a", X, tenant="paid")
        assert f_paid.result(timeout=60) is not None
        with pytest.raises(TimeoutError):
            f_free.result(timeout=60)
        ev.wait(timeout=60)
        assert box["f"].result(timeout=60) is not None
        faults.clear()
        # an exception at the dispatch seam fails that request only
        faults.install({"faults": [{"site": "fleet.dispatch",
                                    "kind": "exception",
                                    "message": "dispatch boom"}]})
        with pytest.raises(faults.FaultInjected):
            fleet.predict("a", X, timeout=60)
        faults.clear()
        np.testing.assert_array_equal(
            fleet.predict("a", fleet_models["X"], timeout=60),
            fleet_models["ref_a"])


@pytest.mark.slow
def test_fleet_extinct_fails_fast(fleet_models):
    """With the respawn budget spent and every replica dead, queued work
    fails with WorkerFailedError AND later submits fail fast instead of
    queueing into a permanent hang."""
    from xgboost_tpu.launcher import WorkerFailedError

    X = fleet_models["X"][:16]
    fleet = ServingFleet({"a": fleet_models["a"]}, n_replicas=1,
                         warmup_buckets=(64,), max_respawns=0).start()
    try:
        victim = next(iter(fleet._replicas.values()))
        victim.proc.send_signal(signal.SIGKILL)
        deadline = time.monotonic() + 60
        while not fleet._extinct and time.monotonic() < deadline:
            time.sleep(0.05)
        assert fleet._extinct
        with pytest.raises(WorkerFailedError, match="respawn budget"):
            fleet.predict("a", X, timeout=60)
    finally:
        fleet.close()


@pytest.mark.slow
def test_fleet_start_crash_fails_fast(fleet_models):
    """Replicas that crash during launch with no respawn budget must fail
    start() as soon as the fleet is extinct, not at ready_timeout_s."""
    from xgboost_tpu.launcher import WorkerFailedError

    t0 = time.monotonic()
    with pytest.raises(WorkerFailedError, match="replicas became ready"):
        ServingFleet({"a": fleet_models["a"]}, n_replicas=1,
                     max_respawns=0, platform="not_a_jax_backend",
                     ready_timeout_s=120).start()
    assert time.monotonic() - t0 < 60  # well under the ready timeout


@pytest.mark.slow
def test_fleet_queue_shed_under_pressure(fleet_models):
    """max_queue=2 with the replica stalled: a low-priority resident is
    shed to admit a higher class; an equal-priority newcomer sheds
    itself (FIFO fairness)."""
    from xgboost_tpu.serving.batcher import QueueFullError

    X = fleet_models["X"][:16]
    classes = {"gold": SLOClass("gold", priority=2),
               "free": SLOClass("free", priority=0)}
    with ServingFleet({"a": fleet_models["a"]}, n_replicas=1,
                      warmup_buckets=(64,), max_queue=2,
                      slo_classes=classes) as fleet:
        box, ev = _stalled_first_request(fleet, "a", X, 1.5)
        fillers = [fleet.submit("a", X, tenant="free") for _ in range(2)]
        gold = fleet.submit("a", X, tenant="gold")  # sheds a free filler
        shed = [f for f in fillers
                if isinstance(f.exception(timeout=60), QueueFullError)]
        assert len(shed) == 1 and shed[0] is fillers[1]  # newest free
        assert gold.result(timeout=60) is not None
        ev.wait(timeout=60)
        assert box["f"].result(timeout=60) is not None


# =========================================================================
# degraded-network survival: kill/respawn churn, breaker readmission via
# heartbeat probe, hedged dispatch neutrality (docs/reliability.md
# "Degraded networks")


def _counter(name, *labels):
    from xgboost_tpu.telemetry.registry import get_registry

    fam = get_registry().get(name)
    if fam is None:
        return 0.0
    if labels:
        for values, child in fam.collect():
            if values == tuple(labels):
                return float(child.value)
        return 0.0
    return sum(child.value for _v, child in fam.collect())


@pytest.mark.slow
def test_fleet_kill_respawn_churn_deterministic(fleet_models, tmp_path):
    """20 kill/respawn cycles: every request completes with the exact
    reference bits (zero drops), the fleet returns to full strength each
    cycle, and the respawn accounting is monotonic."""
    X = fleet_models["X"]
    ref = fleet_models["ref_a"]
    with ServingFleet({"a": fleet_models["a"]}, n_replicas=2,
                      cache_dir=str(tmp_path / "cache"), max_respawns=25,
                      warmup_buckets=(64,)) as fleet:
        np.testing.assert_array_equal(
            fleet.predict("a", X, timeout=120), ref)
        for cycle in range(20):
            with fleet._cv:
                victim = next(r for r in fleet._replicas.values()
                              if r.alive and r.proc is not None)
            futs = [fleet.submit("a", X) for _ in range(4)]
            victim.proc.send_signal(signal.SIGKILL)
            for fut in futs:  # nothing dropped, nothing wrong
                np.testing.assert_array_equal(fut.result(timeout=120), ref)
            deadline = time.monotonic() + 120
            while (fleet.alive_replicas() < 2
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert fleet.alive_replicas() == 2, f"cycle {cycle}"
            assert fleet._respawned == cycle + 1
        np.testing.assert_array_equal(
            fleet.predict("a", X, timeout=120), ref)


@pytest.mark.slow
def test_fleet_breaker_pong_probe_readmits_without_traffic(fleet_models):
    """The EWMA breaker ejects a laggy replica; with NO further traffic
    (a healthy sibling absorbs everything), the first heartbeat pong
    after cooldown is the half-open probe and readmits it — readmission
    must not depend on starving the healthy replicas first."""
    X = fleet_models["X"][:32]
    opened0 = _counter("xtb_net_breaker_transitions_total", "open")
    closed0 = _counter("xtb_net_breaker_transitions_total", "closed")
    with ServingFleet({"a": fleet_models["a"]}, n_replicas=2,
                      warmup_buckets=(64,), heartbeat_s=0.2,
                      heartbeat_timeout_s=10.0, breaker_latency_s=0.05,
                      breaker_cooldown_s=0.4) as fleet:
        ref = fleet.predict("a", X, timeout=60)
        # every replica0 frame (results and pongs alike) arrives 0.3s
        # late: the EWMA trips past the 50ms threshold immediately
        faults.install({"faults": [{"site": "wire.recv", "kind": "delay",
                                    "seconds": 0.3, "rank": "replica0",
                                    "times": 16}]})
        deadline = time.monotonic() + 30
        while (_counter("xtb_net_breaker_transitions_total", "open")
               == opened0 and time.monotonic() < deadline):
            np.testing.assert_array_equal(
                fleet.predict("a", X, timeout=60), ref)
        assert _counter("xtb_net_breaker_transitions_total",
                        "open") > opened0
        faults.clear()  # the link heals; no requests from here on
        deadline = time.monotonic() + 10
        while (_counter("xtb_net_breaker_transitions_total", "closed")
               == closed0 and time.monotonic() < deadline):
            time.sleep(0.05)
        assert _counter("xtb_net_breaker_transitions_total",
                        "closed") > closed0
        with fleet._cv:
            assert fleet._replicas["replica0"].breaker == "closed"
        np.testing.assert_array_equal(
            fleet.predict("a", X, timeout=60), ref)


@pytest.mark.slow
def test_fleet_hedged_dispatch_bitwise_neutral(fleet_models):
    """Hedging past the latency-quantile budget returns whichever copy
    settles first — and the bytes are the reference's either way (the
    twin shares the future; replicas are deterministic)."""
    X = fleet_models["X"][:48]
    with ServingFleet({"a": fleet_models["a"]}, n_replicas=2,
                      warmup_buckets=(64,), heartbeat_s=0.1,
                      heartbeat_timeout_s=30.0,
                      hedge_quantile=0.5, hedge_min_s=0.05) as fleet:
        ref = fleet.predict("a", X, timeout=60)
        for _ in range(9):  # latency history >= 8 arms the hedge budget
            np.testing.assert_array_equal(
                fleet.predict("a", X, timeout=60), ref)
        hedges0 = _counter("xtb_net_hedges_total")
        wins0 = _counter("xtb_net_hedge_wins_total")
        # replica0's rx path stalls 0.8s per frame: an in-flight request
        # ages past the ~ms p50 budget and hedges onto replica1
        faults.install({"faults": [{"site": "wire.recv", "kind": "delay",
                                    "seconds": 0.8, "rank": "replica0",
                                    "times": 12}]})
        deadline = time.monotonic() + 30
        while (_counter("xtb_net_hedges_total") == hedges0
               and time.monotonic() < deadline):
            np.testing.assert_array_equal(
                fleet.predict("a", X, timeout=60), ref)
        assert _counter("xtb_net_hedges_total") > hedges0
        assert _counter("xtb_net_hedge_wins_total") > wins0
        faults.clear()
        np.testing.assert_array_equal(
            fleet.predict("a", X, timeout=60), ref)


# =========================================================================
# DART + refresh/prune boosters through the fleet fast path (the dormant
# workload axes the lifecycle PR turns live)


def _fastpath_for(store, name, buckets=(64,)):
    """A replica-identical serving stack for one store entry: mmap
    snapshot -> AOT programs -> _FastPath (the exact path replica.py
    runs), without spawning processes."""
    from xgboost_tpu.serving.replica import _FastPath

    snap = store.snapshot(name)
    WarmProgramCache(None).attach(snap, buckets)
    return _FastPath(snap), snap


def test_fastpath_dart_dropout_free_parity(tmp_path):
    """DART inference is dropout-free: the _FastPath result (per-tree
    weights folded into the stacked values) must equal Booster.predict
    bitwise, and the continuation round-trips through model bytes."""
    rng = np.random.default_rng(31)
    X = rng.normal(size=(600, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    params = {"booster": "dart", "objective": "binary:logistic",
              "rate_drop": 0.4, "one_drop": 1, "max_depth": 3, "seed": 5}
    bst = xtb.train(params, xtb.DMatrix(X, label=y), 8, verbose_eval=False)
    assert any(w != 1.0 for w in bst.tree_weights)  # dropout really fired

    store = ModelStore(str(tmp_path))
    store.publish("dart", bst)
    fp, snap = _fastpath_for(store, "dart")
    out = fp.run(X[:64], False)
    assert out is not None  # the AOT fast path took it, no engine fallback
    np.testing.assert_array_equal(out, bst.predict(xtb.DMatrix(X[:64])))

    # continuation round-trip: serialized bytes survive store archive and
    # continue training with the weights intact
    cont = xtb.train(params, xtb.DMatrix(X, label=y), 2,
                     verbose_eval=False, xgb_model=store.booster("dart"))
    assert cont.num_boosted_rounds() == bst.num_boosted_rounds() + 2
    v2 = store.publish("dart", cont)
    fp2, _ = _fastpath_for(store, "dart")
    np.testing.assert_array_equal(
        fp2.run(X[:64], False), cont.predict(xtb.DMatrix(X[:64])))
    assert store.model_bytes("dart", v2) == bytes(cont.serialize())


def test_fastpath_refresh_prune_same_arch_warms_instantly(tmp_path):
    """refresh/prune continuation (process_type=update) keeps the tree
    COUNT and stacked shapes: the arch-keyed program key is unchanged, so
    the hot-swapped version deserializes the incumbent's AOT programs
    instead of compiling (the instant-warm half of the swap design) —
    and the fast path serves it bitwise vs Booster.predict."""
    rng = np.random.default_rng(32)
    X = rng.normal(size=(800, 8)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.3 * rng.normal(size=800)).astype(np.float32)
    X2 = rng.normal(size=(800, 8)).astype(np.float32)
    y2 = (X2[:, 0] * X2[:, 1]).astype(np.float32)
    params = {"objective": "reg:squarederror", "max_depth": 4, "eta": 0.5}
    base = xtb.train(params, xtb.DMatrix(X, label=y), 4, verbose_eval=False)

    store = ModelStore(str(tmp_path))
    store.publish("m", base)
    # refresh the leaves against fresh rows via the continuation path
    refreshed = xtb.train(
        {**params, "process_type": "update", "updater": "refresh,prune"},
        xtb.DMatrix(X2, label=y2), base.num_boosted_rounds(),
        verbose_eval=False, xgb_model=store.booster("m"))
    assert len(refreshed.trees) == len(base.trees)  # structure preserved
    assert not np.array_equal(refreshed.predict(xtb.DMatrix(X2[:64])),
                              base.predict(xtb.DMatrix(X2[:64])))
    store.publish("m", refreshed)

    s1 = store.snapshot("m", 1)
    s2 = store.snapshot("m", 2)
    assert program_key(s1, 64) == program_key(s2, 64)  # same architecture

    # a warm cache populated by the incumbent serves the refresh with
    # hits only — zero compiles (the double-buffer instant-warm contract)
    cache = WarmProgramCache(str(tmp_path / "warm"))
    st1 = cache.attach(s1, (64,))
    cache.save()
    cache2 = WarmProgramCache(str(tmp_path / "warm"))
    st2 = cache2.attach(s2, (64,))
    assert st1["compiled"] >= 1
    assert st2 == {**st2, "hits": 1, "compiled": 0}

    from xgboost_tpu.serving.replica import _FastPath

    fp = _FastPath(s2)
    np.testing.assert_array_equal(fp.run(X2[:64], False),
                                  refreshed.predict(xtb.DMatrix(X2[:64])))


def test_fastpath_refresh_model_bytes_roundtrip(tmp_path):
    """The lifecycle continuation contract for the updaters: archived
    model bytes -> booster -> refresh -> serialize -> unserialize is a
    bitwise fixed point (what hot-swap publishes is exactly what a
    restarted fleet reloads)."""
    rng = np.random.default_rng(33)
    X = rng.normal(size=(500, 6)).astype(np.float32)
    y = (X[:, 0] - X[:, 1]).astype(np.float32)
    params = {"objective": "reg:squarederror", "max_depth": 3}
    base = xtb.train(params, xtb.DMatrix(X, label=y), 3, verbose_eval=False)
    refreshed = xtb.train(
        {**params, "process_type": "update", "updater": "refresh"},
        xtb.DMatrix(X, label=y), 3, verbose_eval=False, xgb_model=base)
    blob = bytes(refreshed.serialize())
    b2 = xtb.Booster()
    b2.unserialize(blob)
    assert bytes(b2.serialize()) == blob
    np.testing.assert_array_equal(b2.predict(xtb.DMatrix(X[:32])),
                                  refreshed.predict(xtb.DMatrix(X[:32])))
