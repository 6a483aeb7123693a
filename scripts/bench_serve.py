"""Serving latency/throughput benchmark -> BENCH_SERVE.json.

Measures the ISSUE-1 acceptance numbers on the CPU backend: p50/p99
request latency and rows/s at batch sizes {1, 64, 4096} through the
ServingEngine's pre-compiled bucket path (direct mode isolates per-request
cost from batching delay), plus one concurrent section — 4 threads of
batch-1 traffic through the micro-batcher — whose engine metrics snapshot
(batch-size histogram, queue peak, compiles_steady) is persisted verbatim.
``compiles_steady`` MUST be 0 in the emitted artifact: a recompile in the
timed loop is a serving regression, and the suite's smoke test
(tests/test_serving.py) fails on the same gauge.

Fleet sections (ISSUE-8/ISSUE-9/ISSUE-15, docs/serving.md "Fleet" +
"Online model lifecycle", docs/reliability.md "Resource pressure &
graceful degradation"):

- ``fleet_coldstart`` — replica warm-work seconds against a cold vs a
  warm persistent compile cache (cold gets a FRESH cache dir every rep;
  warm reuses the dir the cold rep just populated — a within-run pair).
- ``fleet_saturation`` — sustained throughput + p99 under mixed
  two-model closed-loop traffic across the (n_replicas, n_shards)
  sweep in ``FLEET_CONFIGS``, all configs measured in this run (the
  1x1 row IS the baseline pair).  Client threads scale with the fleet
  and carry distinct tenants (the shard routing key); sharded rows
  record per-shard rows/s and rx-loop busy fraction.
- ``lifecycle_swap`` — p99 during a hot version swap vs the same run's
  steady state, with the requests in flight during each swap recorded.
- ``shed_vs_degrade`` — per-SLO-class completions/sheds and gold p99
  under the same synthetic overload, static queue-bound shedding vs
  governor brownout (low-SLO tenants refused at admission).

Host-noise convention (the ladder's): this host is time-shared, so walls
swing run to run; every timed section repeats ``BENCH_SERVE_REPS`` times
and reports the MINIMUM wall (min-of-N estimates the code's actual cost;
the mean estimates the host's load average), latency percentiles taken
from the min-wall rep.  The ``reps`` field records N.

Every section carries the host fingerprint (cores + arch + SIMD flag
set, the ladder's convention); ``--diff old.json new.json`` compares
two artifacts section by section and REFUSES (exit 2) any pair stamped
by different hosts.

Usage:  python scripts/bench_serve.py [out.json]   (default BENCH_SERVE.json)
        python scripts/bench_serve.py --diff old.json new.json
Knobs:  BENCH_SERVE_ROUNDS / _DEPTH / _FEATURES for model size,
        BENCH_SERVE_ITERS to scale the timed loops,
        BENCH_SERVE_REPS for min-of-N (default 3),
        BENCH_SERVE_FLEET=0 to skip the (multi-process, slower) fleet
        sections.
"""
from __future__ import annotations

import hashlib
import json
import os
import platform as _platform
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BATCH_SIZES = (1, 64, 4096)
ITERS = {1: 400, 64: 200, 4096: 30}
# (n_replicas, n_shards) sweep: the single-dispatcher column up to 4,
# then the sharded front-end past the dispatcher ceiling
FLEET_CONFIGS = ((1, 1), (2, 1), (4, 1), (4, 2), (8, 2), (8, 4), (12, 4))
FLEET_BATCH = 512       # rows per fleet request
FLEET_CLIENTS = 8       # closed-loop client threads (floor; scales with fleet)
FLEET_REQS_PER_CLIENT = 40

_HOST_FP = None


def _host_fingerprint() -> dict:
    """What makes a wall-clock number comparable: core count, arch, and
    the SIMD capability set (the ladder's convention).  Stamped on every
    section of BENCH_SERVE.json; --diff refuses (exit 2) when the ids
    differ — a cross-host wall ratio is not a regression signal, it is
    two different machines."""
    global _HOST_FP
    if _HOST_FP is None:
        from xgboost_tpu.utils import native as _native

        simd = _native.simd_info()
        info = dict(cores=os.cpu_count(), machine=_platform.machine(),
                    cpu_flags=sorted(simd.get("cpu_flags", [])),
                    lanes=simd.get("lanes"))
        blob = json.dumps(info, sort_keys=True).encode()
        info["id"] = hashlib.sha256(blob).hexdigest()[:12]
        _HOST_FP = info
    return _HOST_FP


def _stamp(section):
    """Attach the host fingerprint to a section dict (or to every row of
    a section list) so any later cross-file comparison can refuse
    cross-host pairs."""
    if isinstance(section, list):
        for row in section:
            _stamp(row)
    elif isinstance(section, dict):
        section["host"] = _host_fingerprint()
    return section


def _reps() -> int:
    return max(1, int(os.environ.get("BENCH_SERVE_REPS", "3")))


def train_model(rounds: int, depth: int, features: int,
                objective: str = "binary:logistic", num_class: int = 0):
    import xgboost_tpu as xtb

    rng = np.random.default_rng(0)
    X = rng.normal(size=(20_000, features)).astype(np.float32)
    margin = X[:, 0] + 0.5 * X[:, 1] - 0.25 * X[:, 2]
    params = {"objective": objective}
    if num_class:
        y = np.digitize(margin, np.linspace(-1.5, 1.5, num_class - 1)
                        ).astype(np.float32)
        params["num_class"] = num_class
    elif objective.startswith("reg:"):
        y = margin.astype(np.float32)
    else:
        y = (margin > 0).astype(np.float32)
    bst = xtb.train({**params, "max_depth": depth, "max_bin": 256},
                    xtb.DMatrix(X, label=y), rounds, verbose_eval=False)
    return bst, X


def bench_direct(eng, X, batch: int, iters: int) -> dict:
    """Per-request latency through the pre-compiled direct path
    (min-of-N walls; percentiles from the min-wall rep)."""
    rng = np.random.default_rng(batch)
    rows = [X[rng.integers(0, len(X) - batch + 1)
              or 0:][:batch] for _ in range(8)]
    for r in rows[:2]:  # shape warm-up (bucket already compiled by warmup())
        eng.predict("bench", r, direct=True)
    best_wall, best_lat = None, None
    for _ in range(_reps()):
        lat = np.empty(iters)
        t_all0 = time.perf_counter()
        for i in range(iters):
            t0 = time.perf_counter()
            eng.predict("bench", rows[i % len(rows)], direct=True)
            lat[i] = time.perf_counter() - t0
        wall = time.perf_counter() - t_all0
        if best_wall is None or wall < best_wall:
            best_wall, best_lat = wall, lat
    p50, p99 = np.percentile(best_lat, [50, 99])
    return {
        "batch": batch,
        "iters": iters,
        "reps": _reps(),
        "p50_ms": round(float(p50) * 1e3, 4),
        "p99_ms": round(float(p99) * 1e3, 4),
        "rows_per_s": round(batch * iters / best_wall, 1),
    }


def bench_concurrent(eng, X, n_threads: int = 4, per_thread: int = 100):
    """Batch-1 traffic from N threads through the micro-batcher: the
    coalescing path the engine exists for (min-of-N walls)."""
    errors = []

    def worker(tid, barrier):
        rng = np.random.default_rng(tid)
        try:
            barrier.wait(30)
            for _ in range(per_thread):
                eng.predict("bench", X[rng.integers(0, len(X))][None, :])
        except BaseException as e:  # pragma: no cover
            errors.append(repr(e))

    best_wall = None
    for _ in range(_reps()):
        barrier = threading.Barrier(n_threads)
        threads = [threading.Thread(target=worker, args=(t, barrier))
                   for t in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        wall = time.perf_counter() - t0
        best_wall = wall if best_wall is None else min(best_wall, wall)
    snap = eng.metrics_snapshot()
    return {
        "threads": n_threads,
        "requests": n_threads * per_thread,
        "reps": _reps(),
        "wall_s": round(best_wall, 3),
        "requests_per_s": round(n_threads * per_thread / best_wall, 1),
        "errors": errors,
        "engine_metrics": snap,
    }


# ---------------------------------------------------------------- fleet
def bench_fleet_coldstart(model_paths: dict, workdir: str) -> dict:
    """Replica warm-work seconds, cold vs warm persistent compile cache.

    The replica warms its DEFAULT bucket ladder (8..4096) for every
    model — the production configuration, where the AOT file covers
    every admission-policy bucket.  Within-run pairing: each rep starts
    a 1-replica fleet against a FRESH cache dir (cold: every program
    compiles) and then again against the dir that start just populated
    (warm: every program deserializes).  min-of-N on each side; the
    acceptance ratio compares the two minima.
    """
    from xgboost_tpu.serving import ServingFleet

    cold_s, warm_s = [], []
    info_cold = info_warm = None
    for rep in range(_reps()):
        cache = os.path.join(workdir, f"coldstart_cache_{rep}")
        for side, sink in (("cold", cold_s), ("warm", warm_s)):
            with ServingFleet(model_paths, n_replicas=1,
                              cache_dir=cache) as fleet:
                info = fleet.replica_info()[0]
            assert info["cache_state"] == side, (
                f"rep {rep}: expected a {side} cache, got "
                f"{info['cache_state']} (hits={info['aot_hits']} "
                f"compiled={info['aot_compiled']})")
            sink.append(float(info["warmup_s"]))
            if side == "cold":
                info_cold = info
            else:
                info_warm = info
    cold, warm = min(cold_s), min(warm_s)
    return {
        "reps": _reps(),
        "warmup_buckets": "default ladder (8..4096)",
        "models": len(model_paths),
        "programs": int(info_cold["aot_compiled"]),
        "cold_warmup_s": round(cold, 4),
        "warm_warmup_s": round(warm, 4),
        "speedup": round(cold / warm, 1),
        "pair_speedups": [round(c / w, 1) for c, w in zip(cold_s, warm_s)],
        "cold_info": {k: info_cold[k] for k in
                      ("aot_hits", "aot_compiled", "bringup_s")},
        "warm_info": {k: info_warm[k] for k in
                      ("aot_hits", "aot_compiled", "bringup_s")},
    }


def _fleet_configs() -> tuple:
    """The (n_replicas, n_shards) sweep, capped to what this host can
    actually demonstrate: a config with more replicas than max(4, cores)
    measures core-oversubscription, not dispatcher design.  Skips are
    LOUD (printed and recorded in the report) — a silently truncated
    sweep reads as 'measured everything' when it didn't."""
    cores = os.cpu_count() or 1
    cap = max(4, cores)
    run = tuple(c for c in FLEET_CONFIGS if c[0] <= cap)
    skipped = tuple(c for c in FLEET_CONFIGS if c[0] > cap)
    if skipped:
        print(f"fleet saturation: host has {cores} cores — skipping "
              f"{['%dx%d-shard' % c for c in skipped]} (replica counts "
              f"past max(4, cores)={cap} measure oversubscription)")
    return run, skipped


def _fleet_clients(n_replicas: int) -> int:
    """Closed-loop clients sized to the fleet, not a constant: window-1
    dispatch means a replica idles whenever no request is queued for it,
    so demonstrating N-replica scale-out needs comfortably more than N
    outstanding requests (3x keeps every shard's queue non-empty without
    drowning the host in client threads)."""
    return max(FLEET_CLIENTS, 3 * n_replicas)


def _fleet_load(fleet, Xa, Xb, n_clients) -> dict:
    """One closed-loop mixed two-model load: n_clients threads, each
    with a distinct tenant (the shard-routing key — distinct tenants
    spread a sharded fleet's traffic across every shard), alternating
    models request by request.  Returns wall + latencies."""
    lats = [None] * n_clients
    errors = []
    barrier = threading.Barrier(n_clients)

    def client(tid):
        lat = np.empty(FLEET_REQS_PER_CLIENT)
        tenant = f"c{tid}"
        try:
            barrier.wait(60)
            for i in range(FLEET_REQS_PER_CLIENT):
                model, X = (("a", Xa) if (tid + i) % 2 == 0
                            else ("b", Xb))
                t0 = time.perf_counter()
                fleet.predict(model, X, tenant=tenant, timeout=600)
                lat[i] = time.perf_counter() - t0
            lats[tid] = lat
        except BaseException as e:  # pragma: no cover
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(900)
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"fleet load errors: {errors[:3]}")
    return {"wall": wall, "lat": np.concatenate(lats)}


def _shard_counters(fleet, n_shards: int) -> dict:
    """Snapshot the per-shard counters (monotonic; callers diff before/
    after a timed window)."""
    ins = fleet._ins
    return {k: {"rows": ins.shard_rows.get(str(k)),
                "busy": ins.shard_rx_busy.get(str(k))}
            for k in range(n_shards)}


def bench_fleet_saturation(model_paths: dict, workdir: str,
                           features: int) -> list:
    """Sustained mixed-traffic throughput + p99 across the
    (n_replicas, n_shards) sweep in FLEET_CONFIGS.

    All configs run in THIS invocation (within-run pairs: the 1x1 row is
    the baseline every acceptance ratio divides by); per config,
    min-of-N walls with percentiles from the min-wall rep.  The shared
    warm cache keeps what's measured at steady state, not compile time.
    Client threads scale with the fleet (3x replicas) so the closed loop
    never becomes the bottleneck; each client carries its own tenant so
    shard routing spreads the load.  Sharded rows also record per-shard
    rows/s and the rx-loop busy fraction (time the shard's dispatcher-
    side rx threads spent OUT of the blocking recv — the dispatcher-
    ceiling signal the sharding exists to break)."""
    from xgboost_tpu.serving import ServingFleet

    cache = os.path.join(workdir, "saturation_cache")
    rng = np.random.default_rng(7)
    Xa = rng.normal(size=(FLEET_BATCH, features)).astype(np.float32)
    Xb = rng.normal(size=(FLEET_BATCH, features)).astype(np.float32)
    rows = []
    configs, _ = _fleet_configs()
    for n, shards in configs:
        n_clients = _fleet_clients(n)
        n_requests = n_clients * FLEET_REQS_PER_CLIENT
        with ServingFleet(model_paths, n_replicas=n, n_shards=shards,
                          cache_dir=cache,
                          warmup_buckets=(FLEET_BATCH,)) as fleet:
            _fleet_load(fleet, Xa, Xb, n_clients)  # warm pass, untimed
            best = None
            for _ in range(_reps()):
                c0 = _shard_counters(fleet, shards)
                r = _fleet_load(fleet, Xa, Xb, n_clients)
                r["shard_delta"] = {
                    k: {"rows": c1["rows"] - c0[k]["rows"],
                        "busy": c1["busy"] - c0[k]["busy"]}
                    for k, c1 in _shard_counters(fleet, shards).items()}
                if best is None or r["wall"] < best["wall"]:
                    best = r
        p50, p99 = np.percentile(best["lat"], [50, 99])
        wall = best["wall"]
        per_shard = [
            {"shard": k,
             "rows_per_s": round(d["rows"] / wall, 1),
             "rx_busy_frac": round(d["busy"] / wall, 4)}
            for k, d in sorted(best["shard_delta"].items())]
        row = {
            "n_replicas": n,
            "n_shards": shards,
            "clients": n_clients,
            "requests": n_requests,
            "batch": FLEET_BATCH,
            "reps": _reps(),
            "wall_s": round(wall, 3),
            "requests_per_s": round(n_requests / wall, 1),
            "rows_per_s": round(n_requests * FLEET_BATCH / wall, 1),
            "p50_ms": round(float(p50) * 1e3, 3),
            "p99_ms": round(float(p99) * 1e3, 3),
            "per_shard": per_shard,
        }
        rows.append(row)
        busy = max((s["rx_busy_frac"] for s in per_shard), default=0.0)
        print(f"fleet n={n} shards={shards}  "
              f"rows/s={row['rows_per_s']:.0f}  "
              f"p50={row['p50_ms']:.1f}ms  p99={row['p99_ms']:.1f}ms  "
              f"max rx busy={busy:.0%}")
    return rows


def bench_shed_vs_degrade(model_path: str, workdir: str,
                          features: int) -> dict:
    """Static queue-bound shedding vs governor-driven brownout under the
    SAME synthetic overload (docs/reliability.md "Resource pressure &
    graceful degradation").

    One replica, a tight queue (max_queue=8), closed-loop mixed traffic:
    4 gold clients (priority 2) against 8 free clients (priority -1),
    every client sequential.  Leg A (shed): governor nominal — the only
    defense is the queue bound, so free work interleaves into the
    replica whenever gold's queue drains and the window-1 dispatch makes
    every gold request eat head-of-line free execute time.  Leg B
    (degrade): the governor is at overload level 1 — free-class requests
    are browned out AT ADMISSION (`xtb_fleet_brownout_total`), so the
    replica serves gold exclusively.  The row reports per-class
    completions/sheds and gold's p50/p99 for both legs from the same
    fleet (a within-run pair per the host-noise convention; best-of-N
    legs by gold p99).
    """
    import concurrent.futures as cf

    from xgboost_tpu.reliability import resources
    from xgboost_tpu.serving import ServingFleet
    from xgboost_tpu.serving.batcher import QueueFullError
    from xgboost_tpu.serving.fleet import FleetConfig, SLOClass

    classes = {"gold": SLOClass("gold", priority=2, deadline_s=60.0),
               "free": SLOClass("free", priority=-1, deadline_s=60.0)}
    cfg = FleetConfig(n_replicas=1, max_queue=8, slo_classes=classes,
                      nthread_per_replica=1,
                      cache_dir=os.path.join(workdir, "svd_cache"),
                      warmup_buckets=(64,))
    rng = np.random.default_rng(5)
    Xq = rng.normal(size=(64, features)).astype(np.float32)
    gold_clients, free_clients, per_client = 4, 8, 25

    def one_leg(fleet) -> dict:
        out = {c: {"completed": 0, "shed": 0, "expired": 0}
               for c in classes}
        gold_lat = []
        lock = threading.Lock()

        def client(tenant, n):
            for _ in range(n):
                t0 = time.perf_counter()
                try:
                    fleet.predict("m", Xq, tenant=tenant, timeout=120)
                    dt = time.perf_counter() - t0
                    with lock:
                        out[tenant]["completed"] += 1
                        if tenant == "gold":
                            gold_lat.append(dt)
                except QueueFullError:
                    with lock:
                        out[tenant]["shed"] += 1
                except (TimeoutError, cf.TimeoutError):
                    with lock:
                        out[tenant]["expired"] += 1

        threads = ([threading.Thread(target=client,
                                     args=("gold", per_client))
                    for _ in range(gold_clients)]
                   + [threading.Thread(target=client,
                                       args=("free", per_client))
                      for _ in range(free_clients)])
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        wall = time.perf_counter() - t0
        p99 = (round(float(np.percentile(gold_lat, 99)) * 1e3, 2)
               if gold_lat else None)
        p50 = (round(float(np.percentile(gold_lat, 50)) * 1e3, 2)
               if gold_lat else None)
        return {"classes": out, "wall_s": round(wall, 3),
                "gold_p50_ms": p50, "gold_p99_ms": p99}

    legs = {}
    resources.reset()
    with ServingFleet({"m": model_path}, cfg) as fleet:
        fleet.predict("m", Xq, tenant="gold", timeout=600)  # warm pass
        best_shed = best_deg = None
        for _ in range(_reps()):
            resources.reset()
            r = one_leg(fleet)
            if best_shed is None or (r["gold_p99_ms"] or 1e9) < (
                    best_shed["gold_p99_ms"] or 1e9):
                best_shed = r
            resources.get_governor().degrade(
                "overload", "bench synthetic overload")
            import warnings as _w

            with _w.catch_warnings():
                _w.simplefilter("ignore", RuntimeWarning)
                r = one_leg(fleet)
            if best_deg is None or (r["gold_p99_ms"] or 1e9) < (
                    best_deg["gold_p99_ms"] or 1e9):
                best_deg = r
            resources.reset()
    legs["static_shed"] = best_shed
    legs["brownout_degrade"] = best_deg
    legs["reps"] = _reps()
    legs["clients"] = {"gold": gold_clients, "free": free_clients,
                      "requests_each": per_client}
    legs["max_queue"] = 8
    print(f"shed-vs-degrade: static gold p99={best_shed['gold_p99_ms']}ms "
          f"(free completed {best_shed['classes']['free']['completed']}"
          f"/shed {best_shed['classes']['free']['shed']}) | brownout "
          f"gold p99={best_deg['gold_p99_ms']}ms (free browned out "
          f"{best_deg['classes']['free']['shed']})")
    return legs


def _lifecycle_window(features: int):
    """The data the lifecycle section's v2 continues training on."""
    rng = np.random.default_rng(3)
    Xw = rng.normal(size=(4000, features)).astype(np.float32)
    yw = (Xw[:, 0] + 0.5 * Xw[:, 1] > 0).astype(np.float32)
    return Xw, yw


def bench_lifecycle_swap(workdir: str, features: int, v1_path: str,
                         v2_path: str) -> dict:
    """p99 during a hot swap vs steady state, with requests in flight.

    A 2-replica fleet serves v1 from a model store that already holds a
    continuation-trained v2 (training and gating excluded — this times
    the SWAP itself: double-buffered load + serialized activate).  Each
    rep alternates the active version under continuous client traffic;
    min-of-N swap walls with the during-swap p99 from the min-wall rep,
    steady-state p99 from the same run's between-swap windows (a
    within-run pair, per the host-noise convention).
    """
    from xgboost_tpu.lifecycle import LifecycleConfig, LifecycleManager
    from xgboost_tpu.serving import ModelStore, ServingFleet

    store = ModelStore(os.path.join(workdir, "lifecycle_store"))
    store.publish("m", v1_path)
    store.set_active("m", 1)
    store.publish("m", v2_path)

    Xq = _lifecycle_window(features)[0][:FLEET_BATCH]
    n_clients = 4
    lats, lock, errors = [], threading.Lock(), []
    stop = threading.Event()
    swaps = []
    with ServingFleet(store_dir=store.dir, n_replicas=2,
                      cache_dir=os.path.join(workdir, "lifecycle_cache"),
                      warmup_buckets=(FLEET_BATCH,)) as fleet:

        def client(tid):
            try:
                while not stop.is_set():
                    t0 = time.perf_counter()
                    fleet.predict("m", Xq, timeout=600)
                    with lock:
                        lats.append((t0, time.perf_counter() - t0))
            except BaseException as e:  # pragma: no cover
                errors.append(repr(e))

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_clients)]
        for t in threads:
            t.start()
        mgr = LifecycleManager(fleet, "m",
                               config=LifecycleConfig(rounds_per_cycle=1))
        time.sleep(1.0)  # steady-state lead-in
        target = 2
        for _ in range(_reps()):
            t0 = time.perf_counter()
            mgr.swap(target)
            swaps.append((t0, time.perf_counter()))
            target = 1 if target == 2 else 2
            time.sleep(0.5)  # steady window between swaps
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(900)
    if errors:
        raise RuntimeError(f"lifecycle swap bench errors: {errors[:3]}")

    walls = [t1 - t0 for t0, t1 in swaps]
    best = int(np.argmin(walls))
    during_best = [dt for (t, dt) in lats
                   if swaps[best][0] <= t <= swaps[best][1]]
    steady = [dt for (t, dt) in lats
              if not any(a <= t <= b for a, b in swaps)]
    in_flight = [len([1 for (t, _) in lats if a <= t <= b])
                 for a, b in swaps]
    return {
        "reps": _reps(),
        "n_replicas": 2,
        "clients": n_clients,
        "batch": FLEET_BATCH,
        "requests_total": len(lats),
        "swap_wall_s": round(min(walls), 4),
        "swap_walls_s": [round(w, 4) for w in walls],
        "requests_during_swap": in_flight[best],
        "requests_during_swap_all": in_flight,
        "p99_during_ms": round(float(np.percentile(during_best, 99)) * 1e3,
                               3) if during_best else None,
        "p99_steady_ms": round(float(np.percentile(steady, 99)) * 1e3, 3),
        "p50_steady_ms": round(float(np.percentile(steady, 50)) * 1e3, 3),
    }


def _bench_shape() -> tuple:
    return (int(os.environ.get("BENCH_SERVE_ROUNDS", "20")),
            int(os.environ.get("BENCH_SERVE_DEPTH", "6")),
            int(os.environ.get("BENCH_SERVE_FEATURES", "28")))


def _fleet_sections() -> bool:
    return os.environ.get("BENCH_SERVE_FLEET", "1") != "0"


def prepare(workdir: str) -> int:
    """The half that computes in-process, run as a child of :func:`main`:
    trains every model the run needs, benches the in-process engine, and
    leaves the model files and ``engine.json`` in ``workdir``.  It exits
    before any fleet starts: a process that has trained holds the device,
    and a replica that needs it would then fail or hang."""
    import jax

    import xgboost_tpu as xtb
    from xgboost_tpu.serving import ServingEngine

    rounds, depth, features = _bench_shape()
    scale = float(os.environ.get("BENCH_SERVE_ITERS", "1"))

    bst, X = train_model(rounds, depth, features)
    report = {
        "bench": "serving_engine",
        "platform": jax.default_backend(),
        "generated_unix": int(time.time()),
        "reps": _reps(),
        "host_cores": os.cpu_count(),
        "host": _host_fingerprint(),
        "model": {"rounds": rounds, "max_depth": depth, "features": features,
                  "objective": "binary:logistic"},
        "config": {"warmup_buckets": [1, 64, 4096], "max_batch": 4096,
                   "max_delay_us": 2000},
        "results": [],
    }
    with ServingEngine(max_batch=4096, max_delay_us=2000,
                       warmup_buckets=(1, 64, 4096)) as eng:
        eng.add_model("bench", bst)  # compiles every benchmarked bucket
        for b in BATCH_SIZES:
            iters = max(10, int(ITERS[b] * scale))
            r = bench_direct(eng, X, b, iters)
            report["results"].append(_stamp(r))
            print(f"batch={b:5d}  p50={r['p50_ms']:.3f}ms  "
                  f"p99={r['p99_ms']:.3f}ms  rows/s={r['rows_per_s']:.0f}")
        report["concurrent"] = _stamp(bench_concurrent(eng, X))
        steady = report["concurrent"]["engine_metrics"]["compiles_steady"]
        print(f"concurrent: {report['concurrent']['requests_per_s']:.0f} "
              f"req/s over {report['concurrent']['threads']} threads, "
              f"steady-state compiles={steady}")

    if _fleet_sections():
        # mixed-architecture set: the binary model above + a
        # multiclass + a regression one (distinct serve programs per
        # bucket each — a multi-tenant replica's real warm load), and the
        # lifecycle section's continuation-trained v2 of the first
        bst_b, _ = train_model(max(2, rounds // 2), max(3, depth - 2),
                               features, "multi:softprob", num_class=5)
        bst_c, _ = train_model(max(2, rounds // 2), max(3, depth - 1),
                               features, "reg:squarederror")
        Xw, yw = _lifecycle_window(features)
        cont = xtb.train(dict(bst.params), xtb.DMatrix(Xw, label=yw), 2,
                         verbose_eval=False, xgb_model=bst)
        for name, model in (("a", bst), ("b", bst_b), ("c", bst_c),
                            ("a2", cont)):
            model.save_model(os.path.join(workdir, name + ".json"))
    with open(os.path.join(workdir, "engine.json"), "w") as fh:
        json.dump(report, fh)
    return 0


def main(out_path: str) -> int:
    import subprocess

    _, _, features = _bench_shape()
    rc = 0
    workdir = tempfile.mkdtemp(prefix="xtb_bench_fleet_")
    try:
        subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--prepare", workdir], check=True)
        with open(os.path.join(workdir, "engine.json")) as fh:
            report = json.load(fh)
        steady = report["concurrent"]["engine_metrics"]["compiles_steady"]
        if _fleet_sections():
            pa, pb, pc, pa2 = (os.path.join(workdir, n + ".json")
                               for n in ("a", "b", "c", "a2"))
            cs = bench_fleet_coldstart({"a": pa, "b": pb, "c": pc}, workdir)
            report["fleet_coldstart"] = _stamp(cs)
            print(f"fleet coldstart ({cs['programs']} programs): "
                  f"cold={cs['cold_warmup_s']:.2f}s "
                  f"warm={cs['warm_warmup_s']:.3f}s "
                  f"speedup={cs['speedup']:.0f}x")
            sat = bench_fleet_saturation({"a": pa, "b": pb}, workdir,
                                         features)
            report["fleet_saturation"] = _stamp(sat)
            base = sat[0]["rows_per_s"]
            top_row = max(sat, key=lambda r: r["rows_per_s"])
            top = top_row["rows_per_s"]
            unsharded = [r for r in sat if r["n_shards"] == 1]
            report["fleet_scaling_vs_single"] = round(
                unsharded[-1]["rows_per_s"] / base, 2)
            report["fleet_best_scaling"] = round(top / base, 2)
            report["fleet_best_config"] = {
                "n_replicas": top_row["n_replicas"],
                "n_shards": top_row["n_shards"],
                "rows_per_s": top}
            _, skipped = _fleet_configs()
            if skipped:
                report["fleet_configs_skipped"] = [
                    {"n_replicas": n, "n_shards": s} for n, s in skipped]
            max_reps = max(r["n_replicas"] for r in sat)
            cores = os.cpu_count() or 1
            if cores < 2 * max_reps:
                # N replicas + dispatchers + clients need ~2N cores to
                # demonstrate replica-limited scale-out; below that the
                # rows measure core-oversubscription, not the dispatcher
                # design (total CPU bounds fleet/single at cores/1 when a
                # single replica already saturates its core)
                report["fleet_scaling_note"] = (
                    f"host-bound: {cores} cores for "
                    f"{max_reps} replicas + dispatchers; "
                    f"theoretical scaling ceiling ~{cores}.0x")
            print(f"fleet best {top_row['n_replicas']}x"
                  f"{top_row['n_shards']}-shard vs single: "
                  f"{top / base:.2f}x "
                  f"({report.get('fleet_scaling_note', 'replica-limited')})")
            svd = bench_shed_vs_degrade(pa, workdir, features)
            report["shed_vs_degrade"] = _stamp(svd)
            ls = bench_lifecycle_swap(workdir, features, pa, pa2)
            report["lifecycle_swap"] = _stamp(ls)
            print(f"lifecycle swap: wall={ls['swap_wall_s'] * 1e3:.0f}ms  "
                  f"{ls['requests_during_swap']} requests in flight  "
                  f"p99 during={ls['p99_during_ms']}ms "
                  f"steady={ls['p99_steady_ms']}ms")
            # The original 10x acceptance (PR 8) was measured on a 2-core
            # host where the cold side compiled serially (2.31s).  On a
            # many-core host XLA parallelizes the cold compiles (24
            # cores: 1.40s) while the warm side is serial
            # deserialization with a fixed ~0.16s floor — the RATIO
            # shrinks as the host grows even though both absolute walls
            # improve.  Gate at 8x by default, overridable for odd hosts.
            min_x = float(os.environ.get("BENCH_COLDSTART_MIN_X", "8"))
            if cs["speedup"] < min_x:
                print(f"FAIL: warm-cache cold-start speedup "
                      f"{cs['speedup']}x < {min_x}x", file=sys.stderr)
                rc = 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
        fh.write("\n")
    print(f"wrote {out_path}")
    if steady:
        print("FAIL: engine recompiled after warm-up", file=sys.stderr)
        rc = 1
    return rc


def diff_main(old_path: str, new_path: str) -> int:
    """Compare two BENCH_SERVE.json files section by section; refuses
    (exit 2) when any compared pair was produced on different hosts —
    cross-host wall-clock ratios are two machines, not a regression."""
    with open(old_path) as fh:
        old = json.load(fh)
    with open(new_path) as fh:
        new = json.load(fh)
    rc = 0

    def hosts_match(name, a, b) -> bool:
        nonlocal rc
        ha, hb = (a or {}).get("host"), (b or {}).get("host")
        if not ha or not hb or ha.get("id") != hb.get("id"):
            print(f"[{name}] REFUSED: rows are from different hosts "
                  f"({(ha or {}).get('id', 'unstamped')} vs "
                  f"{(hb or {}).get('id', 'unstamped')}) — wall-clock "
                  f"deltas across hosts are not comparable")
            rc = 2
            return False
        return True

    def pct(name, wa, wb, unit):
        if wa and wb:
            print(f"[{name}] {wa}{unit} -> {wb}{unit} "
                  f"({(wb - wa) / wa * 100.0:+.1f}%)")

    oldr = {r["batch"]: r for r in old.get("results", [])}
    for b, rb in {r["batch"]: r for r in new.get("results", [])}.items():
        ra = oldr.get(b)
        if ra and hosts_match(f"direct batch={b}", ra, rb):
            pct(f"direct batch={b} p99", ra["p99_ms"], rb["p99_ms"], "ms")
    ca, cb = old.get("concurrent"), new.get("concurrent")
    if ca and cb and hosts_match("concurrent", ca, cb):
        pct("concurrent req/s", ca["requests_per_s"],
            cb["requests_per_s"], "")
    key = lambda r: (r.get("n_replicas"), r.get("n_shards", 1))
    olds = {key(r): r for r in old.get("fleet_saturation", [])}
    for k, rb in {key(r): r
                  for r in new.get("fleet_saturation", [])}.items():
        ra = olds.get(k)
        name = f"fleet {k[0]}x{k[1]}-shard"
        if ra and hosts_match(name, ra, rb):
            pct(f"{name} rows/s", ra["rows_per_s"], rb["rows_per_s"], "")
    return rc


if __name__ == "__main__":
    if "--prepare" in sys.argv:
        sys.exit(prepare(sys.argv[sys.argv.index("--prepare") + 1]))
    if "--diff" in sys.argv:
        i = sys.argv.index("--diff")
        sys.exit(diff_main(sys.argv[i + 1], sys.argv[i + 2]))
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "BENCH_SERVE.json"))
