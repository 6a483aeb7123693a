"""BASELINE ladder configs #2-#4 vs the reference oracle on identical data.

Runs the three headline training configs from BASELINE.md — HIGGS-class
binary (11M x 28), covertype-class multiclass (581k x 54, 7 classes), and
MSLR-class ranking (30k+ queries) — through BOTH this framework and the
reference oracle (/root/oracle_build, hist method), on the SAME synthetic
stand-in arrays (zero-egress image: the real datasets cannot be fetched;
shapes, sparsity and label structure mirror them).  Records wall-clock and
quality (AUC / merror / ndcg@10 computed by ONE metric implementation —
ours, oracle-parity-tested — over both models' predictions) into
BENCH_LADDER.json.

Scale: `LADDER_SCALE` (fraction of full rows, default 0.05 on CPU / 1.0 on
TPU) bounds single-core CPU runtime; the recorded rows are what actually
ran, and `scale` says how far from the full shape that is.  The TPU
watcher runs this at full scale in its final stage.

Usage:  python scripts/bench_ladder.py [out.json]
"""
from __future__ import annotations

import hashlib
import json
import os
import platform as _platform
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ORACLE_PKG = "/root/oracle_build/pkg"

_HOST_FP = None


def _host_fingerprint() -> dict:
    """What makes a wall-clock number comparable: core count, arch, and
    the SIMD capability set.  Stamped on every ladder row; any path that
    compares rows across files (archived-oracle reuse, --diff) must
    refuse when the ids differ — a cross-host wall ratio is not a
    regression signal, it is two different machines."""
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


def diff_main(old_path: str, new_path: str) -> int:
    """Compare two ladder files config-by-config; refuses (exit 2) when
    any compared pair was produced on different hosts."""
    with open(old_path) as fh:
        old = {r["config"]: r for r in json.load(fh)}
    with open(new_path) as fh:
        new = {r["config"]: r for r in json.load(fh)}
    rc = 0
    for name in sorted(set(old) & set(new)):
        a, b = old[name], new[name]
        ha, hb = a.get("host"), b.get("host")
        if not ha or not hb or ha.get("id") != hb.get("id"):
            print(f"[{name}] REFUSED: rows are from different hosts "
                  f"({(ha or {}).get('id', 'unstamped')} vs "
                  f"{(hb or {}).get('id', 'unstamped')}) — wall-clock "
                  f"deltas across hosts are not comparable")
            rc = 2
            continue
        wa, wb = a.get("ours_wall_s"), b.get("ours_wall_s")
        if wa and wb:
            print(f"[{name}] ours_wall_s {wa} -> {wb} "
                  f"({(wb - wa) / wa * 100.0:+.1f}%)  quality "
                  f"{a.get('ours_quality')} -> {b.get('ours_quality')}")
    return rc

FULL_CONFIGS = [
    # BASELINE.md ladder #2: HIGGS 11M x 28, binary:logistic, AUC
    dict(name="higgs_binary", rows=11_000_000, cols=28, kind="binary",
         objective="binary:logistic", metric="auc", rounds=5,
         params=dict(max_depth=8, eta=0.3, max_bin=256)),
    # ladder #3: covertype 581k x 54, 7 classes, multi:softprob, merror
    dict(name="covertype_softprob", rows=581_012, cols=54, kind="multi",
         classes=7, objective="multi:softprob", metric="merror", rounds=5,
         params=dict(max_depth=8, eta=0.3, max_bin=256)),
    # ladder #4: MSLR-WEB30K 3.77M docs / 31k queries, rank:ndcg, ndcg@10
    dict(name="mslr_ndcg", rows=3_771_125, cols=136, kind="rank",
         groups=31_531, objective="rank:ndcg", metric="ndcg@10", rounds=5,
         params=dict(max_depth=8, eta=0.3, max_bin=256)),
    # ladder #5 slice: Criteo-class out-of-core — OUR side streams zstd
    # pages (ExtMemQuantileDMatrix); the oracle trains in-memory on the
    # same rows (its extmem needs a disk cache pass; quality is the
    # comparable axis here, scale the honest caveat)
    dict(name="criteo_extmem", rows=1_000_000_000, cols=39, kind="extmem",
         objective="binary:logistic", metric="auc", rounds=5,
         params=dict(max_depth=8, eta=0.3, max_bin=256)),
]


def make_data(cfg, scale: float, seed: int = 0):
    rng = np.random.default_rng(seed)
    if cfg["kind"] == "extmem":
        # bounded stand-in: page count scales, page size fixed; cap keeps
        # the 1-core CPU run finite (watcher sets a bigger cap on TPU)
        cap = max(int(os.environ.get("LADDER_EXTMEM_CAP", "262144")),
                  65536)  # below one page the row floor would hit zero
        R = int(min(max(cfg["rows"] * scale, 64 * 1024), cap))
        R = (R // 65536) * 65536
        F = cfg["cols"]
        X = rng.normal(size=(R, F)).astype(np.float32)
        X[rng.random((R, F)) < 0.25] = np.nan  # Criteo-like sparsity
        lin = (np.nan_to_num(X[:, 0]) * 1.2 - np.nan_to_num(X[:, 1])
               + 0.5 * np.nan_to_num(X[:, 2]) * np.nan_to_num(X[:, 3]))
        y = (lin + rng.normal(scale=0.5, size=R) > 0).astype(np.float32)
        return R, X, y, None
    R = max(int(cfg["rows"] * scale), 10_000)
    F = cfg["cols"]
    X = rng.normal(size=(R, F)).astype(np.float32)
    X[rng.random((R, F)) < 0.02] = np.nan  # HIGGS-like light missingness
    lin = (np.nan_to_num(X[:, 0]) * 1.2 - np.nan_to_num(X[:, 1])
           + 0.5 * np.nan_to_num(X[:, 2]) * np.nan_to_num(X[:, 3]))
    if cfg["kind"] == "binary":
        y = (lin + rng.normal(scale=0.5, size=R) > 0).astype(np.float32)
        return R, X, y, None
    if cfg["kind"] == "multi":
        K = cfg["classes"]
        z = lin + rng.normal(scale=0.5, size=R)
        y = np.clip(((z - z.min()) / (np.ptp(z) + 1e-9) * K).astype(np.int64),
                    0, K - 1).astype(np.float32)
        return R, X, y, None
    # ranking: ~120 docs/query like MSLR; graded 0-4 relevance
    G = max(int(cfg["groups"] * scale), 100)
    sizes = rng.integers(40, 200, size=G)
    R = int(sizes.sum())
    X = rng.normal(size=(R, cfg["cols"])).astype(np.float32)
    rel = np.clip((X[:, 0] + 0.5 * rng.normal(size=R) + 2.0).astype(np.int64),
                  0, 4).astype(np.float32)
    return R, X, rel, sizes.astype(np.int64)


def eval_quality(metric, preds, y, group_sizes):
    from xgboost_tpu.metric import create_metric

    fn, _name = create_metric(metric)  # returns (callable, resolved name)
    kw = {}
    if group_sizes is not None:
        kw["group_ptr"] = np.concatenate([[0], np.cumsum(group_sizes)])
    return float(fn(np.asarray(preds), np.asarray(y, np.float64), **kw))


# nthread values for the host-parallelism scaling sweep (satellite of the
# ParallelFor PR): 1 / 4 / all-cores ("0" resolves the default).  Override
# with LADDER_NTHREAD="1,2,0"; LADDER_NTHREAD="" disables the sweep (the
# headline run always uses all cores and records what it used).
def _sweep_nthreads():
    raw = os.environ.get("LADDER_NTHREAD", "1,4,0")
    out = []
    for tok in raw.split(","):
        tok = tok.strip()
        if tok:
            out.append(int(tok))
    return out


# simd levels for the lane-width scaling sweep (round 7): each level
# re-runs the warmed program at nthread=1 — the per-core roofline the SIMD
# work targets — plus one all-cores vector run to show the SIMD and
# threading wins COMPOSE.  Results are bitwise level-invariant
# (docs/native_threading.md), so the sweep times identical outputs.
# Override with LADDER_SIMD="scalar,auto"; LADDER_SIMD="" disables.
def _sweep_simd():
    raw = os.environ.get("LADDER_SIMD", "scalar,auto")
    levels = [tok.strip() for tok in raw.split(",") if tok.strip()]
    from xgboost_tpu.utils import native

    for lvl in levels:  # typos fail HERE, not mid-ladder after a config ran
        native.set_simd(lvl)
    native.set_simd("auto")
    return levels


# LADDER_REPS=N takes the MINIMUM of N runs per sweep point (default 1).
# On time-shared bench hosts single-shot walls swing 2-3x with scheduler
# noise; min-of-N is the standard estimator for the code's actual cost.
def _reps() -> int:
    return max(1, int(os.environ.get("LADDER_REPS", "1")))


def _timed_min(fn) -> float:
    best = float("inf")
    for _ in range(_reps()):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run_ours(cfg, X, y, group_sizes):
    import xgboost_tpu as xtb

    if cfg["kind"] == "extmem":
        from xgboost_tpu.data.extmem import DataIter, ExtMemQuantileDMatrix

        page = 65536

        class Pages(DataIter):
            def __init__(self):
                super().__init__()
                self._i = 0

            def next(self, input_data):
                if self._i * page >= len(y):
                    return 0
                lo = self._i * page
                input_data(data=X[lo:lo + page], label=y[lo:lo + page])
                self._i += 1
                return 1

            def reset(self):
                self._i = 0

        d = ExtMemQuantileDMatrix(Pages(),
                                  max_bin=cfg["params"]["max_bin"])
    else:
        d = xtb.DMatrix(X, label=y)
    if group_sizes is not None:
        d.set_group(group_sizes)
    p = {"objective": cfg["objective"], **cfg["params"]}
    if cfg["kind"] == "multi":
        p["num_class"] = cfg["classes"]
    # warm the jit cache (and the ellpack build) so the timed run measures
    # steady-state boosting, not XLA compilation — the reference's kernels
    # are AOT, so this is the like-for-like comparison
    xtb.train(p, d, 1, verbose_eval=False)
    t0 = time.perf_counter()
    bst = xtb.train(p, d, cfg["rounds"], verbose_eval=False)
    # predictions force full materialization (train is async under jit)
    preds = np.asarray(bst.predict(d))
    dt = time.perf_counter() - t0

    # nthread scaling sweep over the SAME warmed program cache: pool width
    # is not a jit cache key (results are bitwise nthread-invariant,
    # docs/native_threading.md), so each re-run times only the native
    # kernels at a different width.  The width rides the params dict — the
    # same plumbing XGBoosterSetParam("nthread") uses.
    from xgboost_tpu.utils import native

    def train_predict(params):
        b2 = xtb.train(params, d, cfg["rounds"], verbose_eval=False)
        np.asarray(b2.predict(d))

    scaling = {}
    for n in _sweep_nthreads():
        wall = _timed_min(lambda: train_predict({**p, "nthread": n}))
        scaling[f"nthread={n if n > 0 else 'all'}"] = dict(
            wall_s=round(wall, 2), effective=native.get_nthread())

    # lane-width sweep over the same warmed cache: simd level is applied
    # inside the native kernels at execution time, so flipping it re-times
    # the identical program with different (identical-output) bodies.  The
    # pool width must ride the params dict like the nthread sweep above —
    # train() re-applies the params' width, so a bare set_nthread(1) here
    # would be silently reset to all cores at the first configure.
    simd_scaling = {}
    for level in _sweep_simd():
        eff = native.set_simd(level)
        wall = _timed_min(lambda: train_predict({**p, "nthread": 1}))
        simd_scaling[f"{level}@nthread=1"] = dict(
            wall_s=round(wall, 2), effective=eff)
    if simd_scaling:
        native.set_simd("auto")
        wall = _timed_min(lambda: train_predict({**p, "nthread": 0}))
        simd_scaling["auto@nthread=all"] = dict(
            wall_s=round(wall, 2), effective=native.get_simd())
    native.set_simd("auto")
    native.set_nthread(0)  # back to the defaults for the next config
    return dt, preds, scaling, simd_scaling


def run_oracle(cfg, X, y, group_sizes):
    sys.path.insert(0, ORACLE_PKG)
    import xgboost as xgb  # the oracle build

    d = xgb.DMatrix(X, label=y, missing=np.nan)
    if group_sizes is not None:
        d.set_group(group_sizes)
    p = {"objective": cfg["objective"], "tree_method": "hist",
         "nthread": os.cpu_count(), **cfg["params"]}
    if cfg["kind"] == "multi":
        p["num_class"] = cfg["classes"]
    t0 = time.perf_counter()
    bst = xgb.train(p, d, num_boost_round=cfg["rounds"])
    preds = np.asarray(bst.predict(d))
    dt = time.perf_counter() - t0
    return dt, preds


def main() -> None:
    out_path = sys.argv[1] if len(sys.argv) > 1 else "BENCH_LADDER.json"
    # When the oracle build is unavailable (this container has no
    # /root/reference checkout to rebuild it from), fall back to the PRIOR
    # ladder file's oracle wall/quality per config — valid as a comparison
    # only when rows/scale/platform match, which we check, and labeled with
    # its provenance in the emitted row.
    prior_oracle = {}
    if os.path.exists(out_path):
        try:
            with open(out_path) as fh:
                for row in json.load(fh):
                    if row.get("oracle_wall_s") is not None:
                        prior_oracle[row["config"]] = row
        except Exception:  # noqa: BLE001 - a corrupt prior file is not fatal
            prior_oracle = {}
    import jax

    platform = jax.devices()[0].platform
    scale = float(os.environ.get("LADDER_SCALE",
                                 "1.0" if platform == "tpu" else "0.05"))
    rows_out = []
    for cfg in FULL_CONFIGS:
        R, X, y, groups = make_data(cfg, scale)
        print(f"[{cfg['name']}] rows={R} cols={cfg['cols']} "
              f"rounds={cfg['rounds']} scale={scale}", flush=True)
        ours_s, ours_pred, scaling, simd_scaling = run_ours(cfg, X, y, groups)
        ours_q = eval_quality(cfg["metric"], ours_pred, y, groups)
        print(f"  ours:   {ours_s:8.1f}s  {cfg['metric']}={ours_q:.5f}  "
              f"scaling={scaling}  simd={simd_scaling}", flush=True)
        try:
            orc_s, orc_pred = run_oracle(cfg, X, y, groups)
            orc_q = eval_quality(cfg["metric"], orc_pred, y, groups)
            print(f"  oracle: {orc_s:8.1f}s  {cfg['metric']}={orc_q:.5f}",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"  oracle FAILED: {e!r}", flush=True)
            orc_s, orc_q = None, None
        oracle_source = "fresh"
        note = None
        if orc_s is None:
            prev = prior_oracle.get(cfg["name"])
            prev_host = (prev or {}).get("host") or {}
            if (prev and prev.get("rows") == R
                    and prev.get("platform") == platform
                    and prev_host.get("id") != _host_fingerprint()["id"]):
                # a cross-host oracle wall is not a baseline — refuse it
                # loudly rather than mix hosts into speed_vs_oracle
                print(f"  oracle: archived numbers REFUSED — host "
                      f"{prev_host.get('id', 'unstamped')} != this host "
                      f"{_host_fingerprint()['id']}", flush=True)
                prev = None
            if (prev and prev.get("rows") == R
                    and prev.get("platform") == platform):
                orc_s = prev["oracle_wall_s"]
                orc_q = prev.get("oracle_quality")
                oracle_source = "archived (oracle build unavailable)"
                note = ("oracle walls are archived from an earlier run "
                        "on THIS host (fingerprint-matched) — "
                        "like-for-like, but from an older session")
                print(f"  oracle: {orc_s:8.1f}s  [archived numbers — "
                      f"same rows/platform/host]", flush=True)
        from xgboost_tpu.utils import native as _native

        rows_out.append(dict(
            config=cfg["name"], rows=R, cols=cfg["cols"],
            full_rows=cfg["rows"], scale=scale, rounds=cfg["rounds"],
            objective=cfg["objective"], metric=cfg["metric"],
            platform=platform, host=_host_fingerprint(),
            nthread=_native.get_nthread(), cores=os.cpu_count(),
            simd=_native.simd_info(), sweep_reps=_reps(),
            ours_wall_s=round(ours_s, 2), ours_quality=round(ours_q, 6),
            nthread_scaling=scaling,
            simd_scaling=simd_scaling,
            oracle_wall_s=None if orc_s is None else round(orc_s, 2),
            oracle_quality=None if orc_q is None else round(orc_q, 6),
            oracle_source=oracle_source,
            **({"note": note} if note else {}),
            speed_vs_oracle=(None if orc_s is None
                             else round(orc_s / ours_s, 4)),
        ))
        with open(out_path, "w") as fh:  # checkpoint after each config
            json.dump(rows_out, fh, indent=1)
    print(json.dumps({"ladder": rows_out}), flush=True)


# ---------------------------------------------------------------------------
# Out-of-core full-scale rows (ISSUE 12 / ROADMAP item 2): --extmem appends
#   extmem_scaling     — paged vs resident at EQUAL scale, prefetch on/off,
#                        world 1/2 (min-of-N, honest host-bound notes)
#   higgs_full         — the committed full-scale HIGGS-11M 100+-round CPU
#                        number, warmup amortized honestly (the wall
#                        INCLUDES XLA compile + ellpack build)
#   criteo_extmem_40m  — Criteo-shaped sparse/categorical ~40M+ rows,
#                        paged, peak RSS recorded vs the resident-matrix
#                        size it avoids
# Each row runs in a fresh subprocess so peak-RSS numbers are clean.
# ---------------------------------------------------------------------------

EXTMEM_ROW_NAMES = ("extmem_scaling", "higgs_full", "criteo_extmem_40m")


def _peak_rss_mb() -> float:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _extmem_counters():
    from xgboost_tpu.data import extmem

    ins = extmem.instruments()
    return {"decode_s": ins[0].get(), "wait_s": ins[1].get(),
            "overlap_s": ins[2].get(), "pages": ins[3].get()}


def _counter_delta(before):
    now = _extmem_counters()
    return {k: round(now[k] - before[k], 3) for k in before}


def _scaling_page(shard: int, rows: int, cols: int):
    rng = np.random.default_rng(9000 + shard)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    X[rng.random(X.shape) < 0.02] = np.nan
    y = (np.nan_to_num(X[:, 0]) * 1.2 - np.nan_to_num(X[:, 1])
         + 0.5 * np.nan_to_num(X[:, 2]) * np.nan_to_num(X[:, 3])
         + rng.normal(scale=0.5, size=rows) > 0).astype(np.float32)
    return X, y


def _scaling_iter_cls(n_pages: int, page_rows: int, cols: int):
    import xgboost_tpu as xtb

    class Pages(xtb.DataIter):
        def __init__(self, shards):
            super().__init__()
            self._shards, self._i = list(shards), 0

        def reset(self):
            self._i = 0

        def next(self, input_data):
            if self._i >= len(self._shards):
                return 0
            X, y = _scaling_page(self._shards[self._i], page_rows, cols)
            input_data(data=X, label=y)
            self._i += 1
            return 1

    return Pages


def _scaling_world2_worker(rank, world, *, n_pages, page_rows, cols, params,
                           rounds, out_dir):
    import xgboost_tpu as xtb

    Pages = _scaling_iter_cls(n_pages, page_rows, cols)

    def data_fn(smap, rank, world):
        return Pages(smap.shards_of(rank))

    cfg = xtb.ExtMemConfig(data_fn, num_shards=n_pages,
                           max_bin=params["max_bin"])
    # build the paged matrix ONCE: the timed wall must match the world-1
    # legs (train + predict over already-ingested pages), not re-pay
    # ingest per call
    d, _evals = cfg.build()
    xtb.train(params, d, 1, verbose_eval=False)  # warm the jit cache
    t0 = time.perf_counter()
    bst = xtb.train(params, d, rounds, verbose_eval=False)
    np.asarray(bst.predict(d))
    wall = time.perf_counter() - t0
    with open(os.path.join(out_dir, f"w{rank}.wall"), "w") as fh:
        fh.write(str(wall))


def bench_row_extmem_scaling() -> dict:
    """Paged-vs-resident at equal scale.  The paged legs run with the host
    page cache DISABLED (XTB_EXTMEM_HOST_CACHE_MB=0) so every level pays
    the real stage cost — that is the streaming regime the prefetch
    pipeline exists for; with the default cache budget the pages of this
    size are simply resident after round 1 and the legs converge."""
    import functools
    import tempfile

    import xgboost_tpu as xtb

    scale = float(os.environ.get("LADDER_EXTMEM_SCALE", "1.0"))
    n_pages, cols = 16, 28
    page_rows = max(int(65536 * scale), 4096)
    rounds = 5
    params = {"objective": "binary:logistic", "max_depth": 8, "eta": 0.3,
              "max_bin": 256}
    Pages = _scaling_iter_cls(n_pages, page_rows, cols)

    os.environ["XTB_EXTMEM_HOST_CACHE_MB"] = "0"
    d_ext = xtb.ExtMemQuantileDMatrix(Pages(range(n_pages)), max_bin=256)

    gen = [_scaling_page(s, page_rows, cols) for s in range(n_pages)]
    X = np.concatenate([p[0] for p in gen])
    y = np.concatenate([p[1] for p in gen])
    del gen
    d_res = xtb.DMatrix(X, label=y)

    def timed_leg(d, extra):
        p = {**params, **extra}
        xtb.train(p, d, 1, verbose_eval=False)  # warm the jit cache
        before = _extmem_counters()

        def once():
            bst = xtb.train(p, d, rounds, verbose_eval=False)
            np.asarray(bst.predict(d))

        wall = _timed_min(once)
        return wall, _counter_delta(before)

    legs = {}
    wall, _ = timed_leg(d_res, {})
    legs["resident_world1"] = dict(wall_s=round(wall, 2))
    wall, ctr = timed_leg(d_ext, {"_extmem_prefetch": "1"})
    legs["paged_world1_prefetch"] = dict(wall_s=round(wall, 2), extmem=ctr)
    wall, ctr = timed_leg(d_ext, {"_extmem_prefetch": "0"})
    legs["paged_world1_noprefetch"] = dict(wall_s=round(wall, 2), extmem=ctr)
    del d_ext, X, y, d_res

    # world 2 over the tracker relay: per-worker steady-state walls (the
    # workers time their own warmed runs; spawn/rendezvous excluded).
    # Pickle the worker under its importable module name, not __main__ —
    # the spawned children re-import it from scripts/ (launcher mod_dir).
    from xgboost_tpu.launcher import run_distributed

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import bench_ladder as _mod

    with tempfile.TemporaryDirectory(prefix="xtb_lad_w2_") as tmp:
        run_distributed(
            functools.partial(
                _mod._scaling_world2_worker, n_pages=n_pages,
                page_rows=page_rows, cols=cols, params=params,
                rounds=rounds, out_dir=tmp),
            num_workers=2, platform="cpu", timeout=1800,
            rendezvous="tracker")
        walls = [float(open(os.path.join(tmp, f"w{r}.wall")).read())
                 for r in range(2)]
    legs["paged_world2_prefetch"] = dict(
        wall_s=round(max(walls), 2), per_worker=[round(w, 2) for w in walls])

    return dict(
        config="extmem_scaling", rows=n_pages * page_rows, cols=cols,
        pages=n_pages, page_rows=page_rows, scale=scale, rounds=rounds,
        platform="cpu", cores=os.cpu_count(), sweep_reps=_reps(),
        host_cache_mb=0, legs=legs,
        note=("paged legs re-stage every page each level (host cache "
              "disabled) — the streaming regime; world-2 walls are "
              "per-worker steady state over the socket relay on ONE "
              "host, so they measure composition overhead, not "
              "scale-out"),
    )


def bench_row_higgs_full() -> dict:
    import xgboost_tpu as xtb

    rows = int(float(os.environ.get("LADDER_FULL_ROWS", "11000000")))
    rounds = int(os.environ.get("LADDER_FULL_ROUNDS", "100"))
    cfg = dict(name="higgs_full", rows=rows, cols=28, kind="binary",
               objective="binary:logistic", metric="auc", rounds=rounds,
               params=dict(max_depth=8, eta=0.3, max_bin=256))
    R, X, y, _ = make_data(cfg, 1.0)
    t0 = time.perf_counter()
    d = xtb.DMatrix(X, label=y)
    p = {"objective": cfg["objective"], **cfg["params"]}
    bst = xtb.train(p, d, rounds, verbose_eval=False)
    preds = np.asarray(bst.predict(d))
    wall = time.perf_counter() - t0
    q = eval_quality("auc", preds, y, None)
    return dict(
        config="higgs_full", rows=R, cols=28, full_rows=rows, scale=1.0,
        rounds=rounds, objective=cfg["objective"], metric="auc",
        platform="cpu", cores=os.cpu_count(),
        ours_wall_s=round(wall, 2), ours_quality=round(q, 6),
        peak_rss_mb=round(_peak_rss_mb(), 1),
        note=("full-scale in-memory run; the wall INCLUDES sketch + "
              "ellpack build + XLA compile (one-shot costs amortized "
              "honestly over the 100-round run, no warmup subtraction)"),
    )


def bench_row_criteo_extmem() -> dict:
    import gc

    import xgboost_tpu as xtb

    n_pages = int(os.environ.get("LADDER_CRITEO_PAGES", "64"))
    page_rows = int(os.environ.get("LADDER_CRITEO_PAGE_ROWS", "655360"))
    rounds = 5
    n_num, n_cat = 13, 26
    cols = n_num + n_cat
    n_cats = 100
    # max_bin 128 keeps page codes in u8 (129 symbols incl. the missing
    # sentinel; 256 would tip the pages into int16 and double the store),
    # and the host/device page-cache budget is the documented RSS bound
    # knob (docs/extmem.md) — hot pages stay cached, the rest re-stage
    max_bin = int(os.environ.get("LADDER_CRITEO_MAX_BIN", "128"))
    os.environ.setdefault("XTB_EXTMEM_HOST_CACHE_MB", "512")

    def page(shard: int):
        rng = np.random.default_rng(7000 + shard)
        X = np.empty((page_rows, cols), np.float32)
        X[:, :n_num] = rng.normal(size=(page_rows, n_num))
        X[:, :n_num][rng.random((page_rows, n_num)) < 0.2] = np.nan
        # skewed categorical codes, Criteo-style head-heavy vocabulary
        X[:, n_num:] = np.minimum(
            rng.geometric(0.08, size=(page_rows, n_cat)) - 1, n_cats - 1)
        lin = (np.nan_to_num(X[:, 0]) * 1.2 - np.nan_to_num(X[:, 1])
               + 0.5 * np.nan_to_num(X[:, 2]) * np.nan_to_num(X[:, 3])
               + 0.3 * (X[:, n_num] == 0))
        y = (lin + rng.normal(scale=0.5, size=page_rows) > 0
             ).astype(np.float32)
        return X, y

    ftypes = ["q"] * n_num + ["c"] * n_cat

    class Pages(xtb.DataIter):
        def __init__(self):
            super().__init__()
            self._i = 0

        def reset(self):
            self._i = 0

        def next(self, input_data):
            if self._i >= n_pages:
                return 0
            X, y = page(self._i)
            input_data(data=X, label=y, feature_types=ftypes)
            self._i += 1
            return 1

    rows = n_pages * page_rows
    resident_mb = rows * cols * 4 / 2**20
    t0 = time.perf_counter()
    d = xtb.ExtMemQuantileDMatrix(Pages(), max_bin=max_bin,
                                  enable_categorical=True)
    ingest_wall = time.perf_counter() - t0
    gc.collect()
    paged_mb = sum(getattr(p, "nbytes_compressed", p.nbytes)
                   for p in d._pages) / 2**20
    params = {"objective": "binary:logistic", "max_depth": 8, "eta": 0.3,
              "max_bin": max_bin}
    before = _extmem_counters()
    t0 = time.perf_counter()
    bst = xtb.train(params, d, rounds, verbose_eval=False)
    preds = np.asarray(bst.predict(d))
    train_wall = time.perf_counter() - t0
    gc.collect()
    # AUC on a deterministic 1/8 stride sample: the metric's f64 buffers
    # over all 40M+ rows would add ~700 MB to the very peak this row
    # exists to bound
    q = eval_quality("auc", preds[::8],
                     np.asarray(d.info.label[::8], np.float64), None)
    peak = _peak_rss_mb()
    return dict(
        config="criteo_extmem_40m", rows=rows, cols=cols, pages=n_pages,
        page_rows=page_rows, categorical_cols=n_cat, scale=1.0,
        rounds=rounds, objective="binary:logistic",
        metric="auc@stride8", max_bin=max_bin,
        platform="cpu", cores=os.cpu_count(),
        host_cache_mb=float(os.environ["XTB_EXTMEM_HOST_CACHE_MB"]),
        ingest_wall_s=round(ingest_wall, 2),
        ours_wall_s=round(train_wall, 2), ours_quality=round(q, 6),
        peak_rss_mb=round(peak, 1), resident_matrix_mb=round(resident_mb, 1),
        paged_store_mb=round(paged_mb, 1),
        rss_bounded=bool(peak < resident_mb),
        extmem=_counter_delta(before),
        note=("pages synthesized on the fly (never materialized "
              "together); peak RSS covers interpreter + jax runtime + "
              "binned u8 pages + the 512 MB page-cache budget + per-row "
              "training state, and must stay below the f32 "
              "resident-matrix size the paged path avoids (an in-memory "
              "run would hold that matrix AND its binned pages); "
              "max_bin=128 keeps page codes u8; zstd absent in this "
              "container, so pages are uncompressed (paged_store_mb "
              "would shrink further with zstandard installed)"),
    )


def extmem_main(out_path: str) -> None:
    """Run the out-of-core rows, each in a fresh subprocess (clean RSS),
    merging into the existing ladder file by config name."""
    import subprocess
    import tempfile

    only = [t for t in os.environ.get("LADDER_EXTMEM_ONLY", "").split(",")
            if t.strip()]
    rows = []
    if os.path.exists(out_path):
        with open(out_path) as fh:
            rows = json.load(fh)
    for name in EXTMEM_ROW_NAMES:
        if only and name not in only:
            continue
        with tempfile.NamedTemporaryFile(suffix=".json") as tmp:
            print(f"[extmem ladder] {name} ...", flush=True)
            t0 = time.perf_counter()
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--row", name,
                 tmp.name],
                check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
            with open(tmp.name) as fh:
                row = json.load(fh)
        print(f"[extmem ladder] {name} done in "
              f"{time.perf_counter() - t0:.0f}s", flush=True)
        rows = [r for r in rows if r.get("config") != name] + [row]
        with open(out_path, "w") as fh:  # checkpoint after each row
            json.dump(rows, fh, indent=1)


def _row_main(name: str, out_path: str) -> None:
    import jax

    want = os.environ.get("JAX_PLATFORMS")
    if want:
        jax.config.update("jax_platforms", want)
    fn = {"extmem_scaling": bench_row_extmem_scaling,
          "higgs_full": bench_row_higgs_full,
          "criteo_extmem_40m": bench_row_criteo_extmem}[name]
    row = fn()
    row["host"] = _host_fingerprint()
    with open(out_path, "w") as fh:
        json.dump(row, fh, indent=1)
    print(json.dumps(row, indent=1), flush=True)


if __name__ == "__main__":
    if "--row" in sys.argv:
        i = sys.argv.index("--row")
        _row_main(sys.argv[i + 1], sys.argv[i + 2])
    elif "--diff" in sys.argv:
        i = sys.argv.index("--diff")
        sys.exit(diff_main(sys.argv[i + 1], sys.argv[i + 2]))
    elif "--extmem" in sys.argv:
        args = [a for a in sys.argv[1:] if not a.startswith("--")]
        extmem_main(args[0] if args else "BENCH_LADDER.json")
    else:
        main()
