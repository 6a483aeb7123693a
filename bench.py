"""Benchmark: synthetic HIGGS-shaped binary training on the TPU chip.

Needs the chip: with no TPU it fails at the device line and prints no metric
(rehearse on the CPU with ``chip_smoke.py --allow-cpu``, which prints none
either).  Any phase that fails ends the run with a non-zero exit code.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Workload mirrors BASELINE.md config #2 scaled to one chip + bench budget:
HIGGS-like dense f32 (28 features), binary:logistic, hist with max_bin=256,
depth 6.  Metric of record is training throughput in M row·rounds/s (train
loop only — DMatrix/sketch/bin time reported separately to stderr, matching
how gpu_hist timings are usually quoted).

vs_baseline compares against an H100 xgboost `gpu_hist` estimate for the same
workload: public gpu_hist results put HIGGS-class training at roughly
100-130 M row·rounds/s on top-end NVIDIA parts (BASELINE.md: the reference
repo itself publishes no absolute numbers); we use 110 M row·rounds/s.
vs_baseline > 1.0 means faster than that estimate.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

H100_BASELINE_ROW_ROUNDS_PER_S = 110e6

N_ROWS = int(os.environ.get("BENCH_ROWS", 2_000_000))  # the shape of record
N_FEATURES = int(os.environ.get("BENCH_FEATURES", 28))
N_ROUNDS = int(os.environ.get("BENCH_ROUNDS", 40))
MAX_DEPTH = int(os.environ.get("BENCH_DEPTH", 6))
MAX_BIN = int(os.environ.get("BENCH_MAX_BIN", 256))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_data(n: int, f: int, seed: int = 0):
    """HIGGS-like: informative low-order interactions + noise features."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    logits = (
        1.5 * X[:, 0]
        + X[:, 1] * X[:, 2]
        - 0.8 * np.abs(X[:, 3])
        + 0.5 * X[:, 4]
        + 0.3 * rng.normal(size=n)
    )
    y = (logits > 0).astype(np.float32)
    return X, y


def _median_time(fn, reps: int = 5) -> float:
    """Median wall seconds of fn() with device completion; one warmup call
    first so compile time never lands in the samples.  (Shared: the
    scripts/ benches import this.)"""
    import jax

    jax.block_until_ready(fn())  # compile/warmup
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def _hist_flops_per_round(R: int, F: int, B: int, depth: int) -> float:
    """MXU FLOPs of one boosting round's histogram matmuls: each level's
    build is (F*B, R) @ (R, N*2) = 2*R*F*B*N*2 FLOPs; with the subtraction
    trick levels d>0 build only the 2^(d-1) left children."""
    total = 0.0
    for d in range(depth):
        n_build = 1 if d == 0 else 2 ** (d - 1)
        total += 2.0 * R * F * B * n_build * 2
    return total


def phase_bench() -> dict:
    """Standalone per-phase timings at bench shapes: histogram (XLA and the
    fused Pallas kernel, compiled: interpret=False, so a Mosaic refusal
    ends the run), split scan, position rewrite, H2D."""
    import jax
    import jax.numpy as jnp

    from xgboost_tpu.ops.histogram import build_histogram
    from xgboost_tpu.ops.split import SplitParams, evaluate_splits

    R = min(N_ROWS, 1 << 21)
    F, B, depth = N_FEATURES, MAX_BIN, MAX_DEPTH
    N = 2 ** (depth - 1)  # widest built level (subtraction trick)
    rng = np.random.default_rng(0)
    bins_np = rng.integers(0, B, size=(R, F)).astype(np.uint8)
    gp_np = rng.normal(size=(R, 2)).astype(np.float32)
    pos_np = rng.integers((1 << (depth - 1)) - 1, (1 << depth) - 1,
                          size=R).astype(np.int32)
    phases = {}

    t0 = time.perf_counter()
    bins = jax.block_until_ready(jax.device_put(bins_np))
    phases["h2d_bins_s"] = time.perf_counter() - t0
    gp = jax.device_put(gp_np)
    pos = jax.device_put(pos_np)
    root_pos = jnp.zeros(R, jnp.int32)

    phases["hist_root_xla_s"] = _median_time(lambda: build_histogram(
        bins, gp, root_pos, node0=0, n_nodes=1, n_bin=B))
    # the widest level the train loop actually builds: with the subtraction
    # trick only the 2^(depth-2) LEFT children (stride 2) are computed
    n_build = max(N // 2, 1)
    node0 = (1 << (depth - 1)) - 1
    phases["hist_level_xla_s"] = _median_time(lambda: build_histogram(
        bins, gp, pos, node0=node0, n_nodes=n_build, n_bin=B, stride=2))

    from xgboost_tpu.ops.hist_pallas import build_histogram_pallas

    phases["hist_level_pallas_s"] = _median_time(
        lambda: build_histogram_pallas(
            bins, gp, pos, node0=node0, n_nodes=n_build, n_bin=B,
            interpret=False, stride=2))

    hist = build_histogram(bins, gp, pos, node0=node0, n_nodes=N, n_bin=B)
    totals = hist.sum(axis=(1,)).sum(axis=1) / F  # (N, 2) approximation
    params = SplitParams(eta=0.1, gamma=0.0, min_child_weight=1.0,
                         lambda_=1.0, alpha=0.0, max_delta_step=0.0)
    nb = jnp.full(F, B, jnp.int32)
    phases["split_eval_s"] = _median_time(
        lambda: evaluate_splits(hist, totals, nb, params))

    # position rewrite (RowPartitioner role): per-row gather of the split
    # feature's bin + elementwise route
    feat = jnp.zeros(2 * N, jnp.int32)
    sbin = jnp.full(2 * N, B // 2, jnp.int32)

    @jax.jit
    def _route(pos, bins):
        f = feat[jnp.clip(pos, 0, 2 * N - 1)]
        bv = jnp.take_along_axis(bins, f[:, None], axis=1)[:, 0].astype(jnp.int32)
        return jnp.where(bv <= sbin[jnp.clip(pos, 0, 2 * N - 1)],
                         2 * pos + 1, 2 * pos + 2)

    phases["pos_rewrite_s"] = _median_time(lambda: _route(pos, bins))

    phases["hist_flops_per_round"] = _hist_flops_per_round(N_ROWS, F, B, depth)
    # achieved rate of the standalone level build, from its shapes
    phases["hist_level_tflops"] = (
        2.0 * R * F * B * n_build * 2 / phases["hist_level_xla_s"] / 1e12)
    return phases


def bench_extmem() -> dict:
    """Extmem streaming at non-toy page counts (VERDICT r3 #9): >= 20 zstd
    pages through the (mesh-shardable) streaming grower, prefetch overlap
    measured as the wall-clock gain of overlapped host decompress/H2D vs
    the serialized baseline (reference knob: n_prefetch_batches,
    sparse_page_source.h:293)."""
    import xgboost_tpu as xtb
    from xgboost_tpu.data.extmem import DataIter, ExtMemQuantileDMatrix

    rows_page = int(os.environ.get("BENCH_EXTMEM_PAGE_ROWS", "12800"))
    n_pages = int(os.environ.get("BENCH_EXTMEM_PAGES", "24"))
    F = N_FEATURES
    rng = np.random.default_rng(5)
    w = rng.normal(size=F).astype(np.float32)

    class Pages(DataIter):
        def __init__(self):
            super().__init__()
            self._i = 0

        def next(self, input_data):
            if self._i >= n_pages:
                return 0
            r = np.random.default_rng(100 + self._i)
            X = r.normal(size=(rows_page, F)).astype(np.float32)
            y = (X @ w + r.normal(scale=0.5, size=rows_page) > 0
                 ).astype(np.float32)
            input_data(data=X, label=y)
            self._i += 1
            return 1

        def reset(self):
            self._i = 0

    d = ExtMemQuantileDMatrix(Pages(), max_bin=MAX_BIN)
    out = {"pages": len(d._pages), "rows": rows_page * n_pages,
           "compressed_mb": round(sum(
               p.nbytes_compressed if hasattr(p, "nbytes_compressed")
               else p.nbytes for p in d._pages) / 2**20, 2)}
    base = {"objective": "binary:logistic", "max_depth": 6,
            "max_bin": MAX_BIN, "eta": 0.3}

    def one_round(prefetch: bool) -> float:
        p = {**base, "_extmem_prefetch": "1" if prefetch else "0"}
        xtb.train(p, d, 1, verbose_eval=False)  # warm the jit cache
        t0 = time.perf_counter()
        xtb.train(p, d, 1, verbose_eval=False)
        return time.perf_counter() - t0

    out["round_prefetch_s"] = round(one_round(True), 3)
    out["round_serial_s"] = round(one_round(False), 3)
    out["prefetch_overlap_gain"] = round(
        1.0 - out["round_prefetch_s"] / max(out["round_serial_s"], 1e-9), 4)
    return out


def main() -> None:
    import jax

    import xgboost_tpu as xtb
    from xgboost_tpu.serving.warmcache import configure_persistent_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU chip and JAX found {jax.devices()}; "
            f"no chip, no number")
    cache_dir = configure_persistent_cache()
    log(f"device: {dev} kind={dev.device_kind} compile_cache={cache_dir}")
    # drop any stale phases file so a later copy can't publish old numbers
    # under a fresh run's name
    _phases_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "bench_phases.json")
    if os.path.exists(_phases_path):
        os.remove(_phases_path)

    X, y = make_data(N_ROWS, N_FEATURES)
    t0 = time.perf_counter()
    dtrain = xtb.QuantileDMatrix(X, label=y, max_bin=MAX_BIN)
    t_data = time.perf_counter() - t0
    log(f"QuantileDMatrix build: {t_data:.2f}s ({N_ROWS} rows x {N_FEATURES} cols)")

    params = {
        "objective": "binary:logistic",
        "max_depth": MAX_DEPTH,
        "max_bin": MAX_BIN,
        "eta": 0.1,
        "device": "tpu",
    }

    # warmup: compile all level steps (cached across rounds, and across
    # runs by the persistent compilation cache)
    t0 = time.perf_counter()
    bst = xtb.train(params, dtrain, num_boost_round=2, verbose_eval=False)
    warmup_s = time.perf_counter() - t0
    log(f"warmup (2 rounds + compile): {warmup_s:.2f}s")

    t0 = time.perf_counter()
    bst = xtb.train(params, dtrain, num_boost_round=N_ROUNDS, verbose_eval=False,
                    xgb_model=bst)
    train_s = time.perf_counter() - t0

    # sanity: the model must actually learn
    idx = np.random.default_rng(1).choice(N_ROWS, size=min(200_000, N_ROWS), replace=False)
    from xgboost_tpu.metric import auc as _auc

    preds = bst.predict(xtb.DMatrix(X[idx]))
    auc_v = _auc(preds, y[idx])
    log(f"train: {train_s:.2f}s for {N_ROUNDS} rounds; sample AUC={auc_v:.4f}")
    assert auc_v > 0.75, f"model failed to learn (AUC={auc_v})"

    if os.environ.get("BENCH_PHASES", "1") != "0":
        phases = phase_bench()
        phases["warmup_compile_s"] = warmup_s
        # compile wall estimate: warmup minus its 2 steady-state rounds
        phases["compile_est_s"] = max(
            0.0, warmup_s - 2.0 * train_s / N_ROUNDS)
        phases["extmem"] = bench_extmem()
        log("per-phase timings: " + json.dumps(
            {k: (round(v, 6) if isinstance(v, float) else v)
             for k, v in phases.items()}))
        with open(_phases_path, "w") as fh:
            json.dump({"rows": N_ROWS, "features": N_FEATURES,
                       "max_bin": MAX_BIN, "depth": MAX_DEPTH, **phases},
                      fh, indent=1)

    throughput = N_ROWS * N_ROUNDS / train_s
    size = (f"{N_ROWS // 10**6}M" if N_ROWS >= 10**6 else f"{N_ROWS // 1000}k")
    from xgboost_tpu.utils import native as _native

    result = {
        "metric": f"synthetic-HIGGS {size}x{N_FEATURES} "
                  f"binary:logistic depth{MAX_DEPTH} train throughput",
        "value": round(throughput / 1e6, 3),
        "unit": "Mrow_rounds/s",
        "vs_baseline": round(throughput / H100_BASELINE_ROW_ROUNDS_PER_S, 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "warmup_s": round(warmup_s, 2),
        "auc": round(float(auc_v), 4),
        # host-parallelism provenance (docs/native_threading.md): the native
        # kernel pool width this run used, and the cores it had to use
        "nthread": _native.get_nthread(),
        "cores": os.cpu_count(),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
