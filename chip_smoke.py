"""Chip smoke: train -> predict -> serve on one TPU chip, through the entry
points a user calls, at the full width of the HIGGS-shaped configuration
(2,000,000 x 28 float32, binary:logistic, max_bin=256, max_depth=6).

One process, one phase after another, one line per phase with its seconds.
The first phase that fails ends the run with a traceback and a non-zero exit
code: nothing is caught, nothing falls back.  The last line of a run that
got to its end is one JSON object,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and a run that found no TPU prints no such line at all.

    python chip_smoke.py                 # one chip; what the driver runs
    python chip_smoke.py --chips 4       # only the sharded path and the
                                         # one-chip run it is compared with
    python chip_smoke.py --rows 20000 --allow-cpu     # rehearsal, no chip:
                                         # runs on the CPU, says so in the
                                         # device line, ends with "ok": false

The four-chip rehearsal wants four virtual devices from its caller:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

Nothing this prints is a benchmark: the timings are here so that a slow
phase shows, and so that compile time is seen apart from steady time.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

N_FEATURES = 28
MAX_BIN = 256
MAX_DEPTH = 6
# a float32 sum of n terms is off by at most n * 2**-24 of the sum of their
# magnitudes; one bfloat16 pass rounds every term to 2**-9 of itself, which a
# bin that holds a single row shows undiluted
F32_LEVEL = 1e-4
AUC_FLOOR = 0.75  # the model must actually learn
# float32 sums of leaf values in another order, and the chip's own exp: the
# first run on a v5e put predict 1.1e-06 from numpy's sigmoid of the same walk
PRED_TOL = 1e-5
# two float32 summation orders (one device; four and a psum) agree to 1e-6 of
# the magnitudes that went into a sum, not of the sum: a leaf weight is
# -G/(H+lambda) and G cancels, so predictions are held to this and the tree
# structure to equality
SHARD_TOL = 1e-3
# ... and where two candidate splits' gains are closer than float32 sums can
# tell (a gain is a difference of squares of sums: an empty bin between two
# cuts, an exact tie, read 4e-05 apart on the CPU), the two orders may choose
# differently; from that node on the models see different rows.  That is
# what deterministic_histogram=True is for.
TIE_TOL = 1e-3


class Clock:
    """Prints one line per phase: name, seconds, what the phase found."""

    def __init__(self) -> None:
        self.t0 = time.perf_counter()

    def line(self, phase: str, text: str) -> None:
        now = time.perf_counter()
        print(f"[{phase}] {now - self.t0:.2f}s  {text}", flush=True)
        self.t0 = now


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def phase_device(args, clock: Clock):
    import jax

    devs = jax.devices()
    d = devs[0]
    need = args.chips
    if d.platform != "tpu":
        check(args.allow_cpu,
              f"JAX found no TPU: its devices are {devs}.  (--allow-cpu "
              f"rehearses on the CPU and ends with \"ok\": false.)")
    check(len(devs) >= need,
          f"--chips {need} but JAX found {len(devs)}: {devs}")
    clock.line("device", f"platform={d.platform} kind={d.device_kind!r} "
               f"count={len(devs)} jax={jax.__version__}"
               + ("" if d.platform == "tpu"
                  else "  ** REHEARSAL ON THE CPU (--allow-cpu): no chip "
                       "was used, nothing below is a device number **"))
    return d


def phase_native(clock: Clock) -> None:
    """Which host libraries were built from native/ and loaded, and which
    pure-Python paths stand in.  (They serve the host side: parsing,
    sketching, the wire; the FFI kernels are CPU custom calls.)"""
    from xgboost_tpu.utils import native

    ndir = native._native_dir()
    found = []
    for so, load, stand_in in (
            ("libxtb_native.so", native.load_native,
             "numpy parsers/sketch/SHAP"),
            ("libxtb_ffi.so", native.load_ffi,
             "XLA scatter hist, cumsum split, scan predictor"),
            ("libxtb_wire.so", native.load_wire, "Python frame reader")):
        was_there = os.path.exists(os.path.join(ndir, so))
        loaded = bool(load())
        if loaded:
            found.append(f"{so}: loaded "
                         f"({'already built' if was_there else 'built now from native/'})")
        else:
            found.append(f"{so}: NOT loaded -> {stand_in}")
    clock.line("native", "; ".join(found))


def make_data(rows: int, seed: int):
    from benchmarks.data import higgs_like  # the HIGGS cells' maker

    return higgs_like(rows, N_FEATURES, seed)


def train_params(device) -> dict:
    return {"objective": "binary:logistic", "max_depth": MAX_DEPTH,
            "max_bin": MAX_BIN, "eta": 0.1, "device": device.platform}


def sample_rows(X, seed: int) -> np.ndarray:
    """Indices of a seeded 200k-row sample (all rows of a smaller X)."""
    return np.random.default_rng(seed).choice(
        len(X), size=min(200_000, len(X)), replace=False)


def sample_auc(bst, X, y) -> float:
    """Sanity check: AUC on a 200k-row sample."""
    import xgboost_tpu as xtb
    from xgboost_tpu.metric import auc

    idx = sample_rows(X, 1)
    return float(auc(bst.predict(xtb.DMatrix(X[idx])), y[idx]))


def where(arr) -> str:
    return ",".join(sorted(str(d) for d in arr.devices()))


def phase_train(device, X, y, clock: Clock):
    import jax

    import xgboost_tpu as xtb
    from xgboost_tpu.telemetry.compile import compiles_total

    t0 = time.perf_counter()
    dtrain = xtb.QuantileDMatrix(X, label=y, max_bin=MAX_BIN)
    dmat_s = time.perf_counter() - t0
    params = train_params(device)
    c0 = compiles_total()
    t0 = time.perf_counter()
    bst = xtb.train(params, dtrain, 2, verbose_eval=False)
    cache = bst._get_cache(dtrain)
    jax.block_until_ready((cache.bins, cache.margin))
    first_s = time.perf_counter() - t0
    c1 = compiles_total()
    t0 = time.perf_counter()
    bst = xtb.train(params, dtrain, 5, verbose_eval=False, xgb_model=bst)
    cache = bst._get_cache(dtrain)
    jax.block_until_ready(cache.margin)
    steady_s = time.perf_counter() - t0
    c2 = compiles_total()
    auc = sample_auc(bst, X, y)
    check(len(bst.trees) == 7, f"7 rounds gave {len(bst.trees)} trees")
    check(auc > AUC_FLOOR, f"sample AUC {auc:.4f} <= {AUC_FLOOR}")
    clock.line("train", f"{len(X)}x{N_FEATURES} max_bin={MAX_BIN} "
               f"depth={MAX_DEPTH}: QuantileDMatrix {dmat_s:.2f}s; first 2 "
               f"rounds with binning and compilation {first_s:.2f}s "
               f"({c1 - c0} compiles); 5 more rounds {steady_s:.2f}s "
               f"({c2 - c1} compiles), so about "
               f"{max(first_s - 0.4 * steady_s, 0.0):.2f}s of the first was "
               f"not rounds; sample AUC {auc:.4f}; "
               f"bins {cache.bins.dtype}{tuple(cache.bins.shape)} on "
               f"[{where(cache.bins)}], margin on [{where(cache.margin)}]")
    return bst, dtrain


def host_hist(bins, gpair, pos, node0: int, n_nodes: int, stride: int):
    """Exact (float64) host histogram and, beside it, the sum of magnitudes
    each bin's error is measured against: both (n_nodes, F, B, 2)."""
    F = bins.shape[1]
    local = pos - node0
    ok = (local >= 0) & (local % stride == 0) & (local // stride < n_nodes)
    node = np.where(ok, local // stride, 0)
    hist = np.zeros((n_nodes, F, MAX_BIN, 2))
    mags = np.zeros_like(hist)
    g64 = gpair.astype(np.float64)
    for f in range(F):
        keep = ok & (bins[:, f] < MAX_BIN)  # the sentinel is a missing value
        flat = node[keep] * MAX_BIN + bins[keep, f]
        for c in range(2):
            v = g64[keep, c]
            hist[:, f, :, c] = np.bincount(
                flat, weights=v, minlength=n_nodes * MAX_BIN
            ).reshape(n_nodes, MAX_BIN)
            mags[:, f, :, c] = np.bincount(
                flat, weights=np.abs(v), minlength=n_nodes * MAX_BIN
            ).reshape(n_nodes, MAX_BIN)
    return hist, mags


def rel_err(got, want, mags) -> float:
    """Largest |device - exact| over the bins, each relative to the sum of
    magnitudes that went into the bin."""
    filled = mags > 0
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)[filled]
                        / mags[filled]))


# the two histograms a depth-6 tree's levels ask for: the root, and the
# widest level built (depth 5: 32 nodes from heap id 31, of which the 16 left
# children are built and the right ones derived by subtraction)
LEVELS = (("root", 0, 1, 1), ("depth-5 level", 31, 16, 2))


def level_inputs(seed: int, bst, dtrain):
    """Device and host copies of what a level's histogram is built from:
    the binned matrix of the run, the gradient pairs of the trained model's
    own margin, and seeded node positions for the depth-5 level."""
    import jax.numpy as jnp

    cache = bst._get_cache(dtrain)
    R = len(np.asarray(dtrain.get_label()))
    p = 1.0 / (1.0 + np.exp(-np.asarray(cache.margin)[:, 0].astype(np.float64)))
    lab = np.zeros(cache.n_padded)
    lab[:R] = dtrain.get_label()
    valid = np.arange(cache.n_padded) < R
    gpair = np.where(valid[:, None],
                     np.stack([p - lab, p * (1.0 - p)], axis=1),
                     0.0).astype(np.float32)
    rng = np.random.default_rng(seed + 1)
    deep = np.where(valid, rng.integers(31, 63, cache.n_padded), -1)
    pos = {"root": np.where(valid, 0, -1).astype(np.int32),
           "depth-5 level": deep.astype(np.int32)}
    host = dict(bins=np.asarray(cache.bins), gpair=gpair, pos=pos)
    dev = dict(bins=cache.bins, gpair=jnp.asarray(gpair),
               pos={k: jnp.asarray(v) for k, v in pos.items()},
               valid=jnp.asarray(valid))
    return host, dev


def phase_hist_truth(host, dev, clock: Clock) -> None:
    """Is the float32 histogram float32 on this device?  The path the
    grower takes by default against an exact host sum, on all the rows."""
    from xgboost_tpu.ops.histogram import build_histogram

    found = []
    for name, node0, n_nodes, stride in LEVELS:
        got = build_histogram(dev["bins"], dev["gpair"], dev["pos"][name],
                              node0=node0, n_nodes=n_nodes, n_bin=MAX_BIN,
                              stride=stride)
        want, mags = host_hist(host["bins"], host["gpair"], host["pos"][name],
                               node0, n_nodes, stride)
        err = rel_err(got, want, mags)
        host[name] = (want, mags)
        found.append(f"{name} {err:.3e}")
        check(err < F32_LEVEL,
              f"histogram of the {name} is off by {err:.3e} of its bin's "
              f"magnitudes: the bfloat16 level is 2**-9 = 1.95e-03, the "
              f"float32 level is under {F32_LEVEL:g}")
    clock.line("histogram truth",
               "largest relative error vs an exact float64 host sum: "
               + ", ".join(found)
               + f" (float32 level: under {F32_LEVEL:g}; one bfloat16 pass: "
                 f"2**-9 = 1.95e-03)")


def numpy_walk(model: dict, X: np.ndarray) -> np.ndarray:
    """Plain numpy margin from the saved JSON model: every row walks every
    tree (``value < split_condition`` goes left, NaN takes the default
    side) and leaf values are summed in float32 in tree order."""
    learner = model["learner"]
    base = np.float32(learner["learner_model_param"]["base_score"])
    margin = np.full(len(X), np.log(base / (np.float32(1) - base)), np.float32)
    rows = np.arange(len(X))
    for tree in learner["gradient_booster"]["model"]["trees"]:
        left = np.asarray(tree["left_children"], np.int32)
        right = np.asarray(tree["right_children"], np.int32)
        feat = np.asarray(tree["split_indices"], np.int32)
        cond = np.asarray(tree["split_conditions"], np.float32)
        dleft = np.asarray(tree["default_left"], bool)
        node = np.zeros(len(X), np.int32)
        while True:
            inner = left[node] != -1
            if not inner.any():
                break
            v = X[rows, feat[node]]
            go_left = np.where(np.isnan(v), dleft[node], v < cond[node])
            node = np.where(inner, np.where(go_left, left[node], right[node]),
                            node)
        margin = margin + cond[node]
    return margin


def sigmoid(margin: np.ndarray) -> np.ndarray:
    return (np.float32(1) / (np.float32(1) + np.exp(-margin))).astype(np.float32)


def phase_predict(bst, X, clock: Clock):
    import xgboost_tpu as xtb

    Xs = X[sample_rows(X, 2)]
    ds = xtb.DMatrix(Xs)
    pred = bst.predict(ds)
    margin = bst.predict(ds, output_margin=True)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "smoke.json")
        bst.save_model(path)
        with open(path) as fh:
            model = json.load(fh)
        again = xtb.Booster()
        again.load_model(path)
    walk = numpy_walk(model, Xs)
    margin_diff = float(np.max(np.abs(margin - walk)))
    walk_diff = float(np.max(np.abs(pred - sigmoid(walk))))
    trip_diff = float(np.max(np.abs(pred - again.predict(ds))))
    check(pred.shape == (len(Xs),) and bool(np.isfinite(pred).all()),
          "predictions are not finite values of the expected shape")
    check(margin_diff <= PRED_TOL and walk_diff <= PRED_TOL,
          f"predict differs from the numpy walk of the saved model by "
          f"{margin_diff:.3e} in margin and {walk_diff:.3e} in probability")
    check(trip_diff == 0.0,
          f"save_model/load_model round trip changed predictions by {trip_diff}")
    clock.line("predict", f"{len(Xs)} rows: max |predict - numpy walk of the "
               f"saved JSON| = {margin_diff:.3e} in margin, {walk_diff:.3e} "
               f"in probability; save/load round trip diff = {trip_diff}")
    return Xs, pred


def phase_serve(bst, Xs, pred, clock: Clock) -> None:
    from xgboost_tpu.serving import ServingEngine

    sizes = (1, 64, 4096)
    worst = 0.0
    with ServingEngine(max_batch=4096, warmup_buckets=sizes) as eng:
        eng.add_model("smoke", bst)  # compiles the three buckets
        n = 0
        for size in sizes:
            for k in range(3):
                lo = (k * size) % (len(Xs) - size + 1)
                # direct and through the micro-batcher, turn about
                out = eng.predict("smoke", Xs[lo:lo + size], direct=k % 2 == 0)
                worst = max(worst, float(np.max(np.abs(
                    out - pred[lo:lo + size]))))
                n += 1
        steady = eng.metrics_snapshot()["compiles_steady"]
    check(worst <= 1e-6,
          f"serve answers differ from bst.predict by {worst:.3e}")
    check(steady == 0, f"the engine compiled {steady} programs after warm-up")
    clock.line("serve", f"{n} requests of {sizes} rows: max |engine - "
               f"bst.predict| = {worst:.3e}; steady-state compiles = {steady}")


def phase_fused_kernel(device, host, dev, clock: Clock) -> None:
    """Both fused Pallas kernels at the shapes the grower hands them at this
    width, compiled and not interpreted on the chip, against the XLA path
    (float32 form) and bit for bit (int8-limb form)."""
    from xgboost_tpu.ops.hist_pallas import (build_histogram_pallas,
                                             build_histogram_pallas_q)
    from xgboost_tpu.ops.quantise import (hist_accumulate_q, local_rho,
                                          quantise_gpair)

    on_chip = device.platform == "tpu"

    def run(kernel, name, *arrays, **kw):
        """On the chip: compile ahead, see the Mosaic kernel in the compiled
        text, and run that very executable.  Elsewhere: interpret."""
        if not on_chip:
            return kernel(*arrays, **kw)
        compiled = kernel.lower(*arrays, interpret=False, **kw).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{kernel.__name__} ({name}) compiled to no tpu_custom_call: "
              f"it is not the Mosaic kernel")
        return compiled(*arrays)

    gq = quantise_gpair(dev["gpair"], local_rho(dev["gpair"], dev["valid"]))
    found = []
    for name, node0, n_nodes, stride in LEVELS:
        kw = dict(node0=node0, n_nodes=n_nodes, n_bin=MAX_BIN, stride=stride)
        pos = dev["pos"][name]
        got = run(build_histogram_pallas, name, dev["bins"], dev["gpair"],
                  pos, **kw)
        want, mags = host[name]
        err = rel_err(got, want, mags)
        check(err < F32_LEVEL, f"fused float32 kernel ({name}) is off by "
              f"{err:.3e} of its bin's magnitudes")
        got_q = run(build_histogram_pallas_q, name, dev["bins"], gq, pos, **kw)
        want_q = hist_accumulate_q(dev["bins"], gq, pos, node0, n_nodes,
                                   MAX_BIN, stride=stride)
        same = bool(np.array_equal(np.asarray(got_q), np.asarray(want_q)))
        check(same, f"fused int8-limb kernel ({name}) differs from "
              f"hist_accumulate_q")
        found.append(f"{name}: float32 err {err:.3e}, int8 limbs "
                     f"bitwise equal to the XLA path")
    clock.line("fused kernel",
               ("compiled (tpu_custom_call in the compiled text)" if on_chip
                else "INTERPRETED (no chip)")
               + f", bins {dev['bins'].dtype}; " + "; ".join(found))


class CacheHits:
    """Counts jax's own persistent-cache events.  (``compiles_total()``
    cannot tell a warm cache from a cold one: in jax 0.9.0 the backend
    compile event fires on a persistent-cache hit too.)"""

    def __init__(self) -> None:
        import jax.monitoring

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def phase_cache(device, dtrain, cache_dir: str, seen: CacheHits,
                clock: Clock) -> None:
    import xgboost_tpu as xtb
    from xgboost_tpu.telemetry.compile import compile_delta

    with compile_delta() as again:
        xtb.train(train_params(device), dtrain, 2, verbose_eval=False)
    check(again.count == 0,
          f"a second identical xtb.train compiled {again.count} programs")
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    clock.line("cache", f"compile cache at {cache_dir} "
               f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'the fixed default in the checkout'}), "
               f"{entries} entries; of this process's {seen.requests} "
               f"compile requests {seen.hits} were found in it (0 = cold); "
               f"a second identical xtb.train compiled {again.count} "
               f"programs")


# every number a tree holds: equal here means the two models are bitwise one
EVERY_FIELD = ("left_children", "right_children", "split_indices",
               "split_conditions", "default_left", "base_weights",
               "loss_changes", "sum_hessian")


def same_trees(a, b) -> bool:
    return len(a.trees) == len(b.trees) and all(
        np.array_equal(getattr(ta, f), getattr(tb, f))
        for ta, tb in zip(a.trees, b.trees) for f in EVERY_FIELD)


def first_divergence(a, b):
    """Walk two models tree by tree and node by node, breadth first, to the
    first node where they split differently.  None if they never do; else
    (tree, depth, text, relative gap of the two best gains).  Past that node
    the models see different rows and gradients and are not comparable."""
    for t, (ta, tb) in enumerate(zip(a.trees, b.trees)):
        queue = [(0, 0, 0)]
        while queue:
            na, nb, depth = queue.pop(0)
            leaf_a, leaf_b = ta.left_children[na] == -1, tb.left_children[nb] == -1
            if leaf_a and leaf_b:
                continue
            split_a = (leaf_a, ta.split_indices[na], ta.split_conditions[na])
            split_b = (leaf_b, tb.split_indices[nb], tb.split_conditions[nb])
            if split_a != split_b:
                ga, gb = float(ta.loss_changes[na]), float(tb.loss_changes[nb])
                gap = abs(ga - gb) / max(abs(ga), abs(gb), 1e-30)
                return (t, depth,
                        f"tree {t} depth {depth}: feature {split_a[1]} < "
                        f"{split_a[2]:.6g} (gain {ga:.6g}) against feature "
                        f"{split_b[1]} < {split_b[2]:.6g} (gain {gb:.6g})",
                        gap)
            queue.append((ta.left_children[na], tb.left_children[nb], depth + 1))
            queue.append((ta.right_children[na], tb.right_children[nb], depth + 1))
    return None


def phase_sharded(n: int, device, X, y, clock: Clock) -> None:
    """The ``n_devices`` path on four devices against one device: five
    rounds each way, with deterministic_histogram=True (integer sums: the
    two must be bitwise one) and in float32 (two summation orders: equal
    until the first split that float32 cannot tell apart)."""
    import jax

    import xgboost_tpu as xtb

    ds = xtb.DMatrix(X[sample_rows(X, 2)])
    dtrain = xtb.QuantileDMatrix(X, label=y, max_bin=MAX_BIN)
    runs = {}
    for exact in (False, True):
        for nd in (n, 1):
            params = {**train_params(device), "n_devices": nd,
                      "deterministic_histogram": exact}
            t0 = time.perf_counter()
            bst = xtb.train(params, dtrain, 5, verbose_eval=False)
            cache = bst._get_cache(dtrain)
            jax.block_until_ready(cache.margin)
            runs[exact, nd] = (bst, bst.predict(ds),
                               time.perf_counter() - t0)
            if nd == n and not exact:
                shards = cache.bins.addressable_shards
                rows = sorted((str(s.device), s.data.shape[0]) for s in shards)
                check(len({d for d, _ in rows}) == n
                      and all(r == cache.n_padded // n for _, r in rows),
                      f"the binned matrix does not lie a quarter on each "
                      f"device: {rows} of {cache.n_padded} rows")
                clock.line("sharded layout", f"bins "
                           f"{tuple(cache.bins.shape)}: "
                           + ", ".join(f"{r} rows on {d}" for d, r in rows))

    (qn, pqn, tqn), (q1, pq1, tq1) = runs[True, n], runs[True, 1]
    exact_same = same_trees(qn, q1) and bool(np.array_equal(pqn, pq1))
    clock.line("sharded exact", f"deterministic_histogram=True, 5 rounds, "
               f"{len(X)}x{N_FEATURES}: n_devices={n} {tqn:.2f}s, "
               f"n_devices=1 {tq1:.2f}s (compile included); every tree field "
               f"and every prediction bitwise equal: {exact_same}; sample "
               f"AUC {sample_auc(qn, X, y):.4f}")
    check(exact_same, f"deterministic_histogram=True is not bitwise the same "
          f"on {n} devices and on one")

    (bn, pn, tn), (b1, p1, t1) = runs[False, n], runs[False, 1]
    diff = np.abs(pn - p1)
    auc_n, auc_1 = sample_auc(bn, X, y), sample_auc(b1, X, y)
    fork = first_divergence(bn, b1)
    clock.line("sharded float32", f"5 rounds: n_devices={n} {tn:.2f}s, "
               f"n_devices=1 {t1:.2f}s (compile included); "
               + ("every split of every tree the same"
                  if fork is None else
                  f"same splits up to {fork[2]}: best gains {fork[3]:.2e} "
                  f"apart, relatively")
               + f"; predictions differ by at most {float(diff.max()):.3e}, "
                 f"by more than {SHARD_TOL:g} on "
                 f"{100.0 * float((diff > SHARD_TOL).mean()):.2f}% of "
                 f"{len(diff)} rows; sample AUC {auc_n:.4f} against {auc_1:.4f}")
    if fork is None:
        check(float(diff.max()) <= SHARD_TOL, f"same splits, yet n_devices={n} "
              f"predictions differ from one device's by {float(diff.max()):.3e}")
    else:
        check(fork[3] <= TIE_TOL, f"n_devices={n} and n_devices=1 part at "
              f"{fork[2]}, which is no tie: the gains are {fork[3]:.2e} apart")
        check(abs(auc_n - auc_1) <= 1e-3, f"after parting at a tie the models' "
              f"AUC differ: {auc_n:.4f} against {auc_1:.4f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=2_000_000,
                    help="cut rows for a rehearsal; the width is never cut")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the sharded path and its comparison")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU; the last line says ok: false")
    args = ap.parse_args()

    clock = Clock()
    device = phase_device(args, clock)

    from xgboost_tpu.serving.warmcache import configure_persistent_cache

    cache_dir = configure_persistent_cache()
    seen = CacheHits()
    print(f"chip_smoke: rows={args.rows} features={N_FEATURES} "
          f"max_bin={MAX_BIN} max_depth={MAX_DEPTH} seed={args.seed} "
          f"chips={args.chips}", flush=True)
    X, y = make_data(args.rows, args.seed)
    clock.line("data", f"{X.shape} float32 from seed {args.seed}")
    if args.chips == 4:
        phase_sharded(args.chips, device, X, y, clock)
    else:
        phase_native(clock)
        bst, dtrain = phase_train(device, X, y, clock)
        host, dev = level_inputs(args.seed, bst, dtrain)
        phase_hist_truth(host, dev, clock)
        Xs, pred = phase_predict(bst, X, clock)
        phase_serve(bst, Xs, pred, clock)
        phase_fused_kernel(device, host, dev, clock)
        phase_cache(device, dtrain, cache_dir, seen, clock)
    import jax

    print(json.dumps({"ok": device.platform == "tpu",
                      "device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
