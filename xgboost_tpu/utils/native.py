"""ctypes bindings for the native host runtime (native/xtb_native.cc).

The reference's host-side hot loops are C++ (dmlc-core text parsers, CSR
adapters src/data/adapter.h:538 FileAdapter, GK summaries
src/common/quantile.h); ours live in one small C-ABI library loaded here.
Pure-Python fallbacks keep everything working when the .so hasn't been built
(``make -C native``) — the library auto-builds on first use when a toolchain
is present.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

_LIB = None
_TRIED = False


def _native_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")


def load_native() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    so = os.path.join(_native_dir(), "libxtb_native.so")
    srcs = [os.path.join(_native_dir(), n)
            for n in ("xtb_native.cc", "xtb_kernels.h", "xtb_simd.h")]
    stale = (not os.path.exists(so)
             or any(os.path.exists(s)
                    and os.path.getmtime(s) > os.path.getmtime(so)
                    for s in srcs))
    if stale:
        try:
            subprocess.run(["make", "-C", _native_dir()], capture_output=True,
                           timeout=120, check=True)
        except Exception:
            if not os.path.exists(so):
                return None
    try:
        lib = ctypes.CDLL(so)
    except OSError:
        return None
    c = ctypes
    lib.xtb_parse_libsvm.restype = c.c_void_p
    lib.xtb_parse_libsvm.argtypes = [c.c_char_p, c.c_int64]
    lib.xtb_parse_csv.restype = c.c_void_p
    lib.xtb_parse_csv.argtypes = [c.c_char_p, c.c_int64, c.c_int]
    lib.xtb_csr_rows.restype = c.c_int64
    lib.xtb_csr_nnz.restype = c.c_int64
    lib.xtb_csr_cols.restype = c.c_int32
    lib.xtb_csr_has_qid.restype = c.c_int32
    lib.xtb_csr_qid_count.restype = c.c_int64
    for f in (lib.xtb_csr_rows, lib.xtb_csr_nnz, lib.xtb_csr_cols,
              lib.xtb_csr_has_qid, lib.xtb_csr_qid_count, lib.xtb_csr_free,
              lib.xtb_dense_free):
        f.argtypes = [c.c_void_p]
    lib.xtb_csr_copy.argtypes = [c.c_void_p] + [c.c_void_p] * 5
    lib.xtb_dense_rows.restype = c.c_int64
    lib.xtb_dense_rows.argtypes = [c.c_void_p]
    lib.xtb_dense_cols.restype = c.c_int32
    lib.xtb_dense_cols.argtypes = [c.c_void_p]
    lib.xtb_dense_copy.argtypes = [c.c_void_p, c.c_void_p]
    lib.xtb_summary_new.restype = c.c_void_p
    lib.xtb_summary_new.argtypes = [c.c_int64]
    lib.xtb_summary_push.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64]
    lib.xtb_summary_query.argtypes = [c.c_void_p, c.c_void_p, c.c_int32, c.c_void_p]
    lib.xtb_summary_total.restype = c.c_double
    lib.xtb_summary_total.argtypes = [c.c_void_p]
    lib.xtb_summary_free.argtypes = [c.c_void_p]
    lib.xtb_shap_values.argtypes = [c.c_void_p, c.c_int64, c.c_int32,
                                    c.c_void_p, c.c_void_p, c.c_void_p,
                                    c.c_void_p, c.c_void_p, c.c_void_p,
                                    c.c_void_p, c.c_int32, c.c_void_p]
    lib.xtb_ellpack_bin.argtypes = [c.c_void_p, c.c_int64, c.c_int32,
                                    c.c_void_p, c.c_void_p, c.c_int32,
                                    c.c_int32, c.c_void_p]
    lib.xtb_hist_f32_u8.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p,
                                    c.c_int64, c.c_int32, c.c_int32,
                                    c.c_int32, c.c_int32, c.c_int32,
                                    c.c_int32, c.c_void_p]
    lib.xtb_hist_packed4.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p,
                                     c.c_int64, c.c_int32, c.c_int32,
                                     c.c_int32, c.c_int32, c.c_int32,
                                     c.c_void_p]
    _bind_pool_abi(lib)
    _LIB = lib
    if _NTHREAD is not None:  # pool configured before this lib loaded
        lib.xtb_set_nthread(_NTHREAD)
    if _SIMD is not None:  # simd level pinned before this lib loaded
        lib.xtb_simd_set(_SIMD)
    return lib


# --------------------------------------------------------------------------
# ParallelFor pool control (native/xtb_kernels.h XtbThreadPool).
#
# Each shared object (libxtb_native.so for the ctypes kernels, libxtb_ffi.so
# for the XLA custom calls) carries its own pool instance; configuration and
# stats reads fan out to every loaded library.  nthread precedence:
# explicit ``nthread`` param > ``XGBOOST_TPU_NTHREAD`` env > os.cpu_count().
# --------------------------------------------------------------------------

_NTHREAD: Optional[int] = None  # last applied effective thread count
_FFI_LIB = None                 # CDLL handle kept for the pool ABI

POOL_STAT_SLOTS = 13  # [regions, busy_ns, bucket_0 .. bucket_10]
POOL_PERF_SLOTS = 5   # [invocations, wall_ns, cycles, bytes, flops]


def _bind_pool_abi(lib) -> None:
    c = ctypes
    lib.xtb_set_nthread.restype = c.c_int
    lib.xtb_set_nthread.argtypes = [c.c_int]
    lib.xtb_get_nthread.restype = c.c_int
    lib.xtb_pool_alive_workers.restype = c.c_int
    lib.xtb_pool_faults_total.restype = c.c_int64
    lib.xtb_pool_regions_total.restype = c.c_int64
    lib.xtb_pool_n_kernels.restype = c.c_int
    lib.xtb_pool_kernel_name.restype = c.c_char_p
    lib.xtb_pool_kernel_name.argtypes = [c.c_int]
    lib.xtb_pool_kernel_stats.argtypes = [c.c_int, c.c_void_p]
    lib.xtb_pool_kernel_perf.argtypes = [c.c_int, c.c_void_p]
    lib.xtb_stream_triad.argtypes = [c.c_void_p, c.c_void_p, c.c_float,
                                     c.c_void_p, c.c_int64]
    lib.xtb_pool_instance_id.restype = c.c_uint64
    lib.xtb_simd_set.restype = c.c_int
    lib.xtb_simd_set.argtypes = [c.c_int]
    lib.xtb_simd_get.restype = c.c_int
    lib.xtb_simd_detected.restype = c.c_int
    lib.xtb_simd_lanes.restype = c.c_int
    lib.xtb_simd_name.restype = c.c_char_p
    lib.xtb_simd_name.argtypes = [c.c_int]


def _pool_libs() -> list:
    """Loaded kernel libraries, deduped by pool instance: gcc gives the
    pool's inline static STB_GNU_UNIQUE linkage, so libxtb_native.so and
    libxtb_ffi.so normally SHARE one pool in-process (configuring/killing/
    counting through either handle hits the same instance)."""
    seen, out = set(), []
    for lib in (load_native(), _FFI_LIB):
        if lib is None:
            continue
        pid = int(lib.xtb_pool_instance_id())
        if pid not in seen:
            seen.add(pid)
            out.append(lib)
    return out


_NTHREAD_CAP = 1024  # must mirror XtbThreadPool::resolve's clamp


def resolve_nthread(n: int = 0) -> int:
    """Effective thread count for ``nthread=n`` (0/negative = default),
    with the same 1024 cap the C++ pool applies — so the cached value,
    the gauge, and bench provenance report what the pool actually runs."""
    if n and int(n) > 0:
        return min(int(n), _NTHREAD_CAP)
    env = os.environ.get("XGBOOST_TPU_NTHREAD", "").strip()
    if env:
        try:
            v = int(env)
            if v > 0:
                return min(v, _NTHREAD_CAP)
        except ValueError:
            pass
    return min(os.cpu_count() or 1, _NTHREAD_CAP)


def set_nthread(n: int = 0) -> int:
    """Configure the native ParallelFor pools (both libraries) to ``n``
    threads (0 = default precedence above).  Kernel results are bitwise
    independent of this value (docs/native_threading.md); it only changes
    how many cores the native kernels use.  Idempotent and cheap when the
    effective count is unchanged."""
    global _NTHREAD
    _pool_fault_probe()
    eff = resolve_nthread(n)
    if eff == _NTHREAD:
        return eff
    for lib in _pool_libs():
        lib.xtb_set_nthread(eff)
    _NTHREAD = eff
    return eff


def get_nthread() -> int:
    """The currently applied pool width (resolving the default lazily)."""
    if _NTHREAD is None:
        return set_nthread(0)
    return _NTHREAD


def ensure_pool() -> None:
    """Dispatch-site hook (ops/histogram.py, ops/predict.py): apply the
    default pool width once before the first native kernel runs."""
    if _NTHREAD is None:
        set_nthread(0)


# --------------------------------------------------------------------------
# SIMD level control (native/xtb_simd.h).  Kernel output is bitwise
# level-INDEPENDENT (the lane-width axis of the determinism contract,
# fuzzed by tests/test_native_threads.py), so flipping this only selects
# which identical-output body runs.  Initial level: XGBOOST_TPU_SIMD env
# (scalar|avx2|neon|auto), else the best ISA cpuid reports.
# --------------------------------------------------------------------------

_SIMD: Optional[int] = None  # last applied level (C-side enum), None = auto
_SIMD_LEVELS = {"auto": -1, "scalar": 0, "avx2": 1, "neon": 2}


def set_simd(level="auto") -> str:
    """Set the active SIMD level on every loaded kernel library.

    ``level``: "auto" (best detected), "scalar", "avx2", "neon", or the
    C-side integer.  A level this HOST cannot run (e.g. "neon" on x86)
    resolves to the detected best; an unknown NAME raises — typos should
    be loud, not silently benchmark the wrong thing.  Returns the
    effective level name.
    """
    global _SIMD
    if not isinstance(level, int):
        key = str(level).lower()
        if key not in _SIMD_LEVELS:
            raise ValueError(
                f"unknown SIMD level {level!r}; expected one of "
                f"{sorted(_SIMD_LEVELS)}")
        lvl = _SIMD_LEVELS[key]
    else:
        lvl = int(level)
    eff = lvl
    for lib in _pool_libs():
        eff = int(lib.xtb_simd_set(lvl))
    _SIMD = eff if eff >= 0 else None
    return get_simd()


def get_simd() -> str:
    """The active SIMD level name on the loaded libraries ("scalar" when no
    native library is available — the pure-Python fallbacks are scalar)."""
    for lib in _pool_libs():
        return lib.xtb_simd_name(lib.xtb_simd_get()).decode()
    return "scalar"


def simd_info() -> dict:
    """Provenance record for benches (BENCH_LADDER.json metadata): active
    and detected ISA, lane width, and the raw CPU flags the detection saw."""
    info = {"active": "scalar", "detected": "scalar", "lanes": 1,
            "env": os.environ.get("XGBOOST_TPU_SIMD") or None}
    for lib in _pool_libs():
        info["active"] = lib.xtb_simd_name(lib.xtb_simd_get()).decode()
        info["detected"] = lib.xtb_simd_name(lib.xtb_simd_detected()).decode()
        info["lanes"] = int(lib.xtb_simd_lanes())
        break
    flags = []
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    present = set(line.split(":", 1)[1].split())
                    flags = sorted(present & {"avx", "avx2", "avx512f",
                                              "fma", "sse4_2", "asimd",
                                              "neon", "sve"})
                    break
    except OSError:  # pragma: no cover - non-procfs hosts
        pass
    info["cpu_flags"] = flags
    return info


def _pool_fault_probe() -> None:
    """`native.parallel_for` fault seam (reliability/faults.py): fires when
    the pool is (re)configured.  ``kill``/``exception``/``delay`` apply at
    the seam; the caller-applied kinds (``drop_connection``/``truncate``)
    make the pool lose one worker thread before its next region — the pool
    must complete the region on the remaining threads, stay bitwise-correct,
    and respawn (pinned by tests/test_native_threads.py)."""
    try:
        from ..reliability.faults import maybe_inject
    except ImportError:  # pragma: no cover - partial install
        return
    spec = maybe_inject("native.parallel_for")
    if spec is not None and spec.kind in ("drop_connection", "truncate"):
        for lib in _pool_libs():
            lib.xtb_pool_kill_worker()


def pool_stats() -> dict:
    """Aggregated pool counters across loaded libraries:
    ``{"nthread", "alive_workers", "faults_total", "regions_total",
    "kernels": {name: {"regions", "busy_ns", "buckets": [11],
    "invocations", "wall_ns", "cycles", "bytes", "flops"}}}``.
    The last five come from the per-kernel XtbKernelPerf scopes (rdtsc
    cycles + modeled bytes/flops); the Python-side telemetry bridge
    (telemetry/native_pool.py) folds the deltas into the registry and
    scripts/bench_roofline.py turns them into achieved GB/s."""
    out = {
        "nthread": get_nthread(),
        "alive_workers": 0,
        "faults_total": 0,
        "regions_total": 0,
        "kernels": {},
    }
    for lib in _pool_libs():
        out["alive_workers"] += int(lib.xtb_pool_alive_workers())
        out["faults_total"] += int(lib.xtb_pool_faults_total())
        out["regions_total"] += int(lib.xtb_pool_regions_total())
        buf = (ctypes.c_int64 * POOL_STAT_SLOTS)()
        pbuf = (ctypes.c_int64 * POOL_PERF_SLOTS)()
        for k in range(int(lib.xtb_pool_n_kernels())):
            name = lib.xtb_pool_kernel_name(k).decode()
            lib.xtb_pool_kernel_stats(k, buf)
            lib.xtb_pool_kernel_perf(k, pbuf)
            agg = out["kernels"].setdefault(
                name, {"regions": 0, "busy_ns": 0,
                       "buckets": [0] * (POOL_STAT_SLOTS - 2),
                       "invocations": 0, "wall_ns": 0, "cycles": 0,
                       "bytes": 0, "flops": 0})
            agg["regions"] += int(buf[0])
            agg["busy_ns"] += int(buf[1])
            for i in range(POOL_STAT_SLOTS - 2):
                agg["buckets"][i] += int(buf[2 + i])
            for i, key in enumerate(("invocations", "wall_ns", "cycles",
                                     "bytes", "flops")):
                agg[key] += int(pbuf[i])
    return out


def stream_triad(b, c, scalar, a) -> bool:
    """Run the native STREAM-style triad ``a[i] = b[i] + scalar*c[i]``
    through the ParallelFor pool (scripts/bench_roofline.py's host-peak
    probe).  Arrays must be contiguous float32 of equal length.  Returns
    False when no native library is loaded (caller falls back to numpy)."""
    import numpy as np

    for lib in _pool_libs():
        n = int(a.shape[0])
        assert b.shape[0] == n and c.shape[0] == n
        lib.xtb_stream_triad(
            b.ctypes.data_as(ctypes.c_void_p),
            c.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_float(float(scalar)),
            a.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int64(n))
        return True
    a[:] = b + np.float32(scalar) * c
    return False


_FFI_READY: Optional[bool] = None

# Distributed veto: when a multi-process communicator finds the FFI kernels
# unavailable on ANY rank, every rank must take the XLA formulations —
# split gains differ from the native scan in the last ulp, and
# heterogeneous per-rank impls could pick different near-tie splits on the
# redundant per-process evaluation (collective.py flips this at init).
FFI_DISTRIBUTED_VETO = False


def load_ffi() -> bool:
    """Build/load the XLA FFI handler library and register its targets.

    Returns True when ``xtb_hist`` / ``xtb_split`` are registered as CPU
    custom calls (jax.ffi).  The pure_callback route is NOT used as a
    fallback — jax 0.9's CPU host-callback deadlocks on large operands —
    callers fall back to the XLA scatter/cumsum formulations instead."""
    global _FFI_READY, _FFI_LIB
    if _FFI_READY is not None:
        return _FFI_READY
    _FFI_READY = False
    nd = _native_dir()
    so = os.path.join(nd, "libxtb_ffi.so")
    srcs = [os.path.join(nd, n)
            for n in ("xtb_ffi.cc", "xtb_kernels.h", "xtb_simd.h")]
    try:
        stale = (not os.path.exists(so)
                 or any(os.path.exists(s)
                        and os.path.getmtime(s) > os.path.getmtime(so)
                        for s in srcs))
        if stale:
            # serialize concurrent builders (multi-process training on one
            # host): the Makefile writes via a temp + rename, the flock
            # makes sure only one make runs and the rest wait for it
            import fcntl

            with open(os.path.join(nd, ".ffi_build.lock"), "w") as lk:
                fcntl.flock(lk, fcntl.LOCK_EX)
                try:
                    subprocess.run(["make", "-C", nd, "ffi"],
                                   capture_output=True, timeout=180,
                                   check=True)
                finally:
                    fcntl.flock(lk, fcntl.LOCK_UN)
        import ctypes as c

        from jax import ffi

        lib = c.CDLL(so)
        for name, sym in (("xtb_hist", lib.XtbHist),
                          ("xtb_hist_q", lib.XtbHistQ),
                          ("xtb_split", lib.XtbSplit),
                          ("xtb_predict", lib.XtbPredict),
                          ("xtb_predict_binned", lib.XtbPredictBinned),
                          ("xtb_lambdarank", lib.XtbLambdaRank)):
            ffi.register_ffi_target(name, ffi.pycapsule(sym), platform="cpu")
        _bind_pool_abi(lib)
        _FFI_LIB = lib
        if _NTHREAD is not None:  # pool configured before this lib loaded
            lib.xtb_set_nthread(_NTHREAD)
        if _SIMD is not None:
            lib.xtb_simd_set(_SIMD)
        _FFI_READY = True
    except Exception:
        _FFI_READY = False
    return _FFI_READY


def ffi_usable() -> bool:
    """load_ffi() minus the distributed veto — the gate compute paths use."""
    return not FFI_DISTRIBUTED_VETO and load_ffi()


_WIRE_LIB = None
_WIRE_TRIED = False


def load_wire() -> Optional[ctypes.CDLL]:
    """The fleet wire rx library (native/xtb_wire.cc): one GIL release
    covers a whole frame read + CRC verify on serving sockets.  Same
    auto-build / graceful-None contract as :func:`load_native`;
    serving/wire.py keeps its pure-Python reader when this returns None,
    so the wire contract never depends on a toolchain."""
    global _WIRE_LIB, _WIRE_TRIED
    if _WIRE_LIB is not None or _WIRE_TRIED:
        return _WIRE_LIB
    _WIRE_TRIED = True
    nd = _native_dir()
    so = os.path.join(nd, "libxtb_wire.so")
    src = os.path.join(nd, "xtb_wire.cc")
    stale = (not os.path.exists(so)
             or (os.path.exists(src)
                 and os.path.getmtime(src) > os.path.getmtime(so)))
    if stale:
        try:
            subprocess.run(["make", "-C", nd, "wire"], capture_output=True,
                           timeout=120, check=True)
        except Exception:
            if not os.path.exists(so):
                return None
    try:
        lib = ctypes.CDLL(so)
    except OSError:
        return None
    c = ctypes
    lib.xtb_wire_read_prefix.restype = c.c_int
    lib.xtb_wire_read_prefix.argtypes = [
        c.c_int, c.c_double, c.POINTER(c.c_uint), c.POINTER(c.c_ulonglong),
        c.POINTER(c.c_uint), c.POINTER(c.c_double)]
    lib.xtb_wire_read_body.restype = c.c_int
    lib.xtb_wire_read_body.argtypes = [
        c.c_int, c.c_void_p, c.c_ulonglong, c.c_double, c.c_uint]
    lib.xtb_wire_crc32.restype = c.c_uint
    lib.xtb_wire_crc32.argtypes = [c.c_uint, c.c_void_p, c.c_ulonglong]
    _WIRE_LIB = lib
    return lib


def parse_libsvm(path: str):
    """Parse a libsvm file -> (indptr, indices, values, labels, qid|None, n_col).

    Native fast path; pure-Python fallback parses the same grammar.
    """
    with open(path, "rb") as fh:
        raw = fh.read()
    lib = load_native()
    if lib is not None:
        h = lib.xtb_parse_libsvm(raw, len(raw))
        try:
            rows = lib.xtb_csr_rows(h)
            nnz = lib.xtb_csr_nnz(h)
            cols = lib.xtb_csr_cols(h)
            has_qid = bool(lib.xtb_csr_has_qid(h))
            if has_qid and lib.xtb_csr_qid_count(h) != rows:
                raise ValueError(
                    f"libsvm file has qid on only {lib.xtb_csr_qid_count(h)} "
                    f"of {rows} rows; qid must cover every row")
            indptr = np.empty(rows + 1, np.int64)
            indices = np.empty(nnz, np.int32)
            values = np.empty(nnz, np.float32)
            labels = np.empty(rows, np.float32)
            qids = np.empty(rows, np.int64) if has_qid else None
            lib.xtb_csr_copy(
                h, indptr.ctypes.data, indices.ctypes.data, values.ctypes.data,
                labels.ctypes.data, qids.ctypes.data if has_qid else None)
            return indptr, indices, values, labels, qids, cols
        finally:
            lib.xtb_csr_free(h)
    # fallback
    indptr, indices, values, labels, qids = [0], [], [], [], []
    n_col = 0
    has_qid = False
    for line in raw.decode("utf-8", "ignore").splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        parts = line.split()
        labels.append(float(parts[0]))
        for tok in parts[1:]:
            if tok.startswith("#"):
                break
            k, v = tok.split(":", 1)
            if k == "qid":
                has_qid = True
                qids.append(int(v))
                continue
            idx = int(k)
            indices.append(idx)
            values.append(float(v))
            n_col = max(n_col, idx + 1)
        indptr.append(len(indices))
    if has_qid and len(qids) != len(labels):
        raise ValueError(
            f"libsvm file has qid on only {len(qids)} of {len(labels)} rows; "
            f"qid must cover every row")
    return (np.asarray(indptr, np.int64), np.asarray(indices, np.int32),
            np.asarray(values, np.float32), np.asarray(labels, np.float32),
            np.asarray(qids, np.int64) if has_qid else None, n_col)


def parse_csv(path: str, skip_header: Optional[bool] = None) -> np.ndarray:
    with open(path, "rb") as fh:
        raw = fh.read()
    if skip_header is None:
        # sniff: a first line whose first field isn't numeric is a header
        first = raw.split(b"\n", 1)[0].split(b",", 1)[0].strip()
        try:
            float(first)
            skip_header = False
        except ValueError:
            skip_header = bool(first)
    lib = load_native()
    if lib is not None:
        h = lib.xtb_parse_csv(raw, len(raw), int(skip_header))
        try:
            rows = lib.xtb_dense_rows(h)
            cols = lib.xtb_dense_cols(h)
            out = np.empty((rows, cols), np.float32)
            lib.xtb_dense_copy(h, out.ctypes.data)
            return out
        finally:
            lib.xtb_dense_free(h)
    return np.genfromtxt(path, delimiter=",", dtype=np.float32,
                         skip_header=int(skip_header))


_ELLPACK_DTYPE_CODES = {np.dtype(np.uint8): 0, np.dtype(np.int16): 1,
                        np.dtype(np.int32): 2}


def ellpack_bin_native(X: np.ndarray, cut_values: np.ndarray,
                       cut_ptrs: np.ndarray, n_bin_pad: int,
                       dtype) -> Optional[np.ndarray]:
    """Native Ellpack binning (xtb_kernels.h xtb_ellpack_bin_impl): bin a
    dense (R, F) f32 matrix against per-feature cuts, bitwise-equal to the
    XLA searchsorted path in data/ellpack.py (upper_bound, clamp into the
    top bin, NaN -> sentinel ``n_bin_pad``).  Streams X row-major once and
    writes the page sequentially through the threaded row-sharded kernel.
    Returns None when the native library is unavailable."""
    lib = load_native()
    if lib is None:
        return None
    code = _ELLPACK_DTYPE_CODES.get(np.dtype(dtype))
    if code is None:
        return None
    R, F = X.shape
    Xc = np.ascontiguousarray(X, np.float32)
    cv = np.ascontiguousarray(cut_values, np.float32)
    cp = np.ascontiguousarray(cut_ptrs, np.int32)
    out = np.empty((R, F), np.dtype(dtype))
    ensure_pool()
    lib.xtb_ellpack_bin(Xc.ctypes.data, R, F, cv.ctypes.data, cp.ctypes.data,
                        int(n_bin_pad), code, out.ctypes.data)
    return out


def shap_values_native(t: dict, X: np.ndarray,
                       max_depth: int) -> Optional[np.ndarray]:
    """Row-parallel exact TreeSHAP for one scalar-leaf numeric tree
    (native/xtb_kernels.h xtb_shap_values_impl — the f64 twin of the host
    walk in interpret/__init__.py, identical operation order).

    ``t`` is interpret's ``_tree_arrays`` dict; returns (R, F+1) with the
    bias column left at zero (the caller fills the tree expectation), or
    None when the native library is unavailable."""
    lib = load_native()
    if lib is None:
        return None
    R, F = X.shape
    Xc = np.ascontiguousarray(X, np.float64)
    left = np.ascontiguousarray(t["left"], np.int32)
    right = np.ascontiguousarray(t["right"], np.int32)
    feat = np.ascontiguousarray(t["feat"], np.int32)
    thr = np.ascontiguousarray(t["thr"], np.float64)
    dleft = np.ascontiguousarray(t["dleft"], np.uint8)
    value = np.ascontiguousarray(t["value"], np.float64)
    cover = np.ascontiguousarray(t["cover"], np.float64)
    out = np.zeros((R, F + 1), np.float64)
    ensure_pool()
    lib.xtb_shap_values(
        Xc.ctypes.data, R, F, left.ctypes.data, right.ctypes.data,
        feat.ctypes.data, thr.ctypes.data, dleft.ctypes.data,
        value.ctypes.data, cover.ctypes.data, int(max_depth),
        out.ctypes.data)
    return out


class StreamingQuantileSummary:
    """Per-feature streaming weighted quantile summary (GK-style merge-prune).

    Native-backed when available; numpy fallback keeps semantics identical.
    The external-memory sketcher now uses the page-wise
    ``data/quantile.py StreamingSketch`` (its merge is the bitwise-pinned
    distributed contract, docs/extmem.md); this remains the public
    bounded-memory single-column summary API (native kernel +
    tests/test_native_threads.py) for callers that cannot batch a page.
    """

    def __init__(self, budget: int = 2048):
        self.budget = budget
        self._lib = load_native()
        if self._lib is not None:
            self._h = self._lib.xtb_summary_new(budget)
        else:
            self._vals = np.zeros(0, np.float32)
            self._wts = np.zeros(0, np.float64)

    def push(self, values: np.ndarray, weights: Optional[np.ndarray] = None):
        values = np.ascontiguousarray(values, np.float32)
        if self._lib is not None:
            w = None if weights is None else np.ascontiguousarray(weights, np.float32)
            self._lib.xtb_summary_push(
                self._h, values.ctypes.data,
                w.ctypes.data if w is not None else None, len(values))
            return
        keep = ~np.isnan(values)
        v = values[keep]
        w = (np.ones(len(v)) if weights is None
             else np.asarray(weights, np.float64)[keep])
        pos = w > 0  # native path drops non-positive weights; keep parity
        v, w = v[pos], w[pos]
        self._vals = np.concatenate([self._vals, v])
        self._wts = np.concatenate([self._wts, w])
        if len(self._vals) > 2 * self.budget:
            self._prune()

    def _prune(self):
        order = np.argsort(self._vals, kind="stable")
        v, w = self._vals[order], self._wts[order]
        cdf = np.cumsum(w)
        targets = cdf[-1] * np.arange(1, self.budget + 1) / self.budget
        idx = np.searchsorted(cdf, targets, side="left")
        idx = np.clip(idx, 0, len(v) - 1)
        uniq, first = np.unique(idx, return_index=True)
        seg_w = np.diff(np.concatenate([[0.0], cdf[uniq]]))
        self._vals = v[uniq].astype(np.float32)
        self._wts = seg_w
    def total_weight(self) -> float:
        if self._lib is not None:
            return float(self._lib.xtb_summary_total(self._h))
        return float(self._wts.sum())

    def query(self, qs: np.ndarray) -> np.ndarray:
        qs = np.ascontiguousarray(qs, np.float64)
        out = np.empty(len(qs), np.float32)
        if self._lib is not None:
            self._lib.xtb_summary_query(self._h, qs.ctypes.data, len(qs),
                                        out.ctypes.data)
            return out
        if len(self._vals) == 0:
            return np.zeros(len(qs), np.float32)
        order = np.argsort(self._vals, kind="stable")
        v, w = self._vals[order], self._wts[order]
        cdf = np.cumsum(w)
        idx = np.searchsorted(cdf, qs * cdf[-1], side="left")
        return v[np.clip(idx, 0, len(v) - 1)].astype(np.float32)

    def __del__(self):
        if getattr(self, "_lib", None) is not None and getattr(self, "_h", None):
            try:
                self._lib.xtb_summary_free(self._h)
            except Exception:
                pass
