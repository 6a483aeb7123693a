"""Replica warm-start: persistent XLA compile cache + AOT program file.

Replica cold-start has two compile layers, attacked separately:

1. **XLA persistent compilation cache** (:func:`configure_persistent_cache`)
   — JAX's own cache, so every backend compile (peripheral eager ops,
   transforms, anything not AOT-covered) is a disk hit after the first
   process that ran it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set that is
   the directory and nothing here names another; otherwise it is the
   caller's directory or one fixed directory in the checkout.  This layer
   skips *compilation* but still pays trace + lowering per program.

2. **AOT program warm file** (``programs.pkl``) — the serving margin
   programs themselves (the multi-second part of warm-up) are compiled
   once, serialized with ``jax.experimental.serialize_executable``, and
   deserialized by every later replica: no trace, no lowering, no compile
   — a few ms per program.  This is what turns replica cold-start from
   seconds into milliseconds (BENCH_SERVE.json ``fleet_coldstart``).

The serialized program is a *fused serve step*: bucket-padded rows in,
``(margin + base_score, pred_transform(margin + base_score))`` out — one
executable serves both ``output_margin`` polarities, and the warm path
never traces the peripheral add/transform ops either.  Programs are keyed
by everything that shapes the executable (stacked tensor shapes/dtypes,
depth, group count, objective, bucket, jax/backend version, device kind
and count), NOT by the weights: two same-architecture model versions share
one program, so a hot-swapped retrain warms instantly.  A program is built
for one device and reloaded onto that device only.

Executables embed the ``xtb_predict`` FFI custom call; deserialization
requires the native library's targets registered first —
:func:`attach_aot` handles that ordering.  The warm file is advisory: any
load failure (version skew, corrupt file) falls back to a fresh compile
and rewrites the file (atomic tmp + rename).
"""
from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

_WARM_FILE = "programs.pkl"
_FORMAT = 1


# the persistent cache's home when neither the environment nor the caller
# names one: a fixed path (the path is part of the cache key, so a directory
# that moves never hits), inside the checkout and git-ignored
_DEFAULT_JAX_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_persistent_cache(cache_dir: Optional[str] = None) -> str:
    """Turn on jax's persistent compilation cache and return its directory
    (idempotent; call before the first jit of the process for full effect).

    The one place in the repository that may place that cache — the smoke,
    the bench and the replica all come through here.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own reading of it stands and
    no directory is set in code; otherwise the cache goes to ``cache_dir``
    or, without one, to ``.jax_cache/`` at the root of the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = os.fspath(cache_dir or _DEFAULT_JAX_CACHE)
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the programs here are many and individually quick to compile — cache
    # all of them, not just the slow or the large ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def program_key(snap, bucket: int) -> str:
    """Cache key for one (snapshot architecture, row bucket) serve program.

    Hashes program *shape*, never weights — see module docstring.  The jax
    and backend versions are folded in because serialized executables are
    not portable across them, the device kind and count because an
    executable carries the device set it was compiled against.
    """
    import jax

    h = hashlib.sha256()
    h.update(f"fmt{_FORMAT}|jax{jax.__version__}|"
             f"{jax.default_backend()}|{_program_device().device_kind}|"
             f"n{jax.device_count()}|".encode())
    h.update(f"b{int(bucket)}|d{snap.depth}|g{snap.n_groups}|"
             f"f{snap.num_features}|{type(snap.objective).__name__}|"
             f"{getattr(snap, 'store_meta', {}).get('objective', '')}|"
             .encode())
    if snap.stacked is None:
        h.update(b"stump")
    else:
        for k in sorted(snap.stacked):
            v = snap.stacked[k]
            if v is None:
                h.update(f"{k}:none|".encode())
            else:
                h.update(f"{k}:{tuple(v.shape)}:{np.dtype(v.dtype).str}|"
                         .encode())
    return h.hexdigest()


def _program_device():
    """The one device a serve program is compiled for and reloaded onto."""
    import jax

    return jax.devices()[0]


def _load_program(payload):
    """Deserialize one warm-file payload onto the program device.  Left to
    its default, ``deserialize_and_load`` reads the executable as spanning
    every local device."""
    from jax.experimental import serialize_executable

    return serialize_executable.deserialize_and_load(
        *payload, execution_devices=[_program_device()])


def _fused_serve_fn(snap):
    """The traced serve step for one snapshot: padded rows -> (margin,
    transformed), base score folded in.  Bitwise-identical math to the
    engine's eager path (same run_stacked_margin trace, same elementwise
    add/transform — fusion cannot reassociate per-element chains)."""
    from ..ops.predict import run_stacked_margin

    depth, n_groups, objective = snap.depth, snap.n_groups, snap.objective

    def fn(Xp, stacked, groups, base):
        m = run_stacked_margin(Xp, stacked, groups, depth, n_groups,
                               None) + base[None, :]
        return m, objective.pred_transform(m)

    return fn


def build_program(snap, bucket: int):
    """Trace + lower + compile the fused serve program for one bucket."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    fn = _fused_serve_fn(snap)
    one = SingleDeviceSharding(_program_device())

    def shape(shp, dtype):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one)

    Xp = shape((int(bucket), max(snap.num_features, 1)), jnp.float32)
    base = shape((snap.n_groups,), jnp.float32)
    shaped = jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype), dict(snap.stacked))
    groups = (shape(snap.groups.shape, snap.groups.dtype)
              if snap.groups is not None else None)
    return jax.jit(fn).lower(Xp, shaped, groups, base).compile()


class WarmProgramCache:
    """The ``programs.pkl`` warm file in a fleet cache directory.

    ``attach(snap, buckets)`` populates ``snap.aot_programs`` (bucket ->
    compiled executable), deserializing warm entries and compiling+
    collecting cold ones; ``save()`` persists anything newly compiled.
    Thread-safe for the multi-model replica warm loop.
    """

    def __init__(self, cache_dir: Optional[str]) -> None:
        self.dir = os.fspath(cache_dir) if cache_dir else None
        self._lock = threading.Lock()
        self._payloads: Dict[str, tuple] = {}
        self._dirty = False
        self.hits = 0
        self.misses = 0
        if self.dir:
            os.makedirs(self.dir, exist_ok=True)
            self._payloads = self._load_file()

    def _path(self) -> str:
        return os.path.join(self.dir, _WARM_FILE)

    def _load_file(self) -> Dict[str, tuple]:
        try:
            with open(self._path(), "rb") as fh:
                obj = pickle.load(fh)
            if obj.get("format") == _FORMAT:
                return dict(obj["programs"])
        except FileNotFoundError:
            pass  # no warm file yet: every program compiles (cold)
        except OSError as e:
            from ..reliability import resources as _resources

            _resources.note_os_error(e, "warmcache.load")
        except (pickle.UnpicklingError, EOFError, KeyError,
                AttributeError):
            pass  # stale/corrupt cache payload: fall back to compiling
        return {}

    # ------------------------------------------------------------------ API
    def attach(self, snap, buckets) -> dict:
        """Ensure ``snap.aot_programs[bucket]`` exists for every bucket.
        Returns ``{"hits": n, "compiled": n, "seconds": s}``."""
        from ..utils import native
        from jax.experimental import serialize_executable

        t0 = time.perf_counter()
        stats = {"hits": 0, "compiled": 0, "seconds": 0.0}
        if snap.stacked is None:  # stump: nothing worth AOT-ing
            return stats
        native.load_ffi()  # register custom-call targets BEFORE deserialize
        for bucket in sorted({int(b) for b in buckets}):
            if bucket in snap.aot_programs:
                continue
            key = program_key(snap, bucket)
            with self._lock:
                payload = self._payloads.get(key)
            compiled = None
            if payload is not None:
                try:
                    compiled = _load_program(payload)
                    stats["hits"] += 1
                except Exception:
                    compiled = None  # stale/foreign entry: recompile below
            if compiled is None:
                compiled = build_program(snap, bucket)
                stats["compiled"] += 1
                if self.dir:
                    ser = serialize_executable.serialize(compiled)
                    # an executable that build_program got as an XLA
                    # persistent-cache HIT serializes non-hermetically
                    # (deserialize dies with "Symbols not found" — the
                    # cached artifact lacks the JIT'd function bodies).
                    # The round-trip check catches exactly that in-process;
                    # a payload that fails it must never reach the warm
                    # file.  Whoever actually COMPILED the program
                    # persists a good entry, so the fleet still converges.
                    try:
                        _load_program(ser)
                    except Exception:
                        ser = None
                    if ser is not None:
                        with self._lock:
                            self._payloads[key] = ser
                            self._dirty = True
            snap.aot_programs[bucket] = compiled
        with self._lock:
            self.hits += stats["hits"]
            self.misses += stats["compiled"]
        stats["seconds"] = time.perf_counter() - t0
        return stats

    def save(self) -> bool:
        """Write newly-compiled programs back (atomic; merges with the
        current on-disk file first — entries are content-keyed, so
        concurrent replicas each persisting their own compiles converge
        on the union instead of last-writer dropping the other's work)."""
        with self._lock:
            if not (self.dir and self._dirty):
                return False
            merged = self._load_file()
            merged.update(self._payloads)
            self._payloads = merged
            blob = pickle.dumps({"format": _FORMAT,
                                 "programs": merged})
            self._dirty = False
        fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".warm.tmp")
        with os.fdopen(fd, "wb") as fh:
            fh.write(blob)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, self._path())
        return True
