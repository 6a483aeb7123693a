"""Retrace accounting: count XLA backend compiles process-wide.

JAX emits a ``/jax/core/compile/backend_compile_duration`` monitoring event
for every program that leaves the in-memory jit cache — a hit there does
not fire it; with jax 0.9.0 a hit in the persistent compilation cache does
(seen on the chip in PR 21: the same count cold and warm), so the count says
"traced and asked for", not "compiled from nothing".
Counting those events gives the exact signal "Out-of-Core GPU Gradient
Boosting" (2005.09148) calls out: the difference between a tuned pipeline
and an accidentally-retracing one is knowing when a step compiled.

The listener registers once at import, costs nothing between compiles, and
feeds three sinks:

- ``compiles_total()`` — the process-global int both training
  (``TelemetryCallback`` per-round deltas, steady-state SLO: 0 after the
  warm-up round) and serving (``ServingEngine`` windows) read;
- the registry counters ``xtb_compiles_total`` / ``xtb_compiles_steady``
  (the steady counter is fed by whoever owns the warm/steady boundary —
  the TelemetryCallback after round 0, ServingMetrics outside warmup());
- a JSONL trace event per compile when ``XGBOOST_TPU_TRACE`` is set, so
  retraces are visible inline with the phase spans they stall.

``jax.monitoring`` listeners cannot be unregistered individually, so this
must never be registered twice (the module guard) and must stay cheap
forever (it is: one string compare per monitoring event).
"""
from __future__ import annotations

import threading
import time
from typing import Optional

from . import trace
from .registry import get_registry

__all__ = ["compiles_total", "compile_delta", "install", "COMPILE_EVENT"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_total = 0
_installed = False
_counter = None  # xtb_compiles_total registry child (lazy)


def _on_event(name: str, duration_secs: float, **kw) -> None:
    global _total, _counter
    if name != COMPILE_EVENT:
        return
    with _lock:
        _total += 1
    if _counter is None:
        _counter = get_registry().counter(
            "xtb_compiles_total",
            "XLA backend compiles in this process (cache misses)").labels()
    _counter.inc()
    if trace.active():
        dur_ns = int(duration_secs * 1e9)
        trace.emit("xla.compile", time.perf_counter_ns() - dur_ns, dur_ns)


def install() -> None:
    """Register the monitoring listener (idempotent; called at telemetry
    import so compile counts exist before the first train())."""
    global _installed
    if _installed:
        return
    try:
        import jax.monitoring
    except Exception:  # pragma: no cover - no jax in the process
        return
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    _installed = True


def compiles_total() -> int:
    """Backend compiles since process start (monotonic)."""
    return _total


class compile_delta:
    """``with compile_delta() as w: ...; w.count`` — compiles inside the
    block.  Process-global like the underlying jit caches: concurrent
    compiling threads land in whichever window is open (same best-effort
    attribution as ServingMetrics.note_steady_compiles)."""

    def __init__(self) -> None:
        self._start = 0
        self.count: Optional[int] = None

    def __enter__(self) -> "compile_delta":
        self._start = compiles_total()
        return self

    def __exit__(self, *exc) -> None:
        self.count = compiles_total() - self._start


install()
