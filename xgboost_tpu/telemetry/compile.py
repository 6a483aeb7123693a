"""Retrace accounting: what jax traced, compiled and loaded, process-wide.

JAX (0.9.0) tells a monitoring listener three things about a program that
leaves the in-memory jit cache (a hit there fires nothing):

- ``/jax/core/compile/jaxpr_trace_duration``: a function was traced to a
  jaxpr (once for each jitted function, inner ones such as ``jnp.sort``
  included);
- ``/jax/core/compile/backend_compile_duration``: a program was asked of the
  backend.  It brackets the persistent compilation cache too, so it fires
  the same whether XLA compiled the program or the cache held it (seen on
  the chip in PR 21: 55 "compiles" cold and warm);
- ``/jax/compilation_cache/cache_hits``: fired inside that bracket, on the
  same thread, when the persistent cache held the program.

So a backend event with a cache hit inside it is a *load*, and one without
is a *compile*: the difference between a tuned pipeline and an accidentally
retracing one ("Out-of-Core GPU Gradient Boosting", 2005.09148) is knowing
when a step compiled, and on a machine with a warm cache, when it only
loaded.

The listeners register once at import, cost nothing between events, and
feed:

- ``compiles_total()`` (real compiles only), ``loads_total()`` and
  ``traces_total()``: the process-global ints that training
  (``TelemetryCallback`` per-round deltas, steady-state SLO: 0 after the
  warm-up round) and serving (``ServingEngine`` windows) read;
- the registry counters ``xtb_compiles_total{kind="compiled"|"loaded"}`` and
  ``xtb_traces_total`` (``xtb_compiles_steady`` is fed by whoever owns the
  warm/steady boundary: the TelemetryCallback after round 0, ServingMetrics
  outside warmup());
- one flight-ring record for each program compiled or loaded (kind
  ``compile``; name ``xla.compiled`` or ``xla.loaded``) with the function's
  name, the seconds and the training round it fell in.  Traces are too many
  for the ring (435 in a three-round toy train, against a ring of 512), so
  they are counted only;
- :func:`counting`, which puts the three counts of a span's lifetime into
  its ring record, and with them what the clocks and the collector did while
  it was open (pauses.py): training wraps the loop's three top-level spans
  in it, so that a window's rounds can be shown to hold no compile, no load
  and no trace, and a round's period to split into waits, CPU and neither.

``jax.monitoring`` listeners cannot be unregistered individually, so this
must never be registered twice (the module guard) and must stay cheap
forever (it is: a few string compares per monitoring event).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, Optional

from . import flight, pauses, spans
from .registry import get_registry

__all__ = ["compiles_total", "loads_total", "traces_total", "compile_delta",
           "counting", "install", "COMPILE_EVENT", "TRACE_EVENT",
           "CACHE_HIT_EVENT"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

KINDS = ("compiled", "loaded", "traced")

_lock = threading.Lock()
_totals: Dict[str, int] = dict.fromkeys(KINDS, 0)
_hit = threading.local()  # .seen: a cache hit since this thread's last backend event
_installed = False
_counters: Dict[str, object] = {}  # kind -> registry child (lazy)


def _counter(kind: str):
    child = _counters.get(kind)
    if child is None:
        reg = get_registry()
        if kind == "traced":
            child = reg.counter(
                "xtb_traces_total",
                "functions jax traced to a jaxpr in this process").labels()
        else:
            child = reg.counter(
                "xtb_compiles_total",
                "programs asked of the XLA backend in this process: "
                "compiled, or loaded from the persistent cache",
                ("kind",)).labels(kind)
        child = _counters.setdefault(kind, child)
    return child


def _count(kind: str, duration_secs: float, fun_name: Optional[str]) -> None:
    with _lock:
        _totals[kind] += 1
    _counter(kind).inc()
    if kind == "traced":
        return
    detail = {"s": duration_secs, "fun": fun_name}
    round_ = spans.current_round()
    if round_ is not None:
        detail["round"] = round_
    flight.record("compile", "xla." + kind, **detail)


def _on_event(name: str, **kw) -> None:
    if name == CACHE_HIT_EVENT:
        _hit.seen = True


def _on_duration(name: str, duration_secs: float, **kw) -> None:
    if name == TRACE_EVENT:
        _count("traced", duration_secs, kw.get("fun_name"))
    elif name == COMPILE_EVENT:
        loaded = getattr(_hit, "seen", False)
        _hit.seen = False
        _count("loaded" if loaded else "compiled", duration_secs,
               kw.get("fun_name"))


def install() -> None:
    """Register the monitoring listeners (idempotent; called at telemetry
    import so the counts exist before the first train())."""
    global _installed
    if _installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True


def compiles_total() -> int:
    """Programs XLA compiled since process start (monotonic); a load from
    the persistent compilation cache is not one."""
    return _totals["compiled"]


def loads_total() -> int:
    """Programs loaded from the persistent compilation cache."""
    return _totals["loaded"]


def traces_total() -> int:
    """Functions traced to a jaxpr."""
    return _totals["traced"]


@contextlib.contextmanager
def counting(sp: spans.Span) -> Iterator[spans.Span]:
    """``with counting(span(...)):`` is ``with span(...):`` whose ring record
    also says how many programs were ``compiled`` and ``loaded`` and how many
    functions ``traced`` while it was open, and what the thread's and the
    process's clocks and the collector's totals grew by (``pauses.since``:
    ``cpu_ns``, ``proc_cpu_ns``, ``ctx_invol``, ``majflt``, ``gc.ns``, ...)."""
    before = dict(_totals)
    with sp:
        meter = pauses.read()
        try:
            yield sp
        finally:
            sp.args.update((k, _totals[k] - before[k]) for k in KINDS)
            sp.args.update(pauses.since(meter))


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
