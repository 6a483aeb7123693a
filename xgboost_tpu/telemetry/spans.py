"""Span tracer: named wall-clock brackets over the training/serving hot path.

``span("grow.to_host")`` is a context manager.  Every span, with no switch,

- opens a ``jax.profiler.TraceAnnotation`` — a no-op unless a profiler
  session is live, and when one is live the span lands on the host plane of
  the same ``.xplane.pb`` as the device operations, on one clock
  (``telemetry/xplane.py`` reads it back);
- on exit appends one record to the flight ring (``flight.py``, bounded)
  with ``t0_ns``, ``dur_ns``, the enclosing span's name (``parent``), the
  training round (``round``: set by the round span, absent outside a round)
  and the span's own arguments.  :func:`recent` reads them back: it is what
  the benchmark's ``program_span`` metrics and ``TelemetryCallback`` read.

The flag (``enable()`` / ``XGBOOST_TPU_TRACE``) gates the two sinks that
cost more: the registry histogram ``xtb_phase_seconds{phase=...}`` and the
JSONL trace event (trace.py).  With it off a span touches no registry
family and no file (tests/test_telemetry.py has the guard test).

``utils/timer.Monitor`` is a thin shim over ``record_phase`` (the flag's
sinks, stack-based start/stop bracketing); use ``span`` directly in new code.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax.profiler as _profiler

from . import flight, trace
from .registry import get_registry

__all__ = ["span", "step_span", "recent", "current_round", "count_in_round",
           "enable", "disable", "enabled", "record_phase", "Span",
           "phase_totals", "PHASE_HISTOGRAM"]

PHASE_HISTOGRAM = "xtb_phase_seconds"

# gates the histogram and the JSONL writer; a configured trace destination
# implies both are wanted (an empty trace would be the only alternative)
_ENABLED: bool = bool(os.environ.get(trace.ENV_VAR))

_phase_hist = None  # created lazily so importing telemetry stays cheap
_children: Dict[str, object] = {}  # phase name -> histogram child (cached)


class _Open(threading.local):
    """The spans open on this thread, outermost first, and its round."""

    def __init__(self) -> None:
        self.names: List[str] = []
        self.round: Optional[int] = None
        self.step: Optional["Span"] = None  # the round span, while open


_open = _Open()


def enabled() -> bool:
    return _ENABLED


def enable(on: bool = True) -> None:
    """Turn the histogram and JSONL sinks on (idempotent; process-wide)."""
    global _ENABLED
    _ENABLED = bool(on)


def disable() -> None:
    enable(False)


def current_round() -> Optional[int]:
    """The training round this thread is in, None outside one."""
    return _open.round


def count_in_round(**counts: int) -> None:
    """Add ``counts`` to the counters that the round span open on this
    thread carries as its arguments (its ring record holds the round's sums);
    outside a round nothing is counted."""
    if _open.step is not None:
        args = _open.step.args
        for key, n in counts.items():
            args[key] = args.get(key, 0) + n


def _hist():
    global _phase_hist
    if _phase_hist is None:
        _phase_hist = get_registry().histogram(
            PHASE_HISTOGRAM,
            "wall-clock seconds per instrumented phase", ("phase",))
    return _phase_hist


def _child(name: str):
    child = _children.get(name)
    if child is None:
        child = _children.setdefault(name, _hist().labels(name))
    return child


def _flag_sinks(name: str, t0_ns: int, dur_ns: int) -> None:
    """The two sinks the flag gates: registry histogram and JSONL event."""
    _child(name).observe(dur_ns / 1e9)
    if trace.active():
        trace.emit(name, t0_ns, dur_ns)


def record_phase(name: str, t0_ns: int, dur_ns: int) -> None:
    """Feed one finished bracket into every sink (ring, histogram, JSONL).
    For callers that time a bracket themselves behind :func:`enabled` (the
    Monitor shim, the batcher's admission wait)."""
    flight.record("span", name, t0_ns=t0_ns, dur_ns=dur_ns)
    _flag_sinks(name, t0_ns, dur_ns)


class Span:
    """One bracket.  Usable as a context manager or via explicit
    begin()/end()."""

    __slots__ = ("name", "args", "t0", "_ann", "_round_before")

    def __init__(self, name: str, args: Optional[Dict[str, Any]] = None) -> None:
        self.name = name
        self.args = args or {}
        self.t0 = 0
        self._ann = None

    def _annotation(self):
        return _profiler.TraceAnnotation(self.name, **self.args)

    def begin(self) -> "Span":
        self._round_before = _open.round
        if "round" in self.args:
            _open.round = self.args["round"]
        _open.names.append(self.name)
        self._ann = self._annotation()
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def end(self) -> int:
        dur = time.perf_counter_ns() - self.t0
        self._ann.__exit__(None, None, None)
        names = _open.names
        names.pop()
        detail = dict(self.args)
        if names:
            detail["parent"] = names[-1]
        if _open.round is not None:
            detail["round"] = _open.round
        _open.round = self._round_before
        flight.record("span", self.name, t0_ns=self.t0, dur_ns=dur, **detail)
        if _ENABLED:
            _flag_sinks(self.name, self.t0, dur)
        return dur

    def __enter__(self) -> "Span":
        return self.begin()

    def __exit__(self, *exc) -> None:
        self.end()


class _StepSpan(Span):
    """The round span: a ``StepTraceAnnotation``, so that XProf groups device
    time by round; its record carries ``seq0``, the ring's sequence number
    as the round began, by which :func:`recent` tells a whole round from one
    the ring has begun to overwrite."""

    __slots__ = ()

    def _annotation(self):
        return _profiler.StepTraceAnnotation(self.name,
                                             step_num=self.args["round"])

    def begin(self) -> "Span":
        self.args["seq0"] = flight.seq()
        _open.step = self
        return super().begin()

    def end(self) -> int:
        _open.step = None
        return super().end()


def span(name: str, **args: Any) -> Span:
    """The instrumentation entry point.  ``args`` go to the annotation and
    the ring record; ``round=`` also makes this span and everything inside it
    belong to that training round."""
    return Span(name, args)


def step_span(name: str, round: int) -> Span:
    """The span of one whole training round (see :class:`_StepSpan`)."""
    return _StepSpan(name, {"round": int(round)})


def recent(name: Optional[str] = None,
           round_from: Optional[int] = None) -> List[Dict[str, Any]]:
    """The span records the ring still holds, oldest first, each a flat dict
    (``name``, ``seq``, ``t0_ns``, ``dur_ns``, and where they apply
    ``parent``, ``round`` and the span's arguments).

    ``name`` keeps the spans of that name.  ``round_from`` keeps the spans of
    rounds ``>= round_from`` and of whole rounds only: a round counts as
    whole when its round span is in the ring and nothing recorded since that
    span began has been overwritten.  A ring that has wrapped therefore
    returns fewer rounds, never part of one."""
    events = flight.events()
    if not events:
        return []
    oldest = events[0]["seq"]
    out = [dict(e.get("detail", ()), name=e["name"], seq=e["seq"])
           for e in events if e["kind"] == "span"]
    if round_from is not None:
        # round spans with nothing lost since they began, and what lies
        # before the first of them is the tail of a round that is not whole
        whole = [r for r in out if r.get("seq0", -1) >= oldest]
        rounds = {r["round"] for r in whole}
        first = min((r["seq0"] for r in whole), default=0)
        out = [r for r in out
               if r.get("round") in rounds and r["seq"] >= first
               and r["round"] >= round_from]
    if name is not None:
        out = [r for r in out if r["name"] == name]
    return out


def phase_totals() -> Dict[str, Dict[str, float]]:
    """{phase: {"count": n, "seconds": s}} accumulated so far while the flag
    was on (render_prometheus() has the full histogram)."""
    hist = get_registry().get(PHASE_HISTOGRAM)
    if hist is None:
        return {}
    return {values[0]: {"count": c, "seconds": s}
            for values, (c, s) in hist.snapshot_sums().items()}
