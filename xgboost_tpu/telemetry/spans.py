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
  the benchmark's ``program_span`` metrics and ``TelemetryCallback`` read,
  and what :func:`round_account` partitions a round's period by (every
  nanosecond of it put down to a span's self time or to the gap between the
  loop's top-level spans).

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
from .xplane import self_time

__all__ = ["span", "step_span", "wait_span", "recent", "round_account",
           "current_round",
           "count_in_round", "enable", "disable", "enabled", "record_phase",
           "Span", "phase_totals", "PHASE_HISTOGRAM", "CONTAINERS", "GC_SPAN",
           "WARM"]

PHASE_HISTOGRAM = "xtb_phase_seconds"
#: The spans of the loop that hold other spans: what is left of them once
#: their children are taken out is host time that no span names.
CONTAINERS = ("train.round", "train.after_iteration", "update.update_tree")
#: A round whose top-level spans count any of these (``compile.counting``
#: sets them: programs compiled and loaded, functions traced, a profiler
#: session begun or ended) is warm, and is never called slow.
WARM = ("compiled", "loaded", "traced", "session_edge")
#: A pause of the cycle collector (pauses.py): a record that overlaps the
#: span it interrupted, so it is reported beside a round's partition.
GC_SPAN = "host.gc"

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

    __slots__ = ("name", "args", "t0", "dur", "_ann", "_round_before")

    def __init__(self, name: str, args: Optional[Dict[str, Any]] = None) -> None:
        self.name = name
        self.args = args or {}
        self.t0 = 0
        self.dur = 0  # set as the span ends
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
        dur = self.dur = time.perf_counter_ns() - self.t0
        self._ann.__exit__(None, None, None)
        names = _open.names
        names.pop()
        detail = dict(self.args, tid=threading.get_ident())
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


class _WaitSpan(Span):
    """A span in which the thread waits for something that has a name: its
    record carries ``cpu_ns``, the thread's CPU time while it was open (a
    wait spins before it sleeps, and an evaluation computes), so that an
    account can tell the CPU time outside the waits from the whole."""

    __slots__ = ("_cpu0",)

    def begin(self) -> "Span":
        self._cpu0 = time.thread_time_ns()
        return super().begin()

    def end(self) -> int:
        self.args["cpu_ns"] = time.thread_time_ns() - self._cpu0
        return super().end()


def span(name: str, **args: Any) -> Span:
    """The instrumentation entry point.  ``args`` go to the annotation and
    the ring record; ``round=`` also makes this span and everything inside it
    belong to that training round."""
    return Span(name, args)


def wait_span(name: str, **args: Any) -> Span:
    """:func:`span` for a named wait (see :class:`_WaitSpan`): the device, a
    copy from it, an evaluation.  :func:`round_account` knows them by name
    (:func:`_is_wait`)."""
    return _WaitSpan(name, args)


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


# an account's sums over the loop's top-level spans: its key, their argument
_TOP_COUNTERS = {
    "compiled": "compiled", "loaded": "loaded", "traced": "traced",
    "session_edge": "session_edge",
    "cpu_ns": "cpu_ns", "proc_cpu_ns": "proc_cpu_ns",
    "ctx_invol": "ctx_invol", "majflt": "majflt", "gc_ns": "gc.ns",
    "gc_collections": "gc.collections", "gc_gen2": "gc.gen2"}


def _is_wait(name: str) -> bool:
    """The spans in which the training thread sits in a wait that has a
    name: for the device, for a copy from it, for an evaluation
    (:func:`wait_span` opens them)."""
    return name in ("grow.wait_device", "grow.to_host") or name.startswith("eval.")


def round_account(round_from: Optional[int] = None,
                  thread: Optional[int] = None) -> List[Dict[str, Any]]:
    """One entry for each whole round of :func:`recent` whose *period* is
    over, oldest first, of the spans that ``thread`` wrote (an ident; the
    calling thread's by default: the ring is the process's, and in-process
    workers each run a loop).  A round's period runs from its
    ``train.round``'s opening to the next one's; the newest round's to the
    end of its last top-level span, and a newest round that has no
    ``train.after_iteration`` yet is left out.  An entry:

    - ``round``, ``period_ns``;
    - ``self_ns``: {span name: its time less its child spans' time}, and
      ``gap_ns``, what lies between the round's top-level spans:
      ``sum(self_ns.values()) + gap_ns == period_ns`` to the nanosecond (the
      arithmetic is ``xplane.self_time``'s, as on a profile);
    - ``host_gc``: the ``host.gc`` records that began in the period (they
      overlap the span they interrupted, so they stand beside the partition);
    - summed over the loop's top-level spans (``train.round``,
      ``train.after_iteration``, ``train.boundary``; ``compile.counting``
      sets them): ``compiled``, ``loaded``, ``traced``, ``session_edge``
      (``warm``: any of the four, such a round is never called slow),
      ``cpu_ns``, ``proc_cpu_ns``, ``ctx_invol``, ``majflt``, ``gc_ns``,
      ``gc_collections``, ``gc_gen2``: None where the spans carry no such
      counter;
    - ``waited_ns``: self time of the named waits (``grow.wait_device``,
      ``grow.to_host``, ``eval.*``), and ``waited_cpu_ns``, the thread's CPU
      time inside them (their ``cpu_ns``: a wait spins before it sleeps);
      ``offcpu_ns``: ``(period_ns - waited_ns) - (cpu_ns - waited_cpu_ns)``,
      the thread off the CPU in code that names no wait.  The thread's clock
      may tick coarsely (10 ms on some hosts), so over one round the
      remainder is good to a tick or two and can read below nought;
      ``unnamed_ns``: self time of :data:`CONTAINERS` plus ``gap_ns``.
    """
    me = threading.get_ident() if thread is None else thread
    records = [r for r in recent(round_from=round_from)
               if r.get("tid", me) == me]
    opened = sorted((r for r in records if r["name"] == "train.round"),
                    key=lambda r: r["t0_ns"])
    out: List[Dict[str, Any]] = []
    for k, rnd in enumerate(opened):
        start = rnd["t0_ns"]
        inside = [r for r in records if r["t0_ns"] >= start]
        if k + 1 < len(opened):
            end = opened[k + 1]["t0_ns"]
        elif any(r["name"] == "train.after_iteration" for r in inside):
            end = max(r["t0_ns"] + r["dur_ns"] for r in inside
                      if "parent" not in r and r["name"] != GC_SPAN)
        else:
            continue
        inside = [r for r in inside if r["t0_ns"] < end]
        mine = [r for r in inside if r["name"] != GC_SPAN]
        period = "(period)"  # the root that the top-level spans lie in
        own = {name: t[2] for name, t in self_time(
            [(period, start, end - start)]
            + [(r["name"], r["t0_ns"], r["dur_ns"]) for r in mine]).items()}
        acct: Dict[str, Any] = {
            "round": rnd["round"], "period_ns": end - start,
            "gap_ns": own.pop(period), "self_ns": own,
            "host_gc": [r for r in inside if r["name"] == GC_SPAN]}
        tops = [r for r in mine if "parent" not in r]
        for key, arg in _TOP_COUNTERS.items():
            held = [r[arg] for r in tops if arg in r]
            acct[key] = sum(held) if held else None
        acct["warm"] = any(acct[kind] for kind in WARM)
        acct["waited_ns"] = sum(ns for name, ns in own.items()
                                if _is_wait(name))
        # a wait inside a wait (eval.predict in eval.eval_set) is in the
        # outer one's clock already
        acct["waited_cpu_ns"] = sum(
            r.get("cpu_ns", 0) for r in mine
            if _is_wait(r["name"]) and not _is_wait(r.get("parent", "")))
        acct["offcpu_ns"] = (None if acct["cpu_ns"] is None else
                             acct["period_ns"] - acct["waited_ns"]
                             - acct["cpu_ns"] + acct["waited_cpu_ns"])
        acct["unnamed_ns"] = acct["gap_ns"] + sum(
            own.get(name, 0) for name in CONTAINERS)
        out.append(acct)
    return out


def phase_totals() -> Dict[str, Dict[str, float]]:
    """{phase: {"count": n, "seconds": s}} accumulated so far while the flag
    was on (render_prometheus() has the full histogram)."""
    hist = get_registry().get(PHASE_HISTOGRAM)
    if hist is None:
        return {}
    return {values[0]: {"count": c, "seconds": s}
            for values, (c, s) in hist.snapshot_sums().items()}
