"""Pauses that no ``with`` can bracket, and the slow round that says why.

A round's *period* (from one ``train.round``'s opening to the next one's) is
tiled by the loop's top-level spans; what stops the training thread inside
them without being a span of its own is kept here, with no switch:

- **the cycle collector**, through ``gc.callbacks`` (:func:`install`, once,
  by ``xtb.train``): for every collection two clock reads and three additions
  into running totals (collections, nanoseconds, collections of the oldest
  generation).  A collection of generation 2, or one that took longer than
  :data:`GC_RECORD_NS`, is also a ``host.gc`` span record in the flight ring
  (``generation``, ``collected``, its round); one of generation 2 is a
  ``TraceAnnotation`` too, from its start to its stop, so that it lies on a
  profile's clock under the idle gap it caused (a younger collection's
  length is known only when it is over, too late to open one).  A young
  collection under that length writes nothing.  The hook runs in whatever
  thread's allocation set the collector off, wherever that thread stands
  (inside ``flight.record``, under the ring's lock, as likely as anywhere),
  so it takes no lock: the record waits in a short queue and the loop's
  next top-level span puts it into the ring as it ends (:func:`since`);
- **the thread's and the process's clocks** (:func:`read`, :func:`since`):
  CPU time of the thread and of the process, and where the platform counts
  them the thread's involuntary context switches and major page faults.
  ``compile.counting`` sets their growth, and the collector's, on the
  loop's top-level spans as each ends; with them a period splits into named
  waits, time on the CPU, and neither (``spans.round_account``);
- **the slow round** (:class:`RoundWatch`): the loop's own record of its
  last periods, and one ``train.slow_round`` event and warning line for a
  round that ran long, with what the ring, the collector, the clocks, the
  sampler (``profiler.ticks``) and the device's allocator say of it.
"""
from __future__ import annotations

import collections
import gc
import statistics
import time
from typing import Any, Deque, Dict, List, Optional

import jax.profiler as _profiler

from . import flight, profiler, spans

try:  # the thread's own switches and faults: Linux
    import resource

    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):  # pragma: no cover - other platforms
    resource = None

try:  # whether a profiler session is open: jax.profiler has no public query
    from jax._src.profiler import _profile_state as _session_state
except ImportError:  # pragma: no cover - another JAX
    _session_state = None

__all__ = ["install", "read", "since", "RoundWatch", "GC_RECORD_NS",
           "SLOW_RATIO", "SLOW_LEAST_NS"]

#: A collection of a young generation is a ring record from this length on.
GC_RECORD_NS = 1_000_000
#: A period is slow from this many times the median of the last steady ones
#: and at least :data:`SLOW_LEAST_NS` above it (rounds of a few milliseconds
#: jitter by more than a fifth; the leaf-wise cell's steady rounds, which
#: follow their trees' passes, reach 1.13 times the median of the sixteen
#: before them on the chip: PERF.md §6, PR 36).
SLOW_RATIO = 1.2
SLOW_LEAST_NS = 20_000_000
_KEPT_PERIODS = 16
_STEADY_BEHIND = 2  # steady periods a round needs behind it to be judged
# what the slow round's event and line repeat of its account
_SAID = ("gap_ns", "waited_ns", "waited_cpu_ns", "cpu_ns", "proc_cpu_ns",
         "offcpu_ns", "ctx_invol", "majflt", "gc_ns", "gc_collections",
         "gc_gen2")

_gc = [0, 0, 0]  # collections, nanoseconds, collections of generation 2
# host.gc records that the hook could not write itself (it may run under the
# ring's lock): since() writes them.  Bounded: outside training nobody does
_gc_records: Deque[Dict[str, int]] = collections.deque(maxlen=64)
_gc_t0 = 0
_gc_ann = None
_installed = False


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_t0, _gc_ann
    if phase == "start":
        if info["generation"] == 2:
            _gc_ann = _profiler.TraceAnnotation(spans.GC_SPAN, generation=2)
            _gc_ann.__enter__()
        _gc_t0 = time.perf_counter_ns()
        return
    dur = time.perf_counter_ns() - _gc_t0
    old = info["generation"] == 2
    _gc[0] += 1
    _gc[1] += dur
    _gc[2] += old
    if old or dur > GC_RECORD_NS:
        if _gc_ann is not None:
            _gc_ann.__exit__(None, None, None)
            _gc_ann = None
        rec = {"generation": info["generation"],
               "collected": info["collected"], "t0_ns": _gc_t0, "dur_ns": dur}
        round_ = spans.current_round()
        if round_ is not None:
            rec["round"] = round_
        _gc_records.append(rec)  # no lock here: since() writes it


def install() -> None:
    """Hook the collector (idempotent; nothing removes it: the callback
    costs two clock reads a collection)."""
    global _installed
    if not _installed:
        _installed = True
        gc.callbacks.append(_on_gc)


def read() -> tuple:
    """The clocks, the collector's totals and whether a profiler session is
    open, now; :func:`since` takes it."""
    if resource is None:
        switches = faults = None
    else:
        usage = resource.getrusage(_RUSAGE_THREAD)
        switches, faults = usage.ru_nivcsw, usage.ru_majflt
    return (time.thread_time_ns(), time.process_time_ns(), switches, faults,
            _gc[0], _gc[1], _gc[2],
            getattr(_session_state, "profile_session", None) is not None)


def since(before: tuple) -> Dict[str, int]:
    """What grew since ``before`` (a :func:`read`), under the names a span
    carries it by: ``cpu_ns`` (this thread on the CPU), ``proc_cpu_ns`` (all
    threads), ``ctx_invol`` and ``majflt`` (this thread descheduled against
    its will, and waiting for a page from disk: absent where the platform
    does not count them), ``gc.collections``, ``gc.ns``, ``gc.gen2``, and
    ``session_edge`` where a profiler session began or ended meanwhile (its
    start and its stop block for seconds: such a round is never called
    slow).  Also writes the ``host.gc`` records that wait into the ring."""
    while _gc_records:
        flight.record("span", spans.GC_SPAN, **_gc_records.popleft())
    now = read()
    out = {"cpu_ns": now[0] - before[0], "proc_cpu_ns": now[1] - before[1],
           "gc.collections": now[4] - before[4], "gc.ns": now[5] - before[5],
           "gc.gen2": now[6] - before[6]}
    if now[2] is not None:
        out["ctx_invol"] = now[2] - before[2]
        out["majflt"] = now[3] - before[3]
    if now[7] != before[7]:
        out["session_edge"] = 1
    return out


def _slow_from(median: int) -> float:
    """The period from which a round is slow, by the steady ones' median."""
    return max(SLOW_RATIO * median, median + SLOW_LEAST_NS)


def _ms(ns: Optional[int]) -> str:
    return "n/a" if ns is None else f"{ns / 1e6:.1f}"


class RoundWatch:
    """What ``training.py``'s loop keeps of its rounds.  The loop hands it
    its three top-level spans (:meth:`top`) and says when a round has opened
    (:meth:`opened`), which closes the period of the round before it: one
    subtraction, and one append if that round neither compiled, loaded nor
    traced.  Only a period over :data:`SLOW_RATIO` times the median of the
    last steady ones (and :data:`SLOW_LEAST_NS` above it), with at least two
    of them behind it, costs more: the account of the ring's rounds, once,
    which the loop asks for at its next boundary (:meth:`tell`), outside
    ``train.round``."""

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        """Forget the open round and the periods (a regroup: another world)."""
        self.periods: Deque[int] = collections.deque(maxlen=_KEPT_PERIODS)
        self.tops: List[spans.Span] = []  # the open period's top-level spans
        self.round: Optional[int] = None
        self.t0 = 0
        # a slow round not told yet: (round, its opening, period, median)
        self.slow: Optional[tuple] = None

    def top(self, sp: spans.Span) -> spans.Span:
        """``sp`` is a top-level span of the loop, about to begin."""
        self.tops.append(sp)
        return sp

    def opened(self, sp: spans.Span) -> None:
        """The round span ``sp`` (the last handed to :meth:`top`) has begun:
        the period before it is over."""
        closing, self.tops = self.tops[:-1], [sp]
        self._close(closing, sp.t0)
        self.round, self.t0 = sp.args["round"], sp.t0

    def finished(self) -> None:
        """The loop is over: the last round's period ends with its last
        top-level span."""
        self.tell()
        if self.tops:
            last = self.tops[-1]
            self._close(self.tops, last.t0 + last.dur)
            self.tell()
        self.tops, self.round = [], None

    def _close(self, tops: List[spans.Span], end_ns: int) -> None:
        if self.round is None:
            return
        period = end_ns - self.t0
        if any(sp.args.get(kind) for sp in tops for kind in spans.WARM):
            return  # a warm round is never called slow, nor kept
        kept = self.periods
        if len(kept) >= _STEADY_BEHIND:
            median = int(statistics.median(kept))
            if period > _slow_from(median):
                self.slow = (self.round, self.t0, period, median)
        kept.append(period)

    def tell(self) -> None:
        """The one event and the one line of a slow round, if one waits to be
        told; never raises.  The telling takes milliseconds (the account of
        the ring's rounds, the sampler's ticks, the device's allocator), so
        the loop spends them in ``train.boundary``, a round late, and not
        in the ``train.round`` that the slow one's end opened."""
        if self.slow is None:
            return
        (round_, t0, period, median), self.slow = self.slow, None
        try:
            fields = self._explain(round_, t0, period, median)
        except Exception as exc:  # observability must not stop training
            fields = {"round": round_, "period_ns": period,
                      "median_ns": median, "error": repr(exc)}
        flight.record("event", "train.slow_round", **fields)
        from ..utils import logging

        logging.warning(slow_round_line(fields))

    @staticmethod
    def _explain(round_: int, t0: int, period: int,
                 median: int) -> Dict[str, Any]:
        accounts = spans.round_account(0)
        at = next((k for k in reversed(range(len(accounts)))
                   if accounts[k]["round"] == round_), None)
        mine = None if at is None else accounts[at]
        fields: Dict[str, Any] = {"round": round_, "period_ns": period,
                                  "median_ns": median}
        if mine is not None:
            # the last steady round before it
            base = next((a for a in reversed(accounts[:at])
                         if not a["warm"]
                         and a["period_ns"] <= _slow_from(median)), None)
            before = base["self_ns"] if base else {}
            grew = sorted(((ns - before.get(name, 0), name)
                           for name, ns in mine["self_ns"].items()),
                          reverse=True)
            fields.update(
                {key: mine[key] for key in _SAID},
                against=base["round"] if base else None,
                grew=[[name, excess] for excess, name in grew[:5]
                      if excess > 0],
                host_gc=[[r["generation"], r["dur_ns"]]
                         for r in mine["host_gc"]])
        seen = profiler.ticks(t0, t0 + period)
        fields["ticks"] = len(seen)
        fields["tick_late_ns"] = max((t[1] for t in seen), default=None)
        stacks = collections.Counter("<".join(t[2]) for t in seen if t[2])
        fields["tick_frames"] = [[stack, n] for stack, n
                                 in stacks.most_common(3)]
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        fields["device_memory"] = {k: stats[k] for k in (
            "bytes_in_use", "largest_free_block_bytes", "num_allocs")
            if k in stats}
        return fields


def slow_round_line(f: Dict[str, Any]) -> str:
    """The warning's text from the event's fields (docs/observability.md,
    "A slow round", says how to read it)."""
    if "error" in f:
        return (f"train.slow_round round {f['round']}: period "
                f"{_ms(f['period_ns'])} ms against a median of "
                f"{_ms(f['median_ns'])} ms; no account ({f['error']})")
    grew = ", ".join(f"{name} +{_ms(ns)}" for name, ns in f.get("grew", ()))
    gcs = ", ".join(f"gen{g} {_ms(ns)} ms" for g, ns in f.get("host_gc", ()))
    frames = "; ".join(f"{n}x {stack}" for stack, n in f["tick_frames"])
    memory = ", ".join(f"{k} {v}" for k, v in f["device_memory"].items())
    return (
        f"train.slow_round round {f['round']}: period {_ms(f['period_ns'])} "
        f"ms against a median of {_ms(f['median_ns'])} ms; grew most "
        f"(self ms against round {f.get('against')}): {grew or 'nothing'}; "
        f"gap {_ms(f.get('gap_ns'))} ms; collector {_ms(f.get('gc_ns'))} ms "
        f"in {f.get('gc_collections')} collections, {f.get('gc_gen2')} of "
        f"generation 2{' (' + gcs + ')' if gcs else ''}; cpu "
        f"{_ms(f.get('cpu_ns'))} ms (process {_ms(f.get('proc_cpu_ns'))}) "
        f"and named waits {_ms(f.get('waited_ns'))} ms of the period "
        f"({_ms(f.get('waited_cpu_ns'))} of them on the cpu), off the cpu "
        f"outside them {_ms(f.get('offcpu_ns'))} ms; ctx_invol "
        f"{f.get('ctx_invol')}, majflt {f.get('majflt')}; sampler "
        f"{f['ticks']} ticks, at most {_ms(f['tick_late_ns'])} ms late"
        f"{', saw ' + frames if frames else ''}; device "
        f"{memory or 'memory_stats: none'}")
