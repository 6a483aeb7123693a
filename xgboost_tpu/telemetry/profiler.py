"""Always-on sampling wall profiler: folded stacks from every process.

Role model: the Linux `perf` + FlameGraph collapsed-stack workflow
(Gregg's `stackcollapse` format: one line per unique stack,
``frame;frame;frame count``), built from pure-Python wall sampling so it
works identically in the driver, fleet replicas, tracker relays, and
training workers — no ptrace, no signals, no native unwinder.

A single daemon thread wakes ``XGBOOST_TPU_PROF_HZ`` times per second
(default :data:`DEFAULT_HZ`; ``0`` disables) and snapshots every live
thread's Python stack via ``sys._current_frames()``.  Each observed
stack folds into an in-memory ``{stack_key: count}`` dict whose keys are
root-first ``thread;module:func;module:func;...`` strings.  At a few Hz
the cost is a handful of microseconds per tick — the BENCH_OBS ≤5%
overhead gate runs with the profiler armed (scripts/bench_obs.py), and
training output is bitwise-identical with the profiler on or off
(tests/test_profiler.py) because sampling only ever *reads* frames.

Shipping rides the existing telemetry channels:
:func:`~xgboost_tpu.telemetry.distributed.snapshot_payload` attaches
:func:`folded_snapshot` under the ``"profile"`` key, so fleet replicas
(wire ``op="telemetry"`` frames) and tracker-mode workers (``cmd=
"telemetry"``) deliver their folded stacks to the driver without new
sockets.  The driver merges them — each stack prefixed with its source
label — into one flame view: :func:`merged_folded` returns the combined
dict, :func:`render_folded` writes the collapsed-stack file any
FlameGraph tool consumes plus a human-readable top-stacks text.

The sampler is also a witness of what no span can bracket.  It needs the
interpreter lock to wake, so **how late a tick woke** is how long somebody
held the lock (a collection of the oldest generation, a C call that kept
it) or how long the whole process did not run.  Beside the folded counts it
keeps its last :data:`_MAX_TICKS` ticks (:func:`ticks`): the tick's time on
the spans' clock, its lateness, and the innermost frames of the thread that
last armed the sampler (``xtb.train``'s); a slow round's one log line
(pauses.py) quotes the ticks that fell into it.

Clock discipline: pacing uses ``time.monotonic`` deadlines only
(xtblint XTB501 — no wall clock anywhere in the sampler).
"""
from __future__ import annotations

import collections
import os
import sys
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple

from .registry import get_registry

__all__ = [
    "ENV_HZ", "DEFAULT_HZ", "configured_hz", "start", "maybe_start",
    "stop", "running", "samples", "ticks", "folded_snapshot",
    "merged_folded", "render_folded", "clear",
]

ENV_HZ = "XGBOOST_TPU_PROF_HZ"
DEFAULT_HZ = 5.0      # a few Hz: ~200ms between ticks, invisible in walls
_MAX_DEPTH = 64       # frames kept per stack (deepest dropped beyond this)
_MAX_STACKS = 4096    # distinct folded keys kept (overflow folds to a bin)
_OVERFLOW_KEY = "overflow;stacks_truncated"
_MAX_TICKS = 64       # last ticks kept: 13 s at the default rate
_TICK_FRAMES = 5      # innermost frames of the watched thread kept a tick

_lock = threading.Lock()
_thread: Optional[threading.Thread] = None
_stop_evt: Optional[threading.Event] = None
_hz = 0.0
_label = ""
_samples = 0
_stacks: Dict[str, int] = {}
_watched = 0  # ident of the thread that armed the sampler last
# (perf_counter_ns as the tick woke, ns past its deadline, watched frames)
_ticks: Deque[Tuple[int, int, Tuple[str, ...]]] = collections.deque(
    maxlen=_MAX_TICKS)


def _after_fork_child() -> None:
    # the sampler thread does not survive fork; drop the handle so the
    # child's next maybe_start() spins up its own (counts reset with the
    # fresh interpreter state the fork copied)
    global _thread, _stop_evt
    _lock.release()
    _thread = None
    _stop_evt = None


if hasattr(os, "register_at_fork"):  # pragma: no branch
    # hold the fold lock across fork so a child never inherits it locked
    os.register_at_fork(before=_lock.acquire,
                        after_in_parent=_lock.release,
                        after_in_child=_after_fork_child)


def configured_hz() -> float:
    """The env-configured sampling rate; unset/invalid -> DEFAULT_HZ."""
    raw = os.environ.get(ENV_HZ, "").strip()
    if not raw:
        return DEFAULT_HZ
    try:
        v = float(raw)
    except ValueError:
        return DEFAULT_HZ
    return max(0.0, v)


def _samples_counter():
    return get_registry().counter(
        "xtb_prof_samples_total",
        "Sampling-profiler ticks taken by this process")


def _frame_entry(code) -> str:
    base = os.path.basename(code.co_filename)
    if base.endswith(".py"):
        base = base[:-3]
    return f"{base}:{code.co_name}"


def _sample_once(own_ident: int) -> Tuple[List[str], Tuple[str, ...]]:
    """One tick: every live thread's stack as a folded key (root-first),
    excluding the sampler's own thread, and the innermost frames of the
    watched thread, innermost first."""
    names = {t.ident: t.name for t in threading.enumerate()}
    keys: List[str] = []
    inner: Tuple[str, ...] = ()
    for ident, frame in sys._current_frames().items():
        if ident == own_ident:
            continue
        parts: List[str] = []
        f = frame
        while f is not None and len(parts) < _MAX_DEPTH:
            parts.append(_frame_entry(f.f_code))
            f = f.f_back
        if ident == _watched:
            inner = tuple(parts[:_TICK_FRAMES])
        parts.reverse()
        thread = names.get(ident) or f"tid-{ident}"
        keys.append(thread + ";" + ";".join(parts))
    return keys, inner


def _run(stop_evt: threading.Event, period: float) -> None:
    global _samples
    counter = _samples_counter()
    own = threading.get_ident()
    next_t = time.monotonic() + period
    while not stop_evt.is_set():
        delay = next_t - time.monotonic()
        if delay > 0:
            if stop_evt.wait(delay):
                break
        else:
            # fell behind (suspended / heavily loaded): skip missed ticks
            # instead of bursting to catch up
            next_t = time.monotonic()
        # past the deadline by this much: the lock was held or the process
        # did not run (a tick that fell behind by whole periods reads 0 and
        # the one before it the stall)
        late_ns = max(0, int((time.monotonic() - next_t) * 1e9))
        woke_ns = time.perf_counter_ns()
        next_t += period
        try:
            keys, inner = _sample_once(own)
        except Exception:
            continue  # a racing thread teardown must not kill the sampler
        with _lock:
            _samples += 1
            _ticks.append((woke_ns, late_ns, inner))
            for k in keys:
                if k in _stacks:
                    _stacks[k] += 1
                elif len(_stacks) < _MAX_STACKS:
                    _stacks[k] = 1
                else:
                    _stacks[_OVERFLOW_KEY] = _stacks.get(_OVERFLOW_KEY,
                                                         0) + 1
        counter.inc()


def start(hz: Optional[float] = None, label: str = "") -> bool:
    """Start the sampler (idempotent).  ``hz=None`` reads the env knob;
    ``hz<=0`` is a no-op returning False.  A second ``start`` while
    running just returns True — the first rate wins until :func:`stop`."""
    global _thread, _stop_evt, _hz, _label, _watched
    rate = configured_hz() if hz is None else max(0.0, float(hz))
    if rate <= 0.0:
        return False
    with _lock:
        _watched = threading.get_ident()
        if _thread is not None and _thread.is_alive():
            if label:
                _label = str(label)
            return True
        _hz = rate
        if label:
            _label = str(label)
        _stop_evt = threading.Event()
        _thread = threading.Thread(
            target=_run, args=(_stop_evt, 1.0 / rate), daemon=True,
            name="xtb-prof-sampler")
        _thread.start()
    return True


def maybe_start(label: str = "") -> bool:
    """The default-on entry point every long-lived loop calls (training
    rounds, fleet dispatcher, replica serve loop, tracker relay): starts
    at the env-configured rate unless disabled (``XGBOOST_TPU_PROF_HZ=0``)."""
    return start(None, label)


def stop(timeout: float = 2.0) -> None:
    """Stop the sampler (idempotent); accumulated stacks are kept."""
    global _thread, _stop_evt
    with _lock:
        t, evt = _thread, _stop_evt
        _thread, _stop_evt = None, None
    if evt is not None:
        evt.set()
    if t is not None and t.is_alive():
        t.join(timeout=timeout)


def running() -> bool:
    with _lock:
        return _thread is not None and _thread.is_alive()


def samples() -> int:
    with _lock:
        return _samples


def clear() -> None:
    """Drop accumulated stacks/counts (tests; the sampler keeps running)."""
    global _samples
    with _lock:
        _samples = 0
        _stacks.clear()
        _ticks.clear()


def ticks(since_ns: int = 0, until_ns: Optional[int] = None
          ) -> List[Tuple[int, int, Tuple[str, ...]]]:
    """The last ticks (at most :data:`_MAX_TICKS`), oldest first, each
    ``(woke_ns, late_ns, frames)``: ``time.perf_counter_ns()`` as the tick
    woke (the spans' clock), the nanoseconds it woke past its deadline, and
    the innermost frames (``module:function``, innermost first) of the
    thread that last armed the sampler.  ``since_ns`` / ``until_ns`` keep
    the ticks that woke in that stretch."""
    with _lock:
        kept = list(_ticks)
    return [t for t in kept if t[0] >= since_ns
            and (until_ns is None or t[0] < until_ns)]


def folded_snapshot() -> Optional[dict]:
    """This process's profile as a JSON-serializable dict, or None when
    nothing was ever sampled (keeps idle payloads small).  Counts are
    cumulative since process start — receivers keep the latest snapshot
    per source, so re-ships overwrite rather than double-count."""
    with _lock:
        if _samples == 0 and not _stacks:
            return None
        return {"pid": os.getpid(), "label": _label, "hz": _hz,
                "samples": _samples, "stacks": dict(_stacks)}


# ---------------------------------------------------------------------------
# Driver-side merged flame view
# ---------------------------------------------------------------------------


def _source_tag(source: str, prof: dict) -> str:
    pid = prof.get("pid")
    return f"{source}/{pid}" if pid is not None else str(source)


def merged_folded(include_local: bool = True,
                  local_source: str = "driver") -> Dict[str, int]:
    """One folded-stack dict across every shipped profile plus (by
    default) this process's own: keys are ``source/pid;thread;frames...``
    so one flame graph separates processes at the root."""
    from . import distributed

    out: Dict[str, int] = {}
    rows: List[Tuple[str, dict]] = list(
        distributed.get_merged().profiles().items())
    if include_local:
        local = folded_snapshot()
        if local:
            rows.append((local_source, local))
    for source, prof in rows:
        if not isinstance(prof, dict):
            continue
        tag = _source_tag(source, prof)
        for stack, count in (prof.get("stacks") or {}).items():
            key = f"{tag};{stack}"
            out[key] = out.get(key, 0) + int(count)
    return out


def render_folded(path: Optional[str] = None, include_local: bool = True,
                  top: int = 20) -> str:
    """Render the merged flame view.  Returns a text report whose first
    section lists the ``top`` hottest stacks (count + leaf-to-root
    abbreviated) and whose second section is the raw collapsed-stack
    lines (``stack count``) — the exact stackcollapse format FlameGraph
    tools take.  ``path`` additionally writes just the collapsed lines
    to a file."""
    folded = merged_folded(include_local=include_local)
    ordered = sorted(folded.items(), key=lambda kv: (-kv[1], kv[0]))
    collapsed = "\n".join(f"{stack} {count}" for stack, count in ordered)
    if path is not None:
        with open(path, "w") as fh:
            fh.write(collapsed + ("\n" if collapsed else ""))
    total = sum(folded.values())
    lines = [f"# merged profile: {len(folded)} stacks, "
             f"{total} weighted samples"]
    for stack, count in ordered[:max(0, top)]:
        frames = stack.split(";")
        head = ";".join(frames[:2])          # source/pid;thread
        leaf = ";".join(frames[-3:]) if len(frames) > 5 else ";".join(
            frames[2:])
        pct = 100.0 * count / total if total else 0.0
        lines.append(f"{count:8d} {pct:5.1f}%  {head};...;{leaf}")
    lines.append("")
    lines.append(collapsed)
    return "\n".join(lines)
