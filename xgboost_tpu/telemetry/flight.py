"""Flight recorder: a fixed-size in-process ring of recent spans, events,
and faults — the postmortem channel that works WITHOUT tracing enabled.

The JSONL trace answers "where did the time go" but costs a file per
process and must be switched on before the run; the flight recorder
answers "what was this process doing right before it died" and is always
armed: a bounded ``collections.deque`` of small dicts that coarse seams
append to unconditionally (round boundaries, control ops, injected
faults, replica/worker deaths) and that enabled spans also feed, so a
crash dump shows the last few hundred things the process did.

Three exits for the ring:

- **dump(path)** — one-shot JSON file (atomic tmp+rename).  ``install()``
  registers it atexit and the launcher/replica crash paths call it
  explicitly, so an exception death leaves a dump.
- **periodic spill** — ``install()`` arms a cheap time-gated spill inside
  :func:`record` (default every ``XGBOOST_TPU_FLIGHT_SPILL_S`` = 5s), so
  even a SIGKILL'd process leaves a recent-past dump on disk.
- **shipping** — fleet replicas and tracker-mode training workers ship
  ``events()`` alongside their registry snapshots
  (telemetry/distributed.py); the driver retains the last ring per
  process and dumps it when the process dies, which is how a SIGKILL'd
  replica's final moments survive driver-side.

Timestamps are ``time.monotonic()`` (the repo's nondeterminism lint bans
wall-clock reads in library code); every dump carries a wall-clock anchor
pair (``wall_at_dump`` ISO-8601 + ``mono_at_dump``) so consumers can
reconstruct absolute times.

Dump location: ``XGBOOST_TPU_FLIGHT_DIR`` (default
``<tmp>/xtb_flight``), file ``flight_<label>.json`` where the label comes
from :func:`install`/``XGBOOST_TPU_FLIGHT_LABEL`` (the launcher sets it
per worker) and falls back to ``pid<pid>``.
"""
from __future__ import annotations

import atexit
import json
import os
import tempfile
import threading
import time
from collections import deque
from datetime import datetime, timezone
from typing import Any, Dict, List, Optional

__all__ = ["record", "events", "seq", "dump", "dump_stacks", "install",
           "dump_dir", "default_path", "stacks_path", "set_label", "clear",
           "ENV_DIR", "ENV_LABEL", "ENV_SIZE", "ENV_SPILL"]

ENV_DIR = "XGBOOST_TPU_FLIGHT_DIR"
ENV_LABEL = "XGBOOST_TPU_FLIGHT_LABEL"
ENV_SIZE = "XGBOOST_TPU_FLIGHT_SIZE"
ENV_SPILL = "XGBOOST_TPU_FLIGHT_SPILL_S"


# Whole rounds of a ten-second window have to fit the ring for the
# benchmark's program_span metrics to read (telemetry/spans.py recent): at 22
# records a round, 512 held 23 rounds, and a depth-6 round is 0.52 s since
# PR 37 (18 untraced rounds of a traced window: 77% of 512).
_DEFAULT_SIZE = 1024


def _ring_size() -> int:
    try:
        return max(16, int(os.environ.get(ENV_SIZE, _DEFAULT_SIZE)))
    except ValueError:
        return _DEFAULT_SIZE


_lock = threading.Lock()
_ring: "deque[Dict[str, Any]]" = deque(maxlen=_ring_size())
_seq = 0  # records appended since process start; a record's "seq" is its own
_label: Optional[str] = os.environ.get(ENV_LABEL) or None
_spill_path: Optional[str] = None
_spill_interval: float = 5.0
_last_spill: float = 0.0
_installed = False


def dump_dir() -> str:
    d = os.environ.get(ENV_DIR) or os.path.join(tempfile.gettempdir(),
                                                "xtb_flight")
    os.makedirs(d, exist_ok=True)
    return d


def _resolved_label() -> str:
    return _label or os.environ.get(ENV_LABEL) or f"pid{os.getpid()}"


def default_path(label: Optional[str] = None) -> str:
    return os.path.join(dump_dir(),
                        f"flight_{label or _resolved_label()}.json")


def set_label(label: str) -> None:
    global _label
    _label = str(label)


def record(kind: str, name: str, **detail: Any) -> None:
    """Append one event to the ring; never raises (observability must not
    take the process down).  ``kind`` is one of ``span``/``event``/
    ``fault`` by convention; ``detail`` must be JSON-serializable."""
    global _seq
    try:
        rec: Dict[str, Any] = {"t_mono": time.monotonic(), "kind": kind,
                               "name": name}
        if detail:
            rec["detail"] = detail
        with _lock:
            rec["seq"] = _seq
            _seq += 1
            _ring.append(rec)
        if _spill_path is not None:
            _maybe_spill()
    except Exception:  # pragma: no cover - defensive
        pass


def events() -> List[Dict[str, Any]]:
    with _lock:
        return list(_ring)


def seq() -> int:
    """The sequence number the next record will get.  Records are numbered
    in the order they were appended, so ``events()[0]["seq"]`` tells how much
    the ring has dropped (spans.recent reads whole rounds by it)."""
    return _seq


def _payload(evs: List[Dict[str, Any]]) -> Dict[str, Any]:
    return {
        "label": _resolved_label(),
        "pid": os.getpid(),
        "wall_at_dump": datetime.now(timezone.utc).isoformat(),
        "mono_at_dump": time.monotonic(),
        "events": evs,
    }


def _write(path: str, evs: List[Dict[str, Any]]) -> str:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(_payload(evs), fh)
    os.replace(tmp, path)
    return path


def dump(path: Optional[str] = None) -> str:
    """Write the ring to ``path`` (default :func:`default_path`)
    atomically; returns the path.  Safe to call repeatedly — each call
    replaces the file with the current ring."""
    return _write(path or default_path(), events())


def stacks_path(label: Optional[str] = None) -> str:
    """Where :func:`dump_stacks` writes for ``label`` (same directory and
    labeling scheme as the ring dump, so a postmortem finds both)."""
    return os.path.join(dump_dir(),
                        f"stacks_{label or _resolved_label()}.txt")


def dump_stacks(path: Optional[str] = None) -> Optional[str]:
    """``faulthandler.dump_traceback`` of ALL threads into the flight
    directory (append — successive dumps of one process stay in order,
    separated by a monotonic-stamped header line).  The crash/abort path
    of every spawned process and the watchdog's dump stage both land
    here, so "what was every thread doing" survives without a debugger
    attached.  Returns the path, or None when the write failed — stack
    dumping must never take the dying process down faster."""
    import faulthandler

    path = path or stacks_path()
    try:
        with open(path, "a", encoding="utf-8") as fh:
            fh.write(f"=== stacks pid={os.getpid()} "
                     f"label={_resolved_label()} "
                     f"mono={time.monotonic():.3f} ===\n")
            fh.flush()
            faulthandler.dump_traceback(file=fh, all_threads=True)
            fh.write("\n")
        record("event", "flight.stacks", path=path)
        return path
    except Exception:  # pragma: no cover - fs trouble on the death path
        return None


def _maybe_spill() -> None:
    global _last_spill
    now = time.monotonic()
    if now - _last_spill < _spill_interval:
        return
    _last_spill = now
    try:
        dump(_spill_path)
    except OSError:  # pragma: no cover - fs trouble must not kill the app
        pass


def install(label: Optional[str] = None,
            spill_interval_s: Optional[float] = None) -> str:
    """Arm this process's recorder: set the dump label, enable the
    periodic spill, and register an atexit dump.  Returns the dump path.
    Idempotent (the launcher child stub and the replica both call it)."""
    global _spill_path, _spill_interval, _installed
    if label:
        set_label(label)
    if spill_interval_s is None:
        try:
            spill_interval_s = float(os.environ.get(ENV_SPILL, "5.0"))
        except ValueError:
            spill_interval_s = 5.0
    path = default_path()
    with _lock:
        _spill_path = path
        _spill_interval = max(0.1, float(spill_interval_s))
        first = not _installed
        _installed = True
    if first:
        atexit.register(_atexit_dump)
    return path


def _atexit_dump() -> None:  # pragma: no cover - interpreter teardown
    # bounded acquire (XTB903): a recorder wedged on the ring lock must
    # not hang shutdown; an unlocked best-effort snapshot beats no dump
    # at all on the death path
    try:
        if _lock.acquire(timeout=1.0):
            try:
                evs = list(_ring)
            finally:
                _lock.release()
        else:
            evs = list(_ring)
        _write(_spill_path or default_path(), evs)
    except Exception:
        pass


def clear() -> None:
    """Drop every buffered event (test isolation)."""
    with _lock:
        _ring.clear()
