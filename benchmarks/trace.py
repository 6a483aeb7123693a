"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy union, device time by ``XLA Modules`` name,
the operations that took most time, and the longest idle gaps.

The arithmetic works on plain ``(name, start_ns, duration_ns)`` tuples so
that it can be checked on a hand-built trace; ``load`` turns a profile file
into those with nothing but JAX.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def busy_union_ns(events: Iterable[Event]) -> float:
    """Nanoseconds covered by at least one event (nested and overlapping
    events count once)."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    total, end = 0.0, None
    for s, e in spans:
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def short(name: str) -> str:
    """A program's name without its numeric suffix (``jit_level_step(12)``),
    an operation's HLO text cut to its name and result (``fusion.11
    s16[10500096]``)."""
    hlo = re.match(r"%?(\S+) = (\(?[a-z0-9]+\[[0-9,]*\])", name)
    if hlo:
        result = hlo.group(2)
        return f"{hlo.group(1)} {'(tuple)' if result[0] == '(' else result}"
    return re.sub(r"\(\d+\)$", "", name)[:120]


def time_by_name(events: Iterable[Event]) -> Dict[str, float]:
    """Summed duration in seconds by ``short`` event name.  An operation
    that nests others (a ``while`` and its body) counts its whole span, so
    the list is not a partition of the busy time."""
    out: Dict[str, float] = {}
    for name, _, d in events:
        key = short(name)
        out[key] = out.get(key, 0.0) + d * 1e-9
    return out


def idle_gaps(events: Iterable[Event], start_ns: float, end_ns: float,
              labels: Optional[Iterable[Event]] = None) -> List[Tuple[str, float]]:
    """Gaps in which no event runs, each named by the labelled event (a
    module) that ended last before it, summed by that name, in seconds."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    marks = sorted((s + d, short(n)) for n, s, d in (labels or []))
    gaps: List[Tuple[float, float]] = []
    cur = start_ns
    for s, e in spans:
        if s > cur:
            gaps.append((cur, min(s, end_ns)))
        cur = max(cur, e)
    if end_ns > cur:
        gaps.append((cur, end_ns))
    out: Dict[str, float] = {}
    j, last = 0, "window start"
    for g0, g1 in gaps:
        while j < len(marks) and marks[j][0] <= g0:
            last = marks[j][1]
            j += 1
        key = f"after {last}"
        out[key] = out.get(key, 0.0) + max(g1 - g0, 0.0) * 1e-9
    return sorted(out.items(), key=lambda kv: -kv[1])


def reduce_planes(planes: Dict[str, Dict[str, List[Event]]],
                  window_s: float) -> Optional[dict]:
    """``planes`` maps a device plane's name to its lines' events.  Returns
    None where no operation ran on any device plane."""
    busy, modules, ops, gaps = [], {}, {}, {}
    for lines in planes.values():
        op_events = lines.get(OPS_LINE, [])
        if not op_events:
            continue
        start = min(s for _, s, _ in op_events)
        end = max(s + d for _, s, d in op_events)
        busy.append(busy_union_ns(op_events) * 1e-9)
        for k, v in time_by_name(lines.get(MODULES_LINE, [])).items():
            modules[k] = modules.get(k, 0.0) + v
        for k, v in time_by_name(op_events).items():
            ops[k] = ops.get(k, 0.0) + v
        for k, v in idle_gaps(op_events, start, end,
                              lines.get(MODULES_LINE, [])):
            gaps[k] = gaps.get(k, 0.0) + v
    if not busy:
        return None
    n = len(busy)

    def top(d: Dict[str, float]) -> list:
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"busy_s": sum(busy) / n, "window_s": window_s, "chips": n,
            "module_s": {k: v / n for k, v in modules.items()},
            "device_ops": top(ops), "idle_gaps": top(gaps)}


def load(trace_dir: str) -> Dict[str, Dict[str, List[Event]]]:
    """Device planes of the newest profile under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        planes[plane.name] = {
            line.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
            for line in plane.lines}
    return planes
