"""The program's reader of its own names in a JAX profiler trace.

    with jax.profiler.trace(d):
        xtb.train(params, dtrain, 4)
    python -m xgboost_tpu.telemetry.xplane d

Any profile of the program (this recipe, XProf's capture, the benchmark's
traced rounds) carries two sets of names that the program put there: the
``jax.named_scope`` of every device operation (:data:`SCOPES`) and the
program's spans (spans.py) as host events of the same file, on one clock.
:func:`summarize` reduces a profile by them, with ``jax.profiler.ProfileData``
alone:

- per device plane, seconds by scope (a ``while`` and its body are not
  counted twice; what carries no scope is the row ``(unscoped)``), seconds
  by module, the same split inside each module, and device seconds by the
  span that was live as each run of a module was enqueued;
- host self time by span: a span's duration less what its child spans cover;
- every idle gap of a device longer than :data:`GAP_NS`, put down to the
  innermost span live when the gap opened.

The arithmetic works on plain tuples so that it can be checked on a
hand-built event list (tests/test_xplane.py).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: The device scopes of the training path (docs/observability.md has the table).
SCOPES = ("hist", "split", "record", "route", "queue", "margin", "predict",
          "bin", "gradient")
UNSCOPED = "(unscoped)"
NO_SPAN = "(no span)"
#: An idle gap shorter than this is the device's own turn-around, not the host's.
GAP_NS = 50_000

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: The stat of an ``XLA Ops`` event's metadata that holds its ``op_name``
#: (the ``jax.named_scope`` path): the TPU runtime's name for it.
OP_NAME_STAT = "tf_op"
#: The host event that enqueues one run of a module; it and the module's
#: event on the device carry the same ``run_id``.
ENQUEUE_EVENT = "DoEnqueueProgram"
#: The program's spans are lower-case dotted words (``grow.to_host``); the
#: runtime's own host events (``PjitFunction(f)``, ``X::Y``) and HLO
#: operations (``copy.23``) never are.
SPAN_NAME = re.compile(r"[a-z][a-z0-9_]*(\.[a-z][a-z0-9_+]*)+")

Op = Tuple[str, float, float, str]  # name, start_ns, duration_ns, op_name
Span = Tuple[str, float, float]  # name, start_ns, duration_ns


def scope_of(op_name: str) -> str:
    """The outermost of :data:`SCOPES` on an operation's ``op_name`` path
    (``jit(level_step)/jit(main)/hist/while/body/dot_general`` is ``hist``)."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def seconds_by_scope(ops: Iterable[Op]) -> Dict[str, float]:
    """Device seconds by scope, every nanosecond counted once: an operation
    that nests others (a ``while`` and its body) counts only what its
    children leave.  An operation with no scope of its own takes its
    parent's; a parent with none (XLA gives a ``while`` no ``op_name``)
    takes the scope that most of its children's time has.  What is left
    with none is the row :data:`UNSCOPED`."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [end, scope or None, dur, children_ns, by_child, loose_ns]

    def close() -> None:
        end, scope, dur, children, by_child, loose = stack.pop()
        if scope is None and by_child:
            scope = max(by_child, key=by_child.get)
        own = dur - children + loose
        if scope is not None:
            out[scope] = out.get(scope, 0.0) + own * 1e-9
        if stack:
            parent = stack[-1]
            parent[3] += dur
            if scope is None:
                parent[5] += own  # the parent's scope, once it has one
            else:
                parent[4][scope] = parent[4].get(scope, 0.0) + dur
        elif scope is None:
            out[UNSCOPED] = out.get(UNSCOPED, 0.0) + own * 1e-9

    for _, start, dur, op_name in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= start:
            close()
        scope = scope_of(op_name)
        if scope == UNSCOPED:
            scope = stack[-1][1] if stack else None
        stack.append([start + dur, scope, dur, 0.0, {}, 0.0])
    while stack:
        close()
    return out


def busy_intervals(events: Iterable[Tuple]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint (start, end)."""
    out: List[List[float]] = []
    for s, e in sorted((ev[1], ev[1] + ev[2]) for ev in events if ev[2] > 0):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_time(spans: Iterable[Span]) -> Dict[str, List[float]]:
    """{name: [count, total, self]} of the spans of ONE thread, in the unit
    of their times (integer nanoseconds stay integers, so the self times of
    a span and of everything under it sum to its duration exactly): a span's
    self time is its duration less the part its direct children cover
    (overlapping siblings cover their union once).  ``spans.round_account``
    partitions a round's period with it."""
    out: Dict[str, List[float]] = {}
    stack: List[list] = []  # [name, start, end, children]

    def close(frame) -> None:
        name, s, e, children = frame
        covered = sum(b - a for a, b in busy_intervals(
            (None, max(cs, s), min(ce, e) - max(cs, s)) for cs, ce in children))
        rec = out.setdefault(name, [0, 0, 0])
        rec[0] += 1
        rec[1] += e - s
        rec[2] += e - s - covered

    for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        # a span that outlasts the open one (by over a ns of rounding) is
        # its sibling, not its child
        while stack and (stack[-1][2] <= s or s + d > stack[-1][2] + 1):
            close(stack.pop())
        if stack:
            stack[-1][3].append((s, s + d))
        stack.append([name, s, s + d, []])
    while stack:
        close(stack.pop())
    return out


def self_seconds(spans: Iterable[Span]) -> Dict[str, Dict[str, float]]:
    """:func:`self_time` of spans in nanoseconds, as {name: {"count",
    "total_s", "self_s"}}."""
    return {name: {"count": n, "total_s": total * 1e-9, "self_s": own * 1e-9}
            for name, (n, total, own) in self_time(spans).items()}


def idle_gaps(ops: Iterable[Tuple], start_ns: float, end_ns: float,
              least_ns: float = GAP_NS) -> List[Tuple[float, float]]:
    """(start_ns, duration_ns) of every gap of [start_ns, end_ns) in which no
    operation runs and that lasts at least ``least_ns``."""
    gaps, cur = [], start_ns
    for s, e in busy_intervals(ops):
        if s - cur >= least_ns:
            gaps.append((cur, min(s, end_ns) - cur))
        cur = max(cur, e)
    if end_ns - cur >= least_ns:
        gaps.append((cur, end_ns - cur))
    return gaps


# --- the profile file -------------------------------------------------------
# ``jax.profiler.ProfileData`` shows an event's name, times and own stats, not
# the stats of its XEventMetadata, and that is where the TPU runtime keeps an
# operation's ``op_name`` (stat ``tf_op``).  So the file is read here as what
# it is, a protobuf (tsl/profiler/protobuf/xplane.proto), by field number:
#   XSpace: planes 1.  XPlane: name 2, lines 3, event_metadata 4 (map),
#   stat_metadata 5 (map).  XLine: name 2, timestamp_ns 3, events 4.
#   XEvent: metadata_id 1, offset_ps 2, duration_ps 3, stats 4.
#   XStat: metadata_id 1, double 2, uint64 3, int64 4, str 5, bytes 6, ref 7.
#   XEventMetadata: name 2, display_name 4, stats 5.  XStatMetadata: name 2.


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, pos: int, end: int):
    """(field number, value) of a message's fields: an int for a varint or a
    fixed-width field, (start, end) for a length-delimited one."""
    while pos < end:
        tag, pos = _varint(buf, pos)
        kind = tag & 7
        if kind == 0:
            value, pos = _varint(buf, pos)
        elif kind == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif kind == 1:
            value, pos = int.from_bytes(buf[pos:pos + 8], "little"), pos + 8
        elif kind == 5:
            value, pos = int.from_bytes(buf[pos:pos + 4], "little"), pos + 4
        else:
            raise ValueError(f"wire type {kind} at byte {pos}: not a profile")
        yield tag >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map(buf: bytes, spans: List[Tuple[int, int]]) -> Dict[int, Tuple[int, int]]:
    """A proto map's entries (key 1, value 2) as {key: span of the value}."""
    out = {}
    for a, b in spans:
        entry = dict(_fields(buf, a, b))
        out[entry.get(1, 0)] = entry[2]
    return out


def _stats(buf: bytes, spans: Iterable[Tuple[int, int]],
           stat_names: Dict[int, str]) -> Dict[str, object]:
    """{stat name: value} of XStat messages; a ``ref`` reads as the name it
    refers to, a double is left as its bits (no caller reads one)."""
    out = {}
    for a, b in spans:
        stat = dict(_fields(buf, a, b))
        for field in (3, 4, 5, 6, 7, 2):
            if field in stat:
                value = stat[field]
                if field in (5, 6):
                    value = _text(buf, value)
                elif field == 7:
                    value = stat_names.get(value, "")
                out[stat_names.get(stat.get(1, 0), "")] = value
                break
    return out


def newest_profile(path: str) -> str:
    """``path`` if it is a file (``.xplane.pb``, or that gzipped), else the
    newest ``.xplane.pb`` under it."""
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def _plane(buf: bytes, span: Tuple[int, int]):
    """(name, lines, event metadata, stat names) of an XPlane, undecoded."""
    name, lines, events, stats = "", [], [], []
    for field, value in _fields(buf, *span):
        if field == 2:
            name = _text(buf, value)
        elif field == 3:
            lines.append(value)
        elif field == 4:
            events.append(value)
        elif field == 5:
            stats.append(value)
    return name, lines, events, stats


def _line(buf: bytes, span: Tuple[int, int]):
    """(name, timestamp_ns, [(metadata_id, start_ns, dur_ns, stat spans)])."""
    name, t0_ns, raw = "", 0, []
    for field, value in _fields(buf, *span):
        if field == 2:
            name = _text(buf, value)
        elif field == 3:
            t0_ns = value
        elif field == 4:
            raw.append(value)
    events = []
    for a, b in raw:
        meta = offset = dur = 0
        stat_spans = []
        for field, value in _fields(buf, a, b):
            if field == 1:
                meta = value
            elif field == 2:
                offset = value
            elif field == 3:
                dur = value
            elif field == 4:
                stat_spans.append(value)
        events.append((meta, t0_ns + offset * 1e-3, dur * 1e-3, stat_spans))
    return name, events


def load(path: str):
    """(devices, host, launches) of a profile.  ``devices`` maps a device
    plane's name to ``{"ops": [Op], "modules": [(name, start_ns,
    duration_ns, run_id)]}``; ``host`` maps ``"<plane>/<line>"`` to the
    program's spans on that thread, with ``steps``: the (name, step_num,
    start_ns, duration_ns) of the round spans among them; ``launches`` maps
    a ``run_id`` to the host time at which that run of a module was
    enqueued (``DoEnqueueProgram``)."""
    file = newest_profile(path)
    with (gzip.open if file.endswith(".gz") else open)(file, "rb") as fh:
        buf = fh.read()
    devices: Dict[str, dict] = {}
    host: Dict[str, dict] = {}
    launches: Dict[int, float] = {}
    for field, span in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        plane, lines, meta_spans, stat_spans = _plane(buf, span)
        on_device = plane.startswith("/device:")
        if not on_device and not plane.startswith("/host:CPU"):
            continue  # /host:metadata holds the HLO protos, and is large
        stat_names = {k: _text(buf, dict(_fields(buf, *v)).get(2, (0, 0)))
                      for k, v in _map(buf, stat_spans).items()}
        metas = {}  # id -> (name, op_name)
        for key, value in _map(buf, meta_spans).items():
            name, shown, own = "", "", []
            for f, v in _fields(buf, *value):
                if f == 2:
                    name = _text(buf, v)
                elif f == 4:
                    shown = _text(buf, v)
                elif f == 5:
                    own.append(v)
            op_name = ""
            if on_device and own:
                op_name = str(_stats(buf, own, stat_names).get(OP_NAME_STAT, ""))
            metas[key] = (shown or name, op_name)
        dev = {"ops": [], "modules": []}
        for line_span in lines:
            line, events = _line(buf, line_span)
            if on_device and line == OPS_LINE:
                dev["ops"] = [(metas[m][0], s, d, metas[m][1])
                              for m, s, d, _ in events]
            elif on_device and line == MODULES_LINE:
                dev["modules"] = [
                    (metas[m][0], s, d,
                     _stats(buf, st, stat_names).get("run_id"))
                    for m, s, d, st in events]
            elif not on_device:
                spans, steps = [], []
                for m, s, d, st in events:
                    name = metas[m][0]
                    if name == ENQUEUE_EVENT:
                        run = _stats(buf, st, stat_names).get("run_id")
                        if run is not None:
                            launches[run] = s
                    if not SPAN_NAME.fullmatch(name):
                        continue
                    spans.append((name, s, d))
                    step = _stats(buf, st, stat_names).get("step_num")
                    if step is not None:
                        steps.append((name, int(step), s, d))
                if spans:
                    host[f"{plane}/{line}"] = {"spans": spans, "steps": steps}
        if dev["ops"]:
            devices[plane] = dev
    return devices, host, launches


def module_of(name: str) -> str:
    """A program's name without its fingerprint (``jit_level_step(1234)``)."""
    return re.sub(r"\(\d+\)$", "", name)


def innermost(spans: Sequence[Span], at_ns: float) -> str:
    """The span live at ``at_ns`` that began last, or :data:`NO_SPAN`."""
    live = [(s, name) for name, s, d in spans if s <= at_ns < s + d]
    return max(live)[1] if live else NO_SPAN


def reduce(devices: Dict[str, dict], host: Dict[str, dict],
           launches: Optional[Dict[int, float]] = None) -> dict:
    """What :func:`summarize` returns, from what :func:`load` returns."""
    launches = launches or {}
    all_spans = [s for thread in host.values() for s in thread["spans"]]
    out = {"devices": {}, "host": {}}
    for name, dev in devices.items():
        ops = dev["ops"]
        start = min(o[1] for o in ops)
        end = max(o[1] + o[2] for o in ops)
        busy = sum(e - s for s, e in busy_intervals(ops))
        modules = sorted(dev["modules"], key=lambda m: m[1])
        in_module: Dict[str, List[Op]] = {}
        j = 0
        for op in sorted(ops, key=lambda o: o[1]):  # as the modules are
            while j < len(modules) and modules[j][1] + modules[j][2] <= op[1]:
                j += 1
            inside = j < len(modules) and modules[j][1] <= op[1]
            in_module.setdefault(
                module_of(modules[j][0]) if inside else "(no module)",
                []).append(op)
        module_scope = {m: seconds_by_scope(o) for m, o in in_module.items()}
        scope_s: Dict[str, float] = {}  # operations never straddle modules
        for by in module_scope.values():
            for key, sec in by.items():
                scope_s[key] = scope_s.get(key, 0.0) + sec
        module_s: Dict[str, float] = {}
        launched_s: Dict[str, float] = {}
        for mod, _, dur, run in modules:
            key = module_of(mod)
            module_s[key] = module_s.get(key, 0.0) + dur * 1e-9
            if run in launches:
                span = innermost(all_spans, launches[run])
                launched_s[span] = launched_s.get(span, 0.0) + dur * 1e-9
        by_span: Dict[str, float] = {}
        gaps = idle_gaps(ops, start, end)
        for g0, dur in gaps:
            span = innermost(all_spans, g0)
            by_span[span] = by_span.get(span, 0.0) + dur * 1e-9
        out["devices"][name] = {
            "window_s": (end - start) * 1e-9, "busy_s": busy * 1e-9,
            "scope_s": scope_s, "module_s": module_s,
            "module_scope_s": module_scope, "launched_by_span_s": launched_s,
            "idle_gaps": len(gaps), "idle_gap_s": sum(by_span.values()),
            "idle_by_span_s": by_span}
    for thread, rec in host.items():
        out["host"][thread] = {
            "spans": self_seconds(rec["spans"]),
            "steps": [{"name": n, "step_num": k, "start_ns": s, "dur_ns": d}
                      for n, k, s, d in rec["steps"]]}
    return out


def summarize(profile_dir: str) -> dict:
    """The profile under ``profile_dir`` (or the ``.xplane.pb`` itself) by the
    program's own names; the module's docstring says what is in it."""
    out = reduce(*load(profile_dir))
    out["file"] = newest_profile(profile_dir)
    return out


def _table(title: str, rows: Dict[str, float], total: float,
           most: int = 12) -> List[str]:
    lines = [title]
    ranked = sorted(rows.items(), key=lambda kv: -kv[1])
    for key, s in ranked[:most]:
        share = f"{100.0 * s / total:6.2f}%" if total else ""
        lines.append(f"  {s:12.6f} s {share}  {key}")
    if len(ranked) > most:
        rest = sum(s for _, s in ranked[most:])
        lines.append(f"  {rest:12.6f} s          ({len(ranked) - most} more)")
    return lines


def render(summary: dict) -> str:
    """The summary as the tables the command prints (--json has all of it)."""
    lines = [f"profile {summary['file']}"]
    for name, dev in summary["devices"].items():
        lines.append(f"{name}: busy {dev['busy_s']:.6f} s of "
                     f"{dev['window_s']:.6f} s")
        lines += _table(" device seconds by scope", dev["scope_s"],
                        dev["busy_s"])
        lines += _table(" device seconds by module", dev["module_s"],
                        dev["busy_s"])
        for mod, by in sorted(dev["module_scope_s"].items(),
                              key=lambda kv: -sum(kv[1].values())):
            if set(by) - {UNSCOPED}:  # a module with no scope says nothing new
                lines += _table(f" {mod} by scope", by, sum(by.values()))
        lines += _table(" device seconds by the span live as the module's run "
                        "was enqueued (that can trail the call that asked)",
                        dev["launched_by_span_s"], dev["busy_s"])
        lines += _table(f" {dev['idle_gaps']} idle gaps of {GAP_NS / 1e3:.0f} "
                        f"us or more, by the span live as each opened",
                        dev["idle_by_span_s"], dev["idle_gap_s"])
    for thread, rec in summary["host"].items():
        lines.append(f"{thread}: rounds "
                     f"{[s['step_num'] for s in rec['steps']]}")
        lines.append("  total s      self s       count  span")
        for span, r in sorted(rec["spans"].items(),
                              key=lambda kv: -kv[1]["self_s"]):
            lines.append(f"  {r['total_s']:12.6f} {r['self_s']:12.6f} "
                         f"{r['count']:6d}  {span}")
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    as_json = "--json" in args
    paths = [a for a in args if a != "--json"]
    if len(paths) != 1:
        print("usage: python -m xgboost_tpu.telemetry.xplane [--json] "
              "<profile dir or .xplane.pb>", file=sys.stderr)
        return 2
    summary = summarize(paths[0])
    print(json.dumps(summary) if as_json else render(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
