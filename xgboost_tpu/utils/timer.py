"""Per-label cumulative timers (reference: src/common/timer.h:45 Monitor).

Now a thin shim over the telemetry span tracer (telemetry/spans.py): each
Start/Stop bracket opens a jax.profiler.TraceAnnotation (the reference's
NVTX range role) and — when telemetry is enabled — records into the
``xtb_phase_seconds`` histogram and the JSONL trace under the same
``label.name`` the TPU profiler shows.  Totals/counts accumulate locally
regardless of the telemetry flag and print at verbosity >= 3 like the
reference (timer.cc).

Re-entrancy: ``start(name)`` pushes onto a per-label stack, so nested or
overlapping brackets of the same label each close their own timestamp and
annotation (a second start() used to silently overwrite the open timestamp
and leak the previous annotation without __exit__).
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Dict, List, Tuple

import jax.profiler

from ..config import get_config
from ..telemetry import spans as _spans


class Monitor:
    def __init__(self, label: str = "") -> None:
        self.label = label
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        # name -> stack of (t0_ns, annotation): LIFO per label so
        # re-entrant brackets nest instead of clobbering each other
        self._open: Dict[str, List[Tuple[int, object]]] = defaultdict(list)

    def start(self, name: str) -> None:
        ann = jax.profiler.TraceAnnotation(f"{self.label}.{name}")
        ann.__enter__()
        self._open[name].append((time.perf_counter_ns(), ann))

    def stop(self, name: str) -> None:
        stack = self._open.get(name)
        if not stack:
            return  # unmatched stop: ignore, like the pop(None) before
        t0, ann = stack.pop()
        dur_ns = time.perf_counter_ns() - t0
        ann.__exit__(None, None, None)
        self.totals[name] += dur_ns / 1e9
        self.counts[name] += 1
        if _spans.enabled():
            _spans.record_phase(f"{self.label}.{name}", t0, dur_ns)

    def print_statistics(self) -> None:
        if get_config().get("verbosity", 1) < 3 or not self.totals:
            return
        print(f"======== Monitor ({self.label}) ========")
        for name in sorted(self.totals):
            print(f"{name}: {self.totals[name]*1e3:.3f}ms, {self.counts[name]} calls")
