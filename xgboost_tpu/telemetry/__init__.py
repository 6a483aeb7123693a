"""xgboost_tpu.telemetry — unified observability for training and serving.

One subsystem replaces the three disconnected mechanisms the repo grew
(utils/timer.Monitor stderr prints, utils/observer debug dumps,
serving/metrics counters with no export format):

- **Registry** (registry.py): lock-cheap Counter/Gauge/Histogram families
  with labels; ``serving/metrics.ServingMetrics`` feeds it, the span tracer
  records into it, ``render_prometheus()`` exposes it.
- **Spans** (spans.py): ``span("grow.to_host")`` brackets the training
  and serving hot paths.  Every span opens a jax.profiler.TraceAnnotation
  (so it lands in any live profiler session, on the device trace's clock)
  and leaves a record in the flight ring (``spans.recent()``); the flag
  (``enable()`` / env ``XGBOOST_TPU_TRACE``) adds the perf_counter
  histogram and the JSONL trace event.
- **Device scopes**: every jitted program of the training path names its
  parts with ``jax.named_scope`` (``hist``, ``split``, ``record``,
  ``route``, ...); ``python -m xgboost_tpu.telemetry.xplane <dir>``
  (xplane.py) sums any profile by them and puts idle gaps down to spans.
- **Retrace accounting** (compile.py): programs compiled, programs loaded
  from the persistent cache and functions traced are counted process-wide
  (``compiles_total()``, ``xtb_compiles_total{kind}``,
  ``xtb_traces_total``); a second identical train() records zero — the
  guard tests/test_telemetry.py keeps.
- **Exporters**: ``render_prometheus()`` text exposition and the
  chrome://tracing JSONL writer gated by ``XGBOOST_TPU_TRACE=path``
  (trace.py).
- **TelemetryCallback** (callback.py): per-round phase timings, tree
  stats, compile deltas, collective-wait attribution, and the optional
  cross-rank straggler report as an inspectable history.
- **Distributed plane** (distributed.py): workers/replicas ship registry
  snapshots over their existing channels into a driver-side
  ``MergedRegistry`` (per-``proc``-labeled + merged series) behind an
  HTTP ``/metrics`` scrape endpoint (``XGBOOST_TPU_METRICS_PORT``).
- **Flight recorder** (flight.py): always-armed fixed-size ring of recent
  spans/events/faults, dumped on crash/kill (and driver-side for
  SIGKILL'd replicas) — postmortems without tracing enabled.
- **Sampling profiler** (profiler.py): default-on wall sampler
  (``XGBOOST_TPU_PROF_HZ``, a few Hz) whose folded stacks ship with
  every telemetry payload into a driver-side merged flame view
  (``profiler.render_folded()`` — collapsed-stack format), and whose last
  ticks (``profiler.ticks()``: how late each woke, what the training thread
  was in) a slow round's line quotes.

Quick start::

    import xgboost_tpu as xtb
    from xgboost_tpu import telemetry

    telemetry.enable()                      # or XGBOOST_TPU_TRACE=run.jsonl
    cb = telemetry.TelemetryCallback()
    xtb.train(params, dtrain, 10, callbacks=[cb])
    print(telemetry.render_prometheus())    # per-phase histograms, compiles
    cb.history[1]["phases"]                 # round 1 attribution

docs/observability.md is the guide.
"""
from __future__ import annotations

from .registry import (Counter, Gauge, Histogram, Registry, get_registry,
                       render_prometheus)
from .spans import (PHASE_HISTOGRAM, Span, disable, enable, enabled,
                    phase_totals, record_phase, span, step_span)
from .compile import (COMPILE_EVENT, compile_delta, compiles_total,
                      loads_total, traces_total)
from . import (distributed, flight, native_pool, pauses, profiler, trace,
               xplane)
from .distributed import (MergedRegistry, get_merged, snapshot_payload,
                          start_metrics_server, stop_metrics_server)
from .callback import TelemetryCallback

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "get_registry",
    "render_prometheus",
    "span", "step_span", "Span", "enable", "disable", "enabled",
    "record_phase", "phase_totals", "PHASE_HISTOGRAM",
    "compiles_total", "loads_total", "traces_total", "compile_delta",
    "COMPILE_EVENT",
    "trace", "native_pool", "distributed", "flight", "pauses", "profiler",
    "xplane",
    "MergedRegistry", "get_merged", "snapshot_payload",
    "start_metrics_server", "stop_metrics_server",
    "TelemetryCallback",
]
