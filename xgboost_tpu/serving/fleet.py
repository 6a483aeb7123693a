"""Serving fleet: N replica processes behind one (or N sharded) dispatchers.

The multi-process scale-out layer over :class:`ServingEngine`
(docs/serving.md "Fleet" has the full topology/tuning guide):

**Sharding** (``n_shards`` > 1, docs/serving.md "Sharded topology"):
past ~4 replicas a single dispatcher thread + one big cv lock becomes
the ceiling, so the fleet splits into shared-nothing shards.  Each shard
is a full single-shard fleet — its own listener socket, DispatchQueue,
cv lock, rx threads, heartbeat/breaker/hedge state, and replica group
(labels prefixed ``s{k}:``) — while the mmap ModelStore, the warm
compile cache, the AIMD/brownout governor, and the telemetry registry
stay shared (per-shard series carry a ``shard=`` label).  The front-end
object routes ``submit`` by a stable hash of (tenant, model)
(:func:`shard_of`) and fans admin/lifecycle calls out to every shard;
every reliability semantic below holds *per shard* (a killed replica's
window-1 batch requeues within its own shard's replica group).

- **Replicas** are launcher-spawned subprocesses (``serving/replica.py``)
  sharing the mmap :class:`ModelStore` (one host copy of every booster)
  and the warm compile cache (``warmcache.py`` — AOT program file + XLA
  persistent cache), so adding a replica costs milliseconds of warm work,
  not seconds of compiles.
- **The dispatcher** (this module) owns admission and routing: requests
  queue centrally in priority order (per-tenant :class:`SLOClass`), and
  each replica holds AT MOST ONE batch in flight.  Central queueing +
  window-1 is a deliberate failure-semantics choice: when a replica dies,
  everything except its single in-flight batch is still in the
  dispatcher's queue — and the in-flight batch itself is requeued onto a
  live replica (predict is idempotent), so replica death drops nothing
  (``xtb_fleet_rerouted_total`` counts the reroutes; the fleet smoke and
  ``tests/test_fleet.py`` pin the no-loss contract).
- **The request path is zero-copy** end to end (``wire.py``): the
  dispatcher routes on the tiny JSON header and forwards Arrow IPC /
  raw-f32 payload buffers verbatim — row bytes are never deserialized,
  copied, or even looked at outside the replica.
- **Failure handling** rides the launcher's machinery: replica stderr is
  captured per process, deaths are tolerated and respawned up to
  ``max_respawns``, and a fleet that loses every replica (or can't start
  one) raises :class:`~xgboost_tpu.launcher.WorkerFailedError` carrying
  each corpse's exit code + stderr tail.

Degradation is explicit, per tenant class: beyond ``max_queue`` queued
requests the LOWEST-priority newest request is shed
(:class:`~xgboost_tpu.serving.batcher.QueueFullError`,
``xtb_fleet_shed_total{slo=}``); a request older than its class deadline
is expired in-queue (``TimeoutError``, ``xtb_fleet_deadline_total{slo=}``)
instead of wasting replica time on an answer nobody is waiting for.

The ``fleet.dispatch`` fault seam fires right before a request is handed
to a replica: ``exception`` fails that request, ``delay`` stalls the
dispatcher, ``drop_connection`` severs the chosen replica's socket — the
deterministic stand-in for a replica vanishing mid-conversation
(docs/reliability.md).

**Degraded-network survival** (docs/reliability.md "Degraded
networks"): a replica that is merely *slow* or *half-open* (process
alive, one direction blackholed) never EOFs, so the death path above
cannot see it.  Three layers close that gap without bigger timeouts:

- **Heartbeats**: the dispatcher pings every replica on a schedule over
  the same serialized control-frame path (``wire.PING``/``wire.PONG``);
  a replica with no pong AND no other frame for ``heartbeat_timeout_s``
  is declared dead — which also folds first-response liveness in (a
  replica that acks ``ready`` and then never answers its first predict
  trips the same deadline instead of coasting to the global one).
- **Circuit breaker**: a per-replica EWMA of send->result latency
  trips closed -> open when it exceeds ``breaker_latency_s``, ejecting
  the slow replica from dispatch *before* it blows the SLO; after
  ``breaker_cooldown_s`` a single half-open probe request readmits it
  on success (closed) or re-opens on failure.
- **Hedged dispatch**: an in-flight predict older than the
  ``hedge_quantile`` of recent latencies (floored at ``hedge_min_s``)
  is re-issued to a free replica as a twin with a fresh id sharing the
  SAME future — replicas are deterministic, so the first result to
  settle wins bitwise-identically and the loser is discarded by the id
  check (``xtb_net_hedge_*`` counts issued/won/wasted).  Hedging is
  bitwise-neutral by construction: hedge-on returns exactly the bytes
  hedge-off would.

**Lifecycle integration** (docs/serving.md "Online model lifecycle"):
:meth:`ServingFleet.load_version` / :meth:`~ServingFleet.activate_version`
/ :meth:`~ServingFleet.retire_version` broadcast control frames that ride
each replica's serialized connection — a replica processes them strictly
after every predict dispatched before them, which is exactly the
"retire only after in-flight batches drain" contract.  ``activate_version``
durably commits the store manifest FIRST, so a replica that dies and
respawns mid-broadcast reads the committed version at startup and
converges with the survivors.  **Shadow scoring**
(:meth:`~ServingFleet.set_shadow`) duplicates a deterministic 1-in-N
subset of a model's unversioned traffic onto a candidate version; the
comparator feeds ``xtb_lifecycle_shadow_*`` divergence series and the
per-version ``xtb_fleet_version_latency_seconds`` histogram without the
duplicated result ever reaching a caller.
"""
from __future__ import annotations

import dataclasses
import errno
import heapq
import itertools
import json
import os
import sys
import tempfile
import threading
import time
import warnings
import zlib
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FuturesTimeout
from socket import socket as Socket
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..launcher import WorkerFailedError, spawn_worker, stderr_tail
from ..reliability import faults as _faults
from ..reliability import lockdep as _lockdep
from ..reliability import resources as _resources
from ..telemetry import distributed as _distributed
from ..telemetry import flight as _flight
from ..telemetry import profiler as _profiler
from ..telemetry import trace as _trace
from ..telemetry.registry import get_registry
from . import wire
from .batcher import QueueFullError

_LATENCY_BUCKETS = tuple(1e-5 * (4.0 ** i) for i in range(12))
_COLDSTART_BUCKETS = tuple(0.01 * (2.0 ** i) for i in range(14))
# prediction divergence spans "bitwise identical continuation" (0) through
# "differently-shaped model" (O(1)); decades, not latency quartics
_SHADOW_BUCKETS = tuple(1e-9 * (10.0 ** i) for i in range(10))
# a two-sample KS statistic lives in [0, 1]: a handful of decision points
# from "indistinguishable distributions" to "disjoint supports"
_KS_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.2, 0.5)
# PSI's conventional decision points straddle 0.1 ("noticeable shift") and
# 0.25 ("act"); decades around them, open-ended above
_PSI_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)

# cumulative per-frame read budget on the dispatcher's rx loops (the
# slow-loris bound in wire.recv_frame): a peer trickling one byte per
# idle interval gets this much wall per frame TOTAL, not per read.
# Generous by default — a full 2 GiB payload over loopback clears it by
# orders of magnitude — and env-tunable for tight test harnesses.
FRAME_BUDGET_ENV = "XGBOOST_TPU_FRAME_BUDGET_S"


def _frame_budget_s() -> Optional[float]:
    raw = os.environ.get(FRAME_BUDGET_ENV, "").strip()
    if not raw:
        return 120.0
    try:
        v = float(raw)
    except ValueError:
        return 120.0
    return v if v > 0 else None


# default dispatcher shard count when FleetConfig.n_shards is 0 ("auto"):
# one shard preserves the classic single-dispatcher topology exactly
SHARDS_ENV = "XGBOOST_TPU_FLEET_SHARDS"
# SO_REUSEPORT accept path for sharded fleets: every shard binds the SAME
# port and an accepted replica connection is handed to its owning shard by
# hello-label prefix.  Default off — per-shard listener ports need no
# kernel support and no cross-shard handoff.
REUSEPORT_ENV = "XGBOOST_TPU_FLEET_REUSEPORT"


def shard_of(model: str, tenant: Optional[str], n_shards: int) -> int:
    """Client-side partition for the sharded front-end: which dispatcher
    shard owns (tenant, model) traffic.  A pure hash of the routing key —
    no registry, no state — so the SAME tenant/model pair lands on the
    SAME shard across respawns, restarts, and processes (the routing
    contract docs/serving.md pins and tests/test_fleet_shards.py
    enforces)."""
    key = f"{tenant or ''}\x00{model}".encode()
    return zlib.crc32(key) % max(1, int(n_shards))


def _ks_stat(a: np.ndarray, b: np.ndarray) -> float:
    """Two-sample Kolmogorov–Smirnov statistic between flattened
    prediction sets: max |ECDF_a - ECDF_b|.  Complements the mean-abs
    divergence — a candidate can match the incumbent on average while
    redistributing scores across the ranking (the failure mode that
    matters for AUC-shaped objectives), and KS catches exactly that."""
    a = np.sort(np.asarray(a, np.float64).ravel())
    b = np.sort(np.asarray(b, np.float64).ravel())
    if a.size == 0 or b.size == 0:
        return 0.0
    grid = np.concatenate([a, b])
    cdf_a = np.searchsorted(a, grid, side="right") / a.size
    cdf_b = np.searchsorted(b, grid, side="right") / b.size
    return float(np.max(np.abs(cdf_a - cdf_b)))


def _psi(a: np.ndarray, b: np.ndarray, bins: int = 10) -> float:
    """Population stability index of ``b`` against reference ``a``, over
    ``a``'s decile bins: sum over bins of (p_a - p_b) * ln(p_a / p_b).
    The third comparator lens next to mean-divergence and KS — KS reports
    the single worst ECDF gap, PSI integrates shift across the whole
    distribution, so a broad small drift that never opens one large gap
    still registers.  Bin fractions are clamped to 1e-6 (empty-bin PSI is
    finite, and a bin emptying out IS the signal)."""
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    if a.size == 0 or b.size == 0:
        return 0.0
    edges = np.quantile(a, np.linspace(0.0, 1.0, bins + 1)[1:-1])
    pa = np.bincount(np.searchsorted(edges, a, side="right"),
                     minlength=bins)[:bins] / a.size
    pb = np.bincount(np.searchsorted(edges, b, side="right"),
                     minlength=bins)[:bins] / b.size
    pa = np.clip(pa, 1e-6, None)
    pb = np.clip(pb, 1e-6, None)
    return float(np.sum((pa - pb) * np.log(pa / pb)))


def _calibration_gap(a: np.ndarray, b: np.ndarray, bins: int = 10) -> float:
    """Max per-decile calibration gap: bucket the pair's rows by the
    INCUMBENT's score deciles, compare each bucket's expected rate (the
    incumbent's mean score — what the serving distribution promised) with
    the candidate's observed mean on the same rows.  A candidate can pass
    mean-divergence and KS while systematically re-scoring one decile
    (e.g. flattening the top bucket a bid system prices from); the
    per-decile max catches exactly that."""
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    if a.size == 0 or a.size != b.size:
        return 0.0
    edges = np.quantile(a, np.linspace(0.0, 1.0, bins + 1)[1:-1])
    idx = np.searchsorted(edges, a, side="right")
    gap = 0.0
    for d in range(bins):
        m = idx == d
        if m.any():
            gap = max(gap, abs(float(a[m].mean()) - float(b[m].mean())))
    return gap


@dataclasses.dataclass(frozen=True)
class SLOClass:
    """One tenant class: who gets served first and how long they wait.

    ``priority``: higher dispatches first and sheds last.  ``deadline_s``:
    submit-to-result budget — expired queued requests fail fast with
    ``TimeoutError`` instead of occupying a replica (None = wait forever).
    """

    name: str = "default"
    priority: int = 0
    deadline_s: Optional[float] = None


# shadow twins are discardable measurements: they outrank NOTHING, so
# under queue pressure a twin sheds itself (a comparator "failure")
# rather than evicting any real caller's request
_SHADOW_SLO = SLOClass("shadow", priority=-(2 ** 31))


@dataclasses.dataclass
class FleetConfig:
    n_replicas: int = 2
    store_dir: Optional[str] = None   # None = private temp dir
    cache_dir: Optional[str] = None   # None = no warm cache (always cold)
    warmup_buckets: Tuple[int, ...] = ()  # () = replica default ladder
    max_queue: int = 4096             # queued requests before shedding
    slo_classes: Dict[str, SLOClass] = dataclasses.field(
        default_factory=dict)       # tenant -> class
    default_slo: SLOClass = dataclasses.field(default_factory=SLOClass)
    nthread_per_replica: int = 1      # native pool width per replica
    max_respawns: int = 2
    ready_timeout_s: float = 300.0
    platform: Optional[str] = None    # replica jax platform (None = inherit)
    # --- degraded-network survival (docs/reliability.md "Degraded
    # networks"); breaker and hedging default OFF, heartbeats default ON
    heartbeat_s: float = 2.0          # ping cadence (0 = no heartbeats)
    heartbeat_timeout_s: float = 30.0  # no pong AND no frame -> declared
    breaker_latency_s: float = 0.0    # EWMA trip point (0 = breaker off)
    breaker_cooldown_s: float = 2.0   # open -> half-open probe delay
    hedge_quantile: float = 0.0       # latency quantile (0 = no hedging)
    hedge_min_s: float = 0.01         # hedge budget floor
    # --- sharded front-end (docs/serving.md "Sharded topology"):
    # n_shards > 1 splits the fleet into shared-nothing dispatcher shards,
    # each owning n_replicas/n_shards replicas, its own listener, queue,
    # rx threads, and degraded-network state; submit() routes by
    # hash(tenant, model).  0 = XGBOOST_TPU_FLEET_SHARDS (default 1).
    n_shards: int = 0
    # None = XGBOOST_TPU_FLEET_REUSEPORT (default off): shards share one
    # SO_REUSEPORT listening port instead of per-shard ports
    reuseport: Optional[bool] = None

    def __post_init__(self) -> None:
        if self.n_shards == 0:
            raw = os.environ.get(SHARDS_ENV, "").strip()
            try:
                self.n_shards = int(raw) if raw else 1
            except ValueError:
                self.n_shards = 1
        if self.reuseport is None:
            self.reuseport = os.environ.get(
                REUSEPORT_ENV, "").strip().lower() not in (
                    "", "0", "false", "off", "no")
        if self.n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        if self.n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if self.n_replicas % self.n_shards:
            # n_replicas is the fleet TOTAL; every shard owns an equal
            # replica group (uneven groups would skew both the routing
            # contract and the saturation math)
            raise ValueError(
                f"n_replicas ({self.n_replicas}) must be divisible by "
                f"n_shards ({self.n_shards})")
        if self.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if not 0.0 <= self.hedge_quantile < 1.0:
            raise ValueError("hedge_quantile must be in [0, 1)")

    def resolve_slo(self, tenant: Optional[str]) -> SLOClass:
        if tenant is None:
            return self.default_slo
        return self.slo_classes.get(tenant, self.default_slo)


class _Instruments:
    """xtb_fleet_* registry families (process-wide singleton)."""

    _singleton = None

    def __init__(self) -> None:
        reg = get_registry()
        self.replicas = reg.gauge(
            "xtb_fleet_replicas", "live (ready) fleet replicas")
        self.requests = reg.counter(
            "xtb_fleet_requests_total", "requests dispatched to replicas",
            ("model",))
        self.rerouted = reg.counter(
            "xtb_fleet_rerouted_total",
            "in-flight requests requeued after a replica death")
        self.respawns = reg.counter(
            "xtb_fleet_respawns_total", "replacement replicas spawned")
        self.shed = reg.counter(
            "xtb_fleet_shed_total",
            "requests shed at admission (queue full)", ("slo",))
        self.deadline = reg.counter(
            "xtb_fleet_deadline_total",
            "requests expired before/at their class deadline", ("slo",))
        self.latency = reg.histogram(
            "xtb_fleet_latency_seconds", "submit-to-result request latency",
            ("model",), buckets=_LATENCY_BUCKETS)
        self.coldstart = reg.histogram(
            "xtb_fleet_coldstart_seconds",
            "replica warm-work seconds at ready, by compile-cache state",
            ("cache",), buckets=_COLDSTART_BUCKETS)
        self.version_latency = reg.histogram(
            "xtb_fleet_version_latency_seconds",
            "submit-to-result latency by served model version",
            ("model", "version"), buckets=_LATENCY_BUCKETS)
        self.shadow_requests = reg.counter(
            "xtb_lifecycle_shadow_requests_total",
            "shadow-scored request pairs compared", ("model",))
        self.shadow_failures = reg.counter(
            "xtb_lifecycle_shadow_failures_total",
            "shadow pairs that could not be compared (either side failed "
            "or was shed)", ("model",))
        self.shadow_divergence = reg.histogram(
            "xtb_lifecycle_shadow_divergence",
            "mean |candidate - incumbent| prediction divergence per "
            "shadow-scored request", ("model",), buckets=_SHADOW_BUCKETS)
        self.shadow_ks = reg.histogram(
            "xtb_lifecycle_shadow_ks",
            "two-sample KS statistic between candidate and incumbent "
            "prediction distributions per shadow-scored request",
            ("model",), buckets=_KS_BUCKETS)
        self.shadow_psi = reg.histogram(
            "xtb_lifecycle_shadow_psi",
            "population stability index of candidate vs incumbent "
            "prediction distributions per shadow-scored request",
            ("model",), buckets=_PSI_BUCKETS)
        self.shadow_calibration = reg.histogram(
            "xtb_lifecycle_shadow_calibration",
            "max per-incumbent-decile calibration gap (expected vs "
            "observed mean score) per shadow-scored request",
            ("model",), buckets=_SHADOW_BUCKETS)
        self.feedback_frames = reg.counter(
            "xtb_online_feedback_frames_total",
            "feedback-capture frames received from replicas", ("model",))
        self.feedback_rows = reg.counter(
            "xtb_online_sampled_rows_total",
            "feature rows received through feedback capture", ("model",))
        self.brownout = reg.counter(
            "xtb_fleet_brownout_total",
            "requests shed at admission by the resource-pressure "
            "brownout (low-SLO tenants first)", ("slo",))
        self.admission_window = reg.gauge(
            "xtb_fleet_admission_window",
            "current AIMD admission window (queued requests admitted "
            "before shedding; collapses under overload, recovers on "
            "completions)")
        self.hb_rtt = reg.histogram(
            "xtb_net_heartbeat_rtt_seconds",
            "application-level ping->pong round trip per replica",
            ("replica",), buckets=_LATENCY_BUCKETS)
        self.breaker_state = reg.gauge(
            "xtb_net_breaker_state",
            "per-replica circuit breaker state (0 closed, 1 open, "
            "2 half-open)", ("replica",))
        self.breaker_transitions = reg.counter(
            "xtb_net_breaker_transitions_total",
            "circuit breaker state transitions, by target state", ("to",))
        self.hedges = reg.counter(
            "xtb_net_hedges_total",
            "hedge twins issued for in-flight requests past the hedge "
            "budget")
        self.hedge_wins = reg.counter(
            "xtb_net_hedge_wins_total",
            "hedged requests whose twin's result settled the caller "
            "first")
        self.hedge_wasted = reg.counter(
            "xtb_net_hedge_wasted_total",
            "duplicate hedge-pair results discarded after the pair's "
            "first settle")
        self.label_frames = reg.counter(
            "xtb_net_label_frames_total",
            "op=\"label\" frames received over label-feed connections")
        # --- sharded front-end series (docs/serving.md "Sharded
        # topology"): per-shard throughput + rx-loop occupancy, labeled by
        # owning dispatcher shard ("0" on an unsharded fleet)
        self.shards = reg.gauge(
            "xtb_fleet_shards", "configured dispatcher shards")
        self.shard_requests = reg.counter(
            "xtb_fleet_shard_requests_total",
            "predict requests dispatched, by owning dispatcher shard",
            ("shard",))
        self.shard_rows = reg.counter(
            "xtb_fleet_shard_rows_total",
            "payload rows dispatched, by owning dispatcher shard",
            ("shard",))
        self.shard_rx_busy = reg.counter(
            "xtb_fleet_shard_rx_busy_seconds_total",
            "rx-loop seconds spent processing received frames (vs "
            "blocked waiting for one), by dispatcher shard — busy/wall "
            "is the shard's rx occupancy fraction", ("shard",))

    @classmethod
    def get(cls) -> "_Instruments":
        if cls._singleton is None:
            cls._singleton = cls()
        return cls._singleton


class AdaptiveAdmission:
    """AIMD admission control over the dispatch queue (pure state machine;
    the fleet wires its transitions to the resource governor, tests drive
    it directly).

    The fixed ``max_queue`` bound is the right *ceiling*, but under
    overload it is the wrong *operating point*: a queue allowed to sit at
    the ceiling serves every request at worst-case latency before finally
    shedding.  TCP's answer applies directly — multiplicative decrease on
    every pressure event (a shed, an in-queue deadline expiry, a replica
    death), additive increase (+1) per completed request, clamped to
    ``[floor, max_queue]``.  A saturated fleet converges to a small
    admission window (shedding early, keeping queue wait bounded); a
    recovered fleet climbs back to the ceiling in ~max_queue completions.

    ``on_pressure()`` returns True on the transition onto the floor —
    the fleet's cue to declare overload to the resource governor (which
    starts the SLO brownout); ``on_ok()`` returns True on the recovery
    transition (window back above half the ceiling) — the cue to restore
    it.  Both edges fire once per excursion, so governor levels move on
    state *transitions*, never per request.
    """

    def __init__(self, max_queue: int, floor: Optional[int] = None) -> None:
        self.max_queue = max(int(max_queue), 1)
        self.floor = max(1, min(int(floor) if floor is not None else 8,
                                self.max_queue))
        # governor coupling needs room between the edges: the floor edge
        # (declare overload) and the recovery edge (ceiling/2) must be at
        # least a doubling apart, or a single completion right after a
        # shed would flap the overload level per request.  Queues under
        # 4x the floor (tests, toy configs) keep the AIMD window but
        # never couple to the governor.
        self.coupled = self.max_queue >= 4 * self.floor
        self._window = float(self.max_queue)
        self._lock = threading.Lock()
        self._floored = False

    def limit(self) -> int:
        return int(self._window)

    def on_pressure(self) -> bool:
        """Multiplicative decrease; True on the onto-the-floor edge
        (coupled queues only — see ``__init__``)."""
        with self._lock:
            self._window = max(float(self.floor), self._window / 2.0)
            hit = self._window <= self.floor and self.coupled
            edge = hit and not self._floored
            if hit:
                self._floored = True
        return edge

    def on_ok(self) -> bool:
        """Additive increase; True on the recovered edge (window back
        above half the ceiling — >= 2x the floor on any coupled queue —
        after having been floored)."""
        with self._lock:
            self._window = min(float(self.max_queue), self._window + 1.0)
            recovered = (self._floored
                         and self._window >= self.max_queue / 2.0)
            if recovered:
                self._floored = False
        return recovered


class _Request:
    __slots__ = ("id", "model", "header", "payload", "future",
                 "slo", "deadline", "t_submit", "tries", "state",
                 "t_submit_ns", "t_send_ns", "hedge", "hedged")

    def __init__(self, rid: int, model: str, header: dict, payload,
                 slo: SLOClass) -> None:
        self.id = rid
        self.model = model
        self.header = header
        self.payload = payload
        self.future: Future = Future()
        self.slo = slo
        self.t_submit = time.monotonic()
        self.deadline = (self.t_submit + slo.deadline_s
                         if slo.deadline_s is not None else None)
        self.tries = 0
        self.state = "queued"  # queued | inflight | done | shed | expired
        # trace bracket anchors (perf_counter_ns: on Linux a system-wide
        # monotonic epoch, so dispatcher and replica events align in one
        # merged chrome://tracing timeline)
        self.t_submit_ns = time.perf_counter_ns()
        self.t_send_ns = 0
        # hedged dispatch: `hedge` marks a twin (fresh id, SHARED future);
        # `hedged` marks an original that already has a twin out, so the
        # tick never double-hedges
        self.hedge = False
        self.hedged = False


class DispatchQueue:
    """Priority queue with SLO-ordered shedding (NOT thread-safe: the
    fleet holds its lock around every call; standalone so the shed/expiry
    policy is unit-testable without processes).

    Order: higher ``SLOClass.priority`` first, FIFO within a class.  When
    full, the victim is the NEWEST request of the LOWEST priority class —
    and only if the incoming request outranks it; an incoming request that
    doesn't outrank anyone is shed itself (equal priority sheds the
    newcomer: FIFO fairness).
    """

    def __init__(self, max_queue: int) -> None:
        self.max_queue = int(max_queue)
        self._heap: List[Tuple[int, int, _Request]] = []
        self._seq = itertools.count()
        self._live = 0

    def __len__(self) -> int:
        return self._live

    def push(self, req: _Request,
             limit: Optional[int] = None) -> Optional[_Request]:
        """Admit ``req``; returns the request shed to make room (which may
        be ``req`` itself), or None when nothing was shed.  ``limit``
        (the AIMD admission window) tightens the bound below
        ``max_queue`` for this push — the ceiling still always applies."""
        victim = None
        cap = self.max_queue if limit is None else max(
            1, min(int(limit), self.max_queue))
        if self._live >= cap:
            # victim = newest request of the lowest-priority class (heap
            # entries carry (-priority, seq): max picks exactly that).
            # Removed PHYSICALLY, not just by state: under a sustained
            # overload with no pops (every replica stalled) lazy removal
            # would grow the heap — and the shed payload buffers it
            # retains — by one entry per shed, without bound.
            cands = [e for e in self._heap if e[2].state == "queued"]
            entry = max(cands, key=lambda e: (e[0], e[1]), default=None)
            if entry is not None and -entry[0] < req.priority_():
                victim = entry[2]
                victim.state = "shed"
                self._heap.remove(entry)
                heapq.heapify(self._heap)
                self._live -= 1
            else:  # nobody outranked: the newcomer is the victim
                req.state = "shed"
                return req
        heapq.heappush(self._heap, (-req.priority_(), next(self._seq), req))
        self._live += 1
        return victim

    def pop(self, now: float) -> Tuple[Optional[_Request], List[_Request]]:
        """Highest-priority oldest live request, plus any expired on the
        way (deadline passed while queued)."""
        expired: List[_Request] = []
        while self._heap:
            _, _, req = self._heap[0]
            if req.state != "queued":  # lazily drop shed/expired/cancelled
                heapq.heappop(self._heap)
                continue
            if req.future.cancelled() or req.future.done():
                # cancelled: the caller timed out — don't burn a replica on
                # an answer nobody will read.  done: a hedge twin already
                # settled the shared future while this side sat requeued
                # after its replica died — dispatching it again is pure
                # waste.
                heapq.heappop(self._heap)
                req.state = "done"
                self._live -= 1
                continue
            if req.deadline is not None and now >= req.deadline:
                heapq.heappop(self._heap)
                req.state = "expired"
                self._live -= 1
                expired.append(req)
                continue
            heapq.heappop(self._heap)
            req.state = "inflight"
            self._live -= 1
            return req, expired
        return None, expired

    def requeue_front(self, req: _Request) -> None:
        """Put a rerouted in-flight request back at the FRONT of its
        class (seq below everything queued so far)."""
        req.state = "queued"
        # negative seq sorts below every normally-pushed entry of the class
        heapq.heappush(self._heap, (-req.priority_(), -next(self._seq), req))
        self._live += 1

    def drain(self) -> List[_Request]:
        out = [e[2] for e in self._heap if e[2].state == "queued"]
        for r in out:
            r.state = "shed"
        self._heap.clear()
        self._live = 0
        return out


# priority accessor lives on the request so DispatchQueue never imports
# SLOClass details
_Request.priority_ = lambda self: self.slo.priority  # type: ignore


class _Replica:
    """Dispatcher-side view of one replica process (plain struct; all
    mutation happens under the fleet condition variable)."""

    __slots__ = ("label", "proc", "sock", "rx", "in_flight", "ready_info",
                 "alive", "ctrl", "quarantined", "last_rx", "last_ping",
                 "ping_sent", "ping_seq", "ewma", "breaker",
                 "breaker_until", "probe", "txlock")

    def __init__(self, label: str, proc) -> None:
        self.label = label
        self.proc = proc
        self.sock: Optional[Socket] = None
        self.rx: Optional[threading.Thread] = None
        self.in_flight: Optional[_Request] = None
        self.ready_info: Optional[dict] = None
        self.alive = False
        # replica-bound lifecycle control frames (load/activate/retire):
        # dispatched ahead of queued traffic, never rerouted to a peer
        self.ctrl: deque = deque()
        # set by an op="quarantine" frame (arena checksum divergence):
        # the death that follows is a quarantine, not a crash
        self.quarantined: Optional[str] = None
        # --- degraded-network state (mutated under the fleet cv, except
        # last_rx which any rx frame stamps — a GIL-atomic float store)
        self.last_rx = 0.0                       # monotonic of last frame
        self.last_ping = 0.0                     # monotonic of last ping
        self.ping_sent: Dict[int, float] = {}    # seq -> send monotonic
        self.ping_seq = 0
        self.ewma: Optional[float] = None        # send->result EWMA
        self.breaker = "closed"                  # closed|open|half_open
        self.breaker_until = 0.0                 # open -> probe allowed at
        self.probe = False                       # half-open probe out
        # heartbeat pings share the socket with dispatch sends from other
        # threads; two interleaved sendalls would shear a frame.  Held
        # across the wire by contract -> serial for the lockdep witness
        self.txlock = _lockdep.mark_serial(threading.Lock())


_ERR_TYPES = {"ValueError": ValueError, "KeyError": KeyError,
              "TimeoutError": TimeoutError, "TypeError": TypeError}


_EBADF_ONLY = (errno.EBADF,)
_SHUTDOWN_BENIGN = (errno.EBADF, errno.EPIPE, errno.ECONNRESET)


def _note_os(e: OSError, site: str, benign=()) -> None:
    """Classify an OS error unless its errno is expected on this path
    (EBADF from closing an already-closed socket at shutdown, EPIPE to a
    dead replica): xtb_resource_errors_total exists to surface the errno
    that MATTERS, and steady shutdown noise would bury it."""
    if getattr(e, "errno", None) not in benign:
        _resources.note_os_error(e, site)


class ServingFleet:
    """Spawn, route, survive.  ``models`` maps name -> Booster or model
    path (published into the store at start); alternatively pass a
    pre-populated ``store_dir`` and ``models=None``.

    Usage::

        from xgboost_tpu.serving import ServingFleet, SLOClass

        with ServingFleet({"ctr": booster}, n_replicas=4,
                          cache_dir="/var/cache/xtb-fleet") as fleet:
            y = fleet.predict("ctr", rows)                  # numpy path
            y = fleet.predict_arrow("ctr", record_batch)    # arrow path
    """

    def __init__(self, models: Optional[Dict[str, Any]] = None,
                 config: Optional[FleetConfig] = None, **overrides) -> None:
        if config is None:
            config = FleetConfig(**overrides)
        elif overrides:
            config = dataclasses.replace(config, **overrides)
        self.config = config
        self._models = dict(models or {})
        self._ins = _Instruments.get()
        self._cv = threading.Condition()
        self._queue = DispatchQueue(config.max_queue)
        self._admit = AdaptiveAdmission(config.max_queue)
        self._ins.admission_window.set(self._admit.limit())
        self._replicas: Dict[str, _Replica] = {}
        self._failures: List[Tuple[str, int, str]] = []
        self._err_files: Dict[str, str] = {}
        # observability plane (all under _cv): last shipped registry
        # snapshot + flight ring per replica label — retained after death
        # (the merged /metrics view and the postmortem dump read these)
        self._telemetry: Dict[str, dict] = {}
        self._flight_rings: Dict[str, list] = {}
        self._flight_dumps: Dict[str, str] = {}
        # label -> reason for every replica that quarantined itself after
        # a failed arena verification (retained after death, like the
        # telemetry above — the postmortem surface)
        self.quarantined: Dict[str, str] = {}
        self._next_id = itertools.count(1)
        # lifecycle state (all under _cv): the fleet's view of each model's
        # active version (labels unversioned latency) and per-model shadow
        # routing config {name: {"version", "every", "n", stats...}}
        self._versions: Dict[str, int] = {}
        self._shadow: Dict[str, dict] = {}
        # online-loop state (under _cv): per-model feedback sample rate
        # (resynced onto respawns like _versions) and the registered
        # driver-side consumer of decoded feedback records
        self._sampling: Dict[str, int] = {}
        self._feedback_sink = None
        # consumer for op="label" frames from label-feed connections
        # (signature sink(trace, y)); the online loop registers
        # FeedbackHub.label here
        self._label_sink = None
        # recent send->result predict latencies (under _cv): the sample
        # the hedge-budget quantile is computed from
        self._lat_hist: deque = deque(maxlen=512)
        self._respawned = 0
        self._started = False
        self._bringup_done = False
        self._closed = False
        self._extinct = False  # every replica dead, respawn budget spent
        self._listener: Optional[Socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._sched_thread: Optional[threading.Thread] = None
        self._store_dir: Optional[str] = None
        self._tmp_store = False
        # --- sharded front-end state (docs/serving.md "Sharded
        # topology").  With n_shards > 1 THIS instance becomes a pure
        # router: start() builds one single-shard sibling ServingFleet
        # per shard (each with its own listener, queue, cv, rx threads,
        # and degraded-network state — shared-nothing by construction;
        # the store/cache dirs and the telemetry registry stay shared)
        # and submit() routes by shard_of(model, tenant).  The list is
        # immutable once start() returns, so routing reads it lock-free.
        self._shards: Optional[List["ServingFleet"]] = None
        self._label_prefix = ""     # "s<k>:" on a shard, "" unsharded
        self._shard_label = "0"     # {shard=} label on per-shard series
        self._ext_listener: Optional[Socket] = None  # pre-bound listener
        # SO_REUSEPORT accept path: label-prefix -> owning shard, shared
        # by every sibling so an accept landing on the wrong shard's
        # listener hands the connection to its owner
        self._shard_peers: Optional[Dict[str, "ServingFleet"]] = None

    # ---------------------------------------------------------------- start
    def start(self) -> "ServingFleet":
        import socket as socketlib

        from .modelstore import ModelStore

        self._check_chip_replicas()
        if self.config.n_shards > 1:
            return self._start_sharded()
        with self._cv:
            if self._started:
                return self
            self._started = True
            self._store_dir = self.config.store_dir
            if self._store_dir is None:
                self._store_dir = tempfile.mkdtemp(prefix="xtb_fleet_store_")
                self._tmp_store = True
        # opt-in scrape endpoint (XGBOOST_TPU_METRICS_PORT): one GET
        # /metrics returns driver-side xtb_fleet_* plus every replica's
        # shipped series, per-process-labeled and merged
        _distributed.start_metrics_server()
        # default-on wall sampler: the dispatcher rx/dispatch loops join
        # the merged flame view (telemetry/profiler.py)
        _profiler.maybe_start("fleet-driver")
        if _trace.active():
            _trace.set_process_name("fleet-driver")
        store = ModelStore(self._store_dir)
        for name, source in self._models.items():
            store.publish(name, source)
        if not store.entries():
            raise ValueError("fleet has no models: pass models= or a "
                             "pre-populated store_dir=")
        with self._cv:
            try:
                # commit the serving versions explicitly (one rewrite,
                # no-op when already committed): once a fleet runs,
                # "active" never silently tracks "latest", so a lifecycle
                # publish (which bumps latest) cannot move what serves
                # before its activate commit
                store.commit_active()
            except OSError as e:
                # read-only store: a pure-read consumer fleet still works
                # (lifecycle publishes need a writable store anyway, so
                # "latest" cannot drift underneath this fleet)
                warnings.warn(f"model store {self._store_dir} is not "
                              f"writable ({e}); serving versions stay "
                              f"implicitly latest-tracking")
            for name, version in store.serving_entries():
                self._versions[name] = version
        listener = self._ext_listener
        if listener is None:
            listener = socketlib.socket()
            listener.bind(("127.0.0.1", 0))
            listener.listen(max(8, self.config.n_replicas * 2))
        with self._cv:
            self._listener = listener
        accept = threading.Thread(target=self._accept_loop, daemon=True,
                                  name="xtb-fleet-accept")
        sched = threading.Thread(target=self._dispatch_loop, daemon=True,
                                 name="xtb-fleet-dispatch")
        with self._cv:
            self._accept_thread = accept
            self._sched_thread = sched
        for i in range(self.config.n_replicas):
            self._spawn(f"{self._label_prefix}replica{i}")
        accept.start()
        sched.start()
        deadline = time.monotonic() + self.config.ready_timeout_s
        with self._cv:
            while True:
                ready = sum(1 for r in self._replicas.values() if r.alive)
                remaining = deadline - time.monotonic()
                if (ready >= self.config.n_replicas or self._closed
                        or self._extinct or remaining <= 0):
                    # extinct = every replica already crashed and the
                    # respawn budget is spent: fail NOW, not at timeout
                    failures = list(self._failures)
                    break
                self._cv.wait(timeout=min(remaining, 0.5))
        if ready < self.config.n_replicas:
            self._shutdown()
            raise WorkerFailedError(
                f"fleet start: only {ready}/{self.config.n_replicas} "
                f"replicas became ready within "
                f"{self.config.ready_timeout_s}s", failures)
        with self._cv:
            self._bringup_done = True
        return self

    def _start_sharded(self) -> "ServingFleet":
        """Bring up the shared-nothing sharded topology: publish the
        models ONCE into the (shared) store, then build and start one
        single-shard sibling fleet per shard concurrently.  Each sibling
        owns its replica group end to end — listener, DispatchQueue,
        heartbeat/breaker/hedge state, rx threads, its own cv lock — so
        shards never contend on a shared dispatcher lock; only the mmap
        store, the warm compile cache, the process-wide governor, and the
        telemetry registry (per-shard series separated by the ``shard=``
        label and shard-prefixed replica labels) are shared."""
        import socket as socketlib

        from .modelstore import ModelStore

        cfg = self.config
        with self._cv:
            if self._started:
                return self
            self._started = True
            self._store_dir = cfg.store_dir
            if self._store_dir is None:
                self._store_dir = tempfile.mkdtemp(prefix="xtb_fleet_store_")
                self._tmp_store = True
        store = ModelStore(self._store_dir)
        for name, source in self._models.items():
            store.publish(name, source)
        if not store.entries():
            raise ValueError("fleet has no models: pass models= or a "
                             "pre-populated store_dir=")
        try:
            store.commit_active()
        except OSError as e:
            warnings.warn(f"model store {self._store_dir} is not "
                          f"writable ({e}); serving versions stay "
                          f"implicitly latest-tracking")
        n = cfg.n_shards
        listeners: Optional[List[Socket]] = None
        if cfg.reuseport and hasattr(socketlib, "SO_REUSEPORT"):
            # every shard listens on ONE shared port: the kernel spreads
            # incoming replica connections across the shard listeners,
            # and an accept that lands on the wrong shard is handed to
            # its owner by hello-label prefix (_accept_loop)
            listeners = []
            port = 0
            for _ in range(n):
                s = socketlib.socket()
                s.setsockopt(socketlib.SOL_SOCKET,
                             socketlib.SO_REUSEPORT, 1)
                s.bind(("127.0.0.1", port))
                port = s.getsockname()[1]
                s.listen(max(8, cfg.n_replicas * 2))
                listeners.append(s)
        shards: List[ServingFleet] = []
        for k in range(n):
            sub = dataclasses.replace(
                cfg, n_shards=1, n_replicas=cfg.n_replicas // n,
                store_dir=self._store_dir)
            shard = ServingFleet(None, sub)
            shard._label_prefix = f"s{k}:"
            shard._shard_label = str(k)
            if listeners is not None:
                shard._ext_listener = listeners[k]
            shards.append(shard)
        if listeners is not None:
            peers = {f"s{k}": shards[k] for k in range(n)}
            for shard in shards:
                shard._shard_peers = peers
        with self._cv:
            self._shards = shards
        self._ins.shards.set(float(n))
        errs: List[BaseException] = []

        def _boot(shard: "ServingFleet") -> None:
            try:
                shard.start()
            except BaseException as e:  # surfaced to the caller below
                errs.append(e)

        threads = [threading.Thread(target=_boot, args=(s,), daemon=True,
                                    name=f"xtb-fleet-boot-s{i}")
                   for i, s in enumerate(shards)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            self.close()
            raise errs[0]
        with self._cv:
            self._bringup_done = True
        return self

    def _replica_platform(self) -> Optional[str]:
        """The platform the replicas will run on, as far as the
        configuration says: ``FleetConfig.platform``, else the
        ``JAX_PLATFORMS`` they inherit, else None (the replica finds out).
        Never by asking JAX: a process that has initialised JAX on a chip
        holds it, and a replica that needs it would then fail or hang."""
        plat = self.config.platform or os.environ.get("JAX_PLATFORMS", "")
        return plat.split(",")[0].strip().lower() or None

    def _check_chip_replicas(self) -> None:
        """A chip belongs to one process, and a replica process takes every
        chip its JAX can see (per-replica chip assignment is not built), so
        more than one chip-holding replica cannot start: refuse it here,
        with the reason, before anything is spawned."""
        plat = self._replica_platform()
        if plat not in (None, "cpu") and self.config.n_replicas > 1:
            raise ValueError(
                f"fleet of {self.config.n_replicas} replicas on platform "
                f"{plat!r}: each replica is a process that claims every "
                f"chip it can see, and a chip belongs to one process, so "
                f"only one chip-holding replica can start.  Ask for "
                f"n_replicas=1, or platform='cpu' for host replicas.")

    def _spawn(self, label: str) -> None:
        port = self._listener.getsockname()[1]
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        plat = self._replica_platform()
        if plat == "cpu" and self.config.nthread_per_replica > 0:
            # N replicas each spawning an ncores-wide spinning XLA intra-op
            # pool convoy each other off the host (4 replicas on 2 cores
            # measured ~10x per-request inflation); one knob caps BOTH
            # pools — the native XtbThreadPool (--nthread) and XLA's —
            # at the configured per-replica width.  This REPLACES any
            # inherited XLA_FLAGS for CPU replicas (set
            # nthread_per_replica=0 to pass the parent's flags through);
            # on other platforms, and where the platform is not stated,
            # replicas inherit the environment as-is.
            env["XLA_FLAGS"] = (
                "--xla_cpu_multi_thread_eigen=false "
                f"intra_op_parallelism_threads="
                f"{self.config.nthread_per_replica}")
        argv = [sys.executable, "-m", "xgboost_tpu.serving.replica",
                "--host", "127.0.0.1", "--port", str(port),
                "--store", self._store_dir, "--label", label,
                "--nthread", str(self.config.nthread_per_replica)]
        if self.config.cache_dir:
            argv += ["--cache", self.config.cache_dir]
        if self.config.platform:
            argv += ["--platform", self.config.platform]
        if self.config.warmup_buckets:
            argv += ["--buckets",
                     ",".join(str(b) for b in self.config.warmup_buckets)]
        proc = spawn_worker(argv, label, self._err_files, env=env)
        with self._cv:
            self._replicas[label] = _Replica(label, proc)

    # ------------------------------------------------------------- accepting
    def _accept_loop(self) -> None:
        while True:
            try:
                sock, _ = self._listener.accept()
            except OSError as e:
                # listener closed = shutdown (EBADF, not worth counting);
                # anything else (EMFILE under fd exhaustion) is
                # classified before we stop accepting
                _note_os(e, "fleet.accept", benign=_EBADF_ONLY)
                return
            wire.configure(sock)
            try:
                sock.settimeout(self.config.ready_timeout_s)
                hello, _ = wire.recv_frame(sock)
                if hello.get("kind") == "label_feed":
                    # not a replica: a label producer (possibly another
                    # process/host) streaming op="label" frames for the
                    # online loop's join — its own rx thread, no replica
                    # bookkeeping
                    sock.settimeout(None)
                    src = str(hello.get("label", "labeler"))
                    threading.Thread(
                        target=self._label_rx_loop, args=(src, sock),
                        daemon=True,
                        name=f"xtb-fleet-label-{src}").start()
                    continue
                ready, _ = wire.recv_frame(sock)
                sock.settimeout(None)
                label = hello.get("label", "?")
            except (wire.WireError, TimeoutError):
                # malformed or slow hello (socket.timeout is
                # TimeoutError): not a resource event
                sock.close()
                continue
            except OSError as e:
                _note_os(e, "fleet.handshake")
                sock.close()
                continue
            # SO_REUSEPORT accept path: the kernel may spread replica
            # connections across the shard listeners, so the one that
            # landed here can belong to a sibling — the hello label's
            # shard prefix names the owner; registration happens there,
            # under the OWNER's cv
            owner = self
            if self._shard_peers is not None and ":" in label:
                owner = self._shard_peers.get(label.split(":", 1)[0], self)
            owner._register_replica(label, sock, ready)

    def _register_replica(self, label: str, sock, ready: dict) -> None:
        """Adopt one post-handshake replica connection: bookkeeping,
        respawn resync control frames, rx thread.  Factored out of
        :meth:`_accept_loop` because under the SO_REUSEPORT accept path
        the accepting thread may be a sibling shard's — every mutation
        here is under THIS shard's cv, whichever thread runs it."""
        rx = threading.Thread(target=self._rx_loop, args=(label, sock),
                              daemon=True, name=f"xtb-fleet-rx-{label}")
        with self._cv:
            rep = self._replicas.get(label)
            if rep is None or self._closed:
                sock.close()
                return
            rep.sock = sock
            rep.rx = rx
            rep.ready_info = ready
            rep.alive = True
            # liveness baseline: the ready frame is frame zero, so a
            # replica that acks ready and then never answers anything
            # trips the heartbeat deadline instead of coasting to the
            # global one; last_ping = now delays the first ping by one
            # full heartbeat period
            rep.last_rx = rep.last_ping = time.monotonic()
            self._ins.breaker_state.labels(label).set(0.0)
            # version resync for RESPAWNS: the replica read the
            # manifest's active versions at process startup, which may
            # predate an activate committed while it was warming up
            # (spawn -> set_active -> broadcast that skipped the
            # not-yet-ready respawn).  Idempotent activate frames,
            # dispatched ahead of any traffic, bring it to the fleet's
            # view; when the replica already serves that version this
            # is a no-op pin.  Initial bring-up needs none of this:
            # start() returns only after every replica is ready, so no
            # activate can precede an initial replica's manifest read.
            for name, version in (self._versions.items()
                                  if self._bringup_done else ()):
                rid = next(self._next_id)
                rep.ctrl.append(_Request(
                    rid, name, {"op": "activate", "model": name,
                                "version": int(version), "id": rid},
                    b"", self.config.default_slo))
            # feedback-capture resync, same contract as the version
            # resync above: a respawn that missed the sample broadcast
            # converges to the fleet's configured rate
            for name, every in (self._sampling.items()
                                if self._bringup_done else ()):
                rid = next(self._next_id)
                rep.ctrl.append(_Request(
                    rid, name, {"op": "sample", "model": name,
                                "every": int(every), "id": rid},
                    b"", self.config.default_slo))
            self._ins.replicas.set(
                sum(1 for r in self._replicas.values() if r.alive))
            self._cv.notify_all()
        self._ins.coldstart.labels(
            ready.get("cache_state", "cold")).observe(
            float(ready.get("warmup_s", 0.0)))
        rx.start()

    # ------------------------------------------------------------ rx per rep
    def _rx_loop(self, label: str, sock) -> None:
        # buffered frame source: one GIL release/reacquire per frame
        # instead of three — the reacquire under a many-threaded
        # dispatcher was profiled at ~ms of convoy per request
        stream = wire.reader(sock)
        budget = _frame_budget_s()
        # rx occupancy: seconds spent PROCESSING frames vs blocked in
        # recv, accumulated per dispatcher shard.  busy/wall is the
        # shard's rx-loop busy fraction — the saturation bench reads it
        # to prove the dispatcher (not the load generator or replicas)
        # is/isn't the ceiling (docs/observability.md).
        busy = self._ins.shard_rx_busy.labels(self._shard_label)
        t_resume = 0.0
        while True:
            if t_resume:
                busy.inc(time.monotonic() - t_resume)
            try:
                header, payload = wire.recv_frame(stream, budget_s=budget,
                                                  peer=label)
            except (wire.WireError, OSError) as e:
                if isinstance(e, wire.WireCorruptError):
                    # corrupt replica->dispatcher frame: the death path
                    # below IS the quarantine — record it as one (the
                    # replica-receive direction counts its own side)
                    from ..reliability import integrity as _integrity

                    _integrity.quarantined("wire")
                    _flight.record("fault", "fleet.wire_corrupt",
                                   replica=label)
                self._on_replica_death(label, e)
                return
            t_resume = time.monotonic()
            rep_rx = self._replicas.get(label)
            if rep_rx is not None:
                # any frame proves the replica end-to-end alive: stamp the
                # liveness clock (GIL-atomic float store, no lock needed)
                rep_rx.last_rx = time.monotonic()
            op = header.get("op")
            if op == wire.PONG:
                self._on_pong(label, header)
                continue
            if op == wire.TELEMETRY:
                # unsolicited shipment from the replica's serve loop: it
                # does NOT complete the in-flight request — ingest and go
                # straight back to the socket
                self._ingest_telemetry(label, payload)
                continue
            if op == wire.FEEDBACK:
                # unsolicited like telemetry: a sampled request's features
                # + served scores for the online loop; never completes the
                # in-flight request
                self._ingest_feedback(label, header, payload)
                continue
            if op == "quarantine":
                # the replica's loaded arena checksum diverged: it fences
                # itself and dies right after this frame.  Record WHY so
                # the imminent death path (EOF on this socket) reads as a
                # quarantine, not an unexplained crash; in-flight work
                # reroutes through the normal death machinery.
                reason = str(header.get("error", "arena checksum diverged"))
                with self._cv:
                    rep = self._replicas.get(label)
                    if rep is not None:
                        rep.quarantined = reason
                    self.quarantined[label] = reason
                from ..reliability import integrity as _integrity

                _integrity.quarantined("arena")
                _flight.record("event", "fleet.replica_quarantined",
                               replica=label, error=reason)
                continue
            # one critical section per completion: free the replica AND
            # claim its next request.  The hot path never notifies the cv —
            # per-request notify_all wakes the housekeeping thread (which
            # polls every replica process) and convoys every rx thread on
            # the lock; profiled as the fleet=4 throughput collapse.
            nxt = None
            expired: List[_Request] = []
            with self._cv:
                rep = self._replicas.get(label)
                req = rep.in_flight if rep is not None else None
                if rep is not None:
                    rep.in_flight = None
                    if rep.alive and not self._closed:
                        # replica-bound control frames dispatch ahead of
                        # queued traffic (a swap must not starve behind a
                        # deep queue; predicts already on the wire keep
                        # their ordering — that IS the drain contract)
                        if rep.ctrl:
                            nxt = rep.ctrl.popleft()
                        elif self._breaker_free(rep, time.monotonic()):
                            nxt, expired = self._queue.pop(time.monotonic())
                        if nxt is not None:
                            rep.in_flight = nxt
                            if rep.breaker == "half_open":
                                rep.probe = True
            self._expire(expired)
            if nxt is not None:
                # next request on the wire BEFORE this result's caller is
                # woken: the replica computes while the client-side wake
                # and copy-out happen, instead of idling through them
                self._send(rep, nxt)
            if req is None or header.get("id") != req.id:
                continue  # late/unmatched frame (e.g. post-reroute twin)
            if op == "result":
                if req.header.get("op") == "predict" and req.t_send_ns:
                    # send->result latency for the EWMA/breaker and the
                    # hedge-budget quantile (stamped BEFORE the send, so
                    # tx-side link degradation counts against the replica)
                    self._net_observe(
                        label,
                        (time.perf_counter_ns() - req.t_send_ns) / 1e9)
                shape = tuple(int(x) for x in header["shape"])
                arr = np.frombuffer(payload, np.float32).reshape(shape)
                self._finish(req, arr)
            elif op == "ctrl_ok":
                self._finish_ctrl(req, header)
            else:
                etype = _ERR_TYPES.get(header.get("etype", ""), RuntimeError)
                self._fail(req, etype(header.get("error", "replica error")))

    def _label_rx_loop(self, source: str, sock) -> None:
        """One label-feed connection: decode each ``op="label"`` frame
        (trace id + float32 labels) and hand it to the registered sink —
        the online loop's FeedbackHub.label, whose bounded symmetric
        join counts every drop.  Best-effort like feedback ingest: a
        malformed frame or sink error is recorded and dropped, never
        fatal — the serving plane must not depend on a label producer."""
        stream = wire.reader(sock)
        budget = _frame_budget_s()
        while True:
            try:
                header, payload = wire.recv_frame(stream, budget_s=budget,
                                                  peer=source)
            except wire.WireError:
                break  # producer gone (EOF/corrupt/slow-loris): drop it
            except OSError as e:
                # same verdict, but a socket-level failure gets classified
                # (ENOSPC/EMFILE here would otherwise surface three
                # subsystems away as a mystery)
                _resources.note_os_error(e, "fleet.label_rx")
                break
            op = header.get("op")
            if op == "close":
                break
            if op != wire.LABEL:
                continue  # unknown op on a label feed: ignore
            self._ins.label_frames.inc()
            try:
                trace = header.get("trace")
                y = np.frombuffer(payload, np.float32)
            except (TypeError, ValueError) as e:
                _flight.record("fault", "fleet.label_decode",
                               source=source, error=str(e))
                continue
            with self._cv:
                sink = self._label_sink
            if sink is None:
                continue
            try:
                sink(trace, y)
            except Exception as e:  # a broken consumer must not kill rx
                _flight.record("fault", "fleet.label_sink",
                               source=source, error=str(e))
        try:
            sock.close()
        except OSError as e:
            _note_os(e, "fleet.sock_close", benign=_EBADF_ONLY)

    def _ingest_feedback(self, label: str, header: dict, payload) -> None:
        """One replica feedback frame: decode the (features, scores) pair
        and hand it to the registered sink.  Malformed frames and sink
        errors are dropped with a flight fault, never fatal — feedback is
        a best-effort measurement stream, the serving plane must not
        depend on its consumer."""
        try:
            R, F = (int(x) for x in header["shape"])
            X = np.frombuffer(payload[:R * F * 4],
                              np.float32).reshape(R, F)
            scores = np.frombuffer(payload[R * F * 4:], np.float32)
            oshape = header.get("oshape")
            if oshape:
                scores = scores.reshape([int(x) for x in oshape])
            model = str(header.get("model"))
            trace = header.get("trace")
        except (KeyError, TypeError, ValueError) as e:
            _flight.record("fault", "fleet.feedback_decode", replica=label,
                           error=str(e))
            return
        self._ins.feedback_frames.labels(model).inc()
        self._ins.feedback_rows.labels(model).inc(float(R))
        with self._cv:
            sink = self._feedback_sink
        if sink is None:
            return
        try:
            sink({"model": model, "trace": trace, "X": X,
                  "scores": scores, "replica": label})
        except Exception as e:  # a broken consumer must not kill rx
            _flight.record("fault", "fleet.feedback_sink", replica=label,
                           error=str(e))

    def _ingest_telemetry(self, label: str, payload) -> None:
        """One replica telemetry frame: retain the latest snapshot +
        flight ring under the replica's label and feed the merged view
        (snapshot, flight ring, and profiler stacks — ingest_payload
        keeps all three per source for /flight and the merged flame)."""
        try:
            data = json.loads(bytes(payload))
        except (ValueError, TypeError):
            return  # a malformed shipment is dropped, never fatal
        snap = data.get("snapshot")
        ring = data.get("flight") or []
        with self._cv:
            if snap:
                self._telemetry[label] = snap
            self._flight_rings[label] = ring
        _distributed.get_merged().ingest_payload(label, data)

    def _finish(self, req: _Request, arr: np.ndarray) -> None:
        req.state = "done"
        if req.future.set_running_or_notify_cancel():
            if req.hedge:
                # the twin beat the original to the shared future
                self._ins.hedge_wins.inc()
            req.future.set_result(arr)
            if _trace.active() and req.header.get("trace"):
                # dispatcher-side bracket of the whole request: with the
                # replica's own replica.execute event (same trace id) the
                # merged timeline shows dispatch -> queue -> execute ->
                # reply per request
                now = time.perf_counter_ns()
                _trace.emit("fleet.request", req.t_submit_ns,
                            now - req.t_submit_ns,
                            trace=req.header["trace"], model=req.model)
            # only delivered results count: an abandoned (caller-timed-out,
            # cancelled) request's latency would skew the histogram
            lat = time.monotonic() - req.t_submit
            # the request's trace id rides as a bucket exemplar: the
            # /metrics scrape names the exact request behind the window's
            # max latency per bucket ("what was the p99"), resolvable
            # against the flight recorder / merged chrome trace
            self._ins.latency.labels(req.model).observe(
                lat, exemplar=req.header.get("trace"))
            self._admit_ok()
            # per-version latency: explicit version from the header, else
            # the fleet's view of the model's active version — the
            # lifecycle comparator reads candidate vs incumbent from here
            v = req.header.get("version")
            if v is None:
                v = self._versions.get(req.model)
            if v is not None:
                self._ins.version_latency.labels(
                    req.model, str(v)).observe(lat)
        elif req.hedge or req.hedged:
            # the pair's other side already settled the caller: this
            # duplicate result is the waste a hedge knowingly pays for
            self._ins.hedge_wasted.inc()

    def _finish_ctrl(self, req: _Request, header: dict) -> None:
        """A replica acked a lifecycle control frame: the future carries
        the ack payload (aot hit/compile counts, seconds)."""
        req.state = "done"
        if req.future.set_running_or_notify_cancel():
            req.future.set_result(dict(header))

    def _fail(self, req: _Request, exc: BaseException) -> None:
        req.state = "done"
        if req.future.set_running_or_notify_cancel():
            req.future.set_exception(exc)

    # --------------------------------------------------- adaptive admission
    def _admit_pressure(self) -> None:
        """One overload signal (shed / expiry / replica death): AIMD
        multiplicative decrease; on the onto-the-floor edge, declare
        overload to the resource governor — the SLO brownout starts."""
        edge = self._admit.on_pressure()
        self._ins.admission_window.set(self._admit.limit())
        if edge:
            _resources.get_governor().degrade(
                "overload", "fleet admission window at floor")
            _resources.degraded_event(
                "fleet", "admission_floor", window=self._admit.limit())

    def _admit_ok(self) -> None:
        """One completed request: additive increase; on the recovered
        edge, lift the governor's overload level again."""
        recovered = self._admit.on_ok()
        self._ins.admission_window.set(self._admit.limit())
        if recovered:
            _resources.get_governor().restore("overload")

    def _expire(self, expired: List[_Request]) -> None:
        """Fail requests whose class deadline passed while queued."""
        for r in expired:
            self._ins.deadline.labels(r.slo.name).inc()
            self._admit_pressure()
            self._fail(r, TimeoutError(
                f"request {r.id} ({r.model}) expired in queue after "
                f"{r.slo.deadline_s}s (slo={r.slo.name})"))

    # ----------------------------------------------------------- death path
    def _on_replica_death(self, label: str, cause: BaseException) -> None:
        with self._cv:
            rep = self._replicas.pop(label, None)
            if rep is None:
                return
            closed = self._closed
            req = rep.in_flight
            rep.in_flight = None
            rep.alive = False
            ctrl_orphans = list(rep.ctrl)
            rep.ctrl.clear()
            self._ins.replicas.set(
                sum(1 for r in self._replicas.values() if r.alive))
            # a dead replica's breaker is moot: park the gauge at closed
            # so the label doesn't read as permanently ejected
            self._ins.breaker_state.labels(label).set(0.0)
            if (req is not None and not closed
                    and req.header.get("op") != "predict"):
                # a replica-bound control frame cannot reroute to a peer:
                # fail it — the broadcast layer tolerates this, because a
                # respawn reads the committed store state at startup
                ctrl_orphans.append(req)
                req = None
            if req is not None and not closed:
                # the dead replica's batch: requeue at the front (predict
                # is idempotent; the twin result from the corpse, if any,
                # is dropped by the id check in _rx_loop)
                req.tries += 1
                if req.tries <= 3:
                    self._queue.requeue_front(req)
                    self._ins.rerouted.inc()
                    req = None
            respawn = (not closed
                       and self._respawned < self.config.max_respawns)
            if respawn:
                self._respawned += 1
                n = self._respawned
            self._cv.notify_all()
        try:
            rep.sock and rep.sock.close()
        except OSError as e:
            _note_os(e, "fleet.sock_close", benign=_EBADF_ONLY)
        rc = rep.proc.poll()
        if not closed:
            # a real death is an overload signal too: the survivors
            # briefly have less capacity (a clean shutdown's EOFs are us
            # closing sockets, not pressure)
            self._admit_pressure()
        tail = stderr_tail(self._err_files.get(label, ""))
        if rep.quarantined:
            tail = f"[quarantined: {rep.quarantined}]\n{tail}"
        if not closed:
            # a real death gets a postmortem; a clean shutdown's EOFs are
            # us closing the sockets, not replicas dying
            dump_path = self._dump_replica_flight(label, rc)
            if dump_path:
                tail += f"\n[flight recorder: {dump_path}]"
            _flight.record("event", "fleet.replica_death", replica=label,
                           exit=rc if rc is not None else -1)
        with self._cv:
            self._failures.append((label, rc if rc is not None else -1,
                                   tail))
        for c in ctrl_orphans:
            self._fail(c, WorkerFailedError(
                f"replica {label} died before completing control op "
                f"{c.header.get('op')!r} (exit={rc}): {cause}",
                [(label, rc if rc is not None else -1, tail)]))
        if req is not None:
            self._fail(req, WorkerFailedError(
                f"request {req.id} lost to replica {label} "
                f"{req.tries} times (exit={rc}): {cause}",
                [(label, rc if rc is not None else -1, tail)]))
        if req is None and not closed:
            self._pump()  # the requeued request goes to a live replica now
        if respawn:
            self._ins.respawns.inc()
            self._spawn(f"{self._label_prefix}respawn{n}")
        elif not self._alive_or_pending():
            # fleet extinct: nothing will ever drain the queue — fail what
            # is queued AND mark the fleet so later submits fail fast
            # instead of queueing into a hang
            failures = list(self._failures)
            with self._cv:
                self._extinct = True
                dead = self._queue.drain()
                self._cv.notify_all()
            err = WorkerFailedError(
                "every fleet replica died and the respawn budget is spent",
                failures)
            for r in dead:
                self._fail(r, err)

    def _dump_replica_flight(self, label: str, rc) -> Optional[str]:
        """Postmortem for a dead replica, written DRIVER-side from the
        last telemetry shipment: the replica's recent flight ring plus
        its final registry snapshot — present even for SIGKILL, which
        leaves the corpse no chance to dump anything itself.  The path
        lands in :attr:`flight_dumps` and on the failure record."""
        with self._cv:
            ring = list(self._flight_rings.get(label, ()))
            snap = self._telemetry.get(label)
        path = os.path.join(_flight.dump_dir(),
                            f"flight_fleet_{label}_{os.getpid()}.json")
        try:
            tmp = f"{path}.tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump({"label": label, "exit": rc, "events": ring,
                           "snapshot": snap, "dumped_by": "dispatcher"},
                          fh)
            os.replace(tmp, path)
        except OSError as e:  # pragma: no cover - fs trouble must not
            _resources.note_os_error(e, "fleet.flight_dump")
            return None       # block the death path
        with self._cv:
            self._flight_dumps[label] = path
        return path

    @property
    def flight_dumps(self) -> Dict[str, str]:
        """label -> postmortem path for every dead replica; on a sharded
        fleet, merged across shards (prefixed labels never collide)."""
        if self._shards is not None:
            out: Dict[str, str] = {}
            for sh in self._shards:
                with sh._cv:
                    out.update(sh._flight_dumps)
            return out
        with self._cv:
            return dict(self._flight_dumps)

    def _alive_or_pending(self) -> bool:
        with self._cv:
            return any(r.proc.poll() is None or r.alive
                       for r in self._replicas.values())

    # ------------------------------------------------------------ dispatching
    def _dispatch_loop(self) -> None:
        """Housekeeping only: reap pre-ready crashes and run a periodic
        fallback pump.  The hot path never waits on this thread — requests
        go to replicas directly from the thread that created the work or
        the capacity (:meth:`_pump`), because a per-request hand-off
        through one scheduler thread costs two GIL/condvar wake hops per
        request and caps fleet throughput at single-replica rates."""
        while True:
            with self._cv:
                if self._closed:
                    return
                self._reap_locked()
                self._cv.wait(timeout=0.2)
                if self._closed:
                    return
            # governor tick: the fleet process's ONLY poll site — it is
            # what walks an errno-raised disk/fd level back down once
            # real headroom recovers (internally rate-limited), ending a
            # brownout instead of latching it for the process lifetime
            _resources.get_governor().poll(self._store_dir)
            self._net_tick()
            self._pump()

    def _pump(self) -> None:
        """Dispatch queued requests onto free replicas until one side runs
        dry.  Called wherever work or capacity appears: submit(), the rx
        loop on completion, the death path after a requeue, and the
        housekeeping loop.  Safe from any number of threads at once — the
        pop and the replica in_flight claim are one critical section, so
        two pumpers can never double-assign; the socket send runs outside
        the lock."""
        while True:
            with self._cv:
                if self._closed:
                    return
                now = time.monotonic()
                req, expired = (None, [])
                target = None
                free = [r for r in self._replicas.values()
                        if r.alive and r.in_flight is None]
                # replica-bound control frames first (they cannot be
                # served by any other replica and must not starve; the
                # breaker never gates them — an ejected replica still
                # takes lifecycle ops)
                for r in free:
                    if r.ctrl:
                        req = r.ctrl.popleft()
                        target = r
                        break
                if req is None and free:
                    admit = [r for r in free if self._breaker_free(r, now)]
                    if admit:
                        req, expired = self._queue.pop(now)
                        target = admit[0] if req is not None else None
                if req is not None:
                    target.in_flight = req
                    if target.breaker == "half_open":
                        target.probe = True
            self._expire(expired)
            if req is None:
                return
            self._send(target, req)

    def _send(self, rep: _Replica, req: _Request) -> None:
        try:
            spec = _faults.maybe_inject("fleet.dispatch")
        except _faults.FaultInjected as e:
            with self._cv:
                rep.in_flight = None
                self._cv.notify_all()
            self._fail(req, e)
            return
        if spec is not None and spec.kind == "drop_connection":
            # sever the chosen replica's socket (in_flight already carries
            # this request): the rx loop sees EOF and runs the death path,
            # which requeues the request onto a surviving replica
            try:
                rep.sock.shutdown(2)
            except OSError as e:
                # severing an already-dead socket is the point here
                _note_os(e, "fleet.sock_close", benign=_SHUTDOWN_BENIGN)
            return
        try:
            # stamp BEFORE the send: tx-side link degradation (jitter,
            # throttling) must count against the replica's measured
            # latency, or the breaker could never see a slow outbound link
            req.t_send_ns = time.perf_counter_ns()
            with rep.txlock:
                wire.send_frame(rep.sock, req.header, req.payload,
                                peer=rep.label)
            if req.header.get("op") == "predict":
                self._ins.requests.labels(req.model).inc()
                # per-shard throughput attribution: the bench divides
                # Δrows by wall to report rows/s per dispatcher shard
                self._ins.shard_requests.labels(self._shard_label).inc()
                shape = req.header.get("shape")
                if shape:
                    self._ins.shard_rows.labels(self._shard_label).inc(
                        float(shape[0]))
                if _trace.active() and req.header.get("trace"):
                    # queue-time bracket: submit -> on-the-wire (re-emitted
                    # per try when a reroute requeues the request)
                    _trace.emit("fleet.queue", req.t_submit_ns,
                                req.t_send_ns - req.t_submit_ns,
                                trace=req.header["trace"], model=req.model,
                                replica=rep.label)
        except OSError as e:
            self._on_replica_death(rep.label, e)

    # ------------------------------------- degraded-network survival plane
    def _set_breaker(self, rep: _Replica, state: str) -> None:
        """Transition a replica's circuit breaker (cv held): state,
        gauge, transition counter, flight event."""
        if rep.breaker == state:
            return
        rep.breaker = state
        rep.probe = False
        self._ins.breaker_transitions.labels(state).inc()
        self._ins.breaker_state.labels(rep.label).set(
            {"closed": 0.0, "open": 1.0, "half_open": 2.0}[state])
        _flight.record("event", "fleet.breaker", replica=rep.label,
                       state=state)

    def _breaker_free(self, rep: _Replica, now: float) -> bool:
        """Whether the breaker lets this replica take queued predicts
        (cv held).  Walks open -> half-open once the cooldown elapses;
        half-open admits at most ONE outstanding probe — the caller that
        claims the replica marks ``rep.probe``."""
        if self.config.breaker_latency_s <= 0:
            return True
        if rep.breaker == "open" and now >= rep.breaker_until:
            self._set_breaker(rep, "half_open")
        if rep.breaker == "open":
            return False
        if rep.breaker == "half_open" and rep.probe:
            return False
        return True

    def _net_observe(self, label: str, lat: float) -> None:
        """One send->result predict latency: feed the hedge-budget
        sample, update the replica's EWMA, and run the breaker state
        machine (docs/reliability.md "Degraded networks")."""
        thresh = self.config.breaker_latency_s
        with self._cv:
            self._lat_hist.append(lat)
            rep = self._replicas.get(label)
            if rep is None:
                return
            rep.ewma = lat if rep.ewma is None else (
                0.2 * lat + 0.8 * rep.ewma)
            if thresh <= 0:
                return
            if rep.breaker == "half_open":
                # this result IS the probe's verdict
                if lat <= thresh:
                    rep.ewma = lat  # the probe is the new baseline
                    self._set_breaker(rep, "closed")
                else:
                    rep.breaker_until = (time.monotonic()
                                         + self.config.breaker_cooldown_s)
                    self._set_breaker(rep, "open")
            elif rep.breaker == "closed" and rep.ewma > thresh:
                rep.breaker_until = (time.monotonic()
                                     + self.config.breaker_cooldown_s)
                self._set_breaker(rep, "open")

    def _on_pong(self, label: str, header: dict) -> None:
        """A replica answered a heartbeat: close out the matching ping,
        observe the application-level round trip, and — when the
        replica's breaker is waiting on a probe no traffic will ever
        send it — let the pong BE the probe.  This is a network breaker:
        the RTT rides the same degraded rx path a predict result would,
        and without it an ejected replica whose siblings absorb all
        traffic would stay ejected forever (readmission must not depend
        on starving the healthy replicas first)."""
        now = time.monotonic()
        with self._cv:
            rep = self._replicas.get(label)
            if rep is None:
                return
            try:
                t0 = rep.ping_sent.pop(int(header.get("seq", -1)), None)
            except (TypeError, ValueError):
                t0 = None
            rtt = (now - t0) if t0 is not None else None
            if (rtt is not None and self.config.breaker_latency_s > 0
                    and not rep.probe):
                if rep.breaker == "open" and now >= rep.breaker_until:
                    self._set_breaker(rep, "half_open")
                if rep.breaker == "half_open":
                    if rtt <= self.config.breaker_latency_s:
                        rep.ewma = rtt  # the probe is the new baseline
                        self._set_breaker(rep, "closed")
                    else:
                        rep.breaker_until = (
                            now + self.config.breaker_cooldown_s)
                        self._set_breaker(rep, "open")
        if rtt is not None:
            self._ins.hb_rtt.labels(label).observe(rtt)

    def _hedge_budget_locked(self) -> Optional[float]:
        """Quantile-derived hedge budget (cv held): the configured
        quantile of recent send->result latencies, floored at
        ``hedge_min_s``.  None = hedging off or not enough history yet
        (a cold fleet must not hedge off noise)."""
        q = self.config.hedge_quantile
        if q <= 0.0 or len(self._lat_hist) < 8:
            return None
        lats = sorted(self._lat_hist)
        idx = min(len(lats) - 1, int(q * len(lats)))
        return max(lats[idx], self.config.hedge_min_s)

    def _net_tick(self) -> None:
        """Degraded-network housekeeping, run from the dispatch loop's
        0.2s cadence: schedule heartbeat pings, declare half-open
        replicas dead (no pong AND no other frame past the deadline),
        and hedge in-flight predicts past the quantile budget onto free
        replicas.  All state decisions under the cv; every socket write
        outside it."""
        cfg = self.config
        now = time.monotonic()
        pings: List[_Replica] = []
        dead: List[str] = []
        hedges: List[Tuple[_Replica, _Request]] = []
        with self._cv:
            if self._closed:
                return
            for rep in self._replicas.values():
                if not rep.alive or rep.sock is None:
                    continue
                if (cfg.heartbeat_s > 0
                        and now - rep.last_ping >= cfg.heartbeat_s):
                    rep.last_ping = now
                    rep.ping_seq += 1
                    rep.ping_sent[rep.ping_seq] = now
                    pings.append(rep)
                if (cfg.heartbeat_timeout_s > 0 and rep.ping_sent
                        and (now - min(rep.ping_sent.values())
                             > cfg.heartbeat_timeout_s)
                        and now - rep.last_rx > cfg.heartbeat_timeout_s):
                    # half-open or wedged: the oldest ping went
                    # unanswered AND nothing else arrived either.  TCP
                    # keepalive cannot see this (the tx direction still
                    # works); EOF never comes (the process is alive).
                    dead.append(rep.label)
            budget = self._hedge_budget_locked()
            if budget is not None:
                spare = [r for r in self._replicas.values()
                         if r.alive and r.in_flight is None
                         and r.label not in dead
                         and self._breaker_free(r, now)]
                for rep in list(self._replicas.values()):
                    if not spare:
                        break  # hedging is bounded to spare capacity
                    req = rep.in_flight
                    if (req is None or rep.label in dead
                            or req.header.get("op") != "predict"
                            or req.hedge or req.hedged
                            or not req.t_send_ns):
                        continue
                    age = (time.perf_counter_ns() - req.t_send_ns) / 1e9
                    if age <= budget:
                        continue
                    # twin: fresh id (the rx id check drops whichever
                    # result loses), SHARED future (first settle wins —
                    # replicas are deterministic, so the winner's bytes
                    # equal the loser's and hedging stays bitwise-neutral)
                    twin_id = next(self._next_id)
                    hdr = dict(req.header)
                    hdr["id"] = twin_id
                    hdr["hedge"] = True  # replica skips feedback capture
                    twin = _Request(twin_id, req.model, hdr, req.payload,
                                    req.slo)
                    twin.future = req.future
                    twin.hedge = True
                    twin.state = "inflight"
                    req.hedged = True
                    tgt = spare.pop(0)
                    tgt.in_flight = twin
                    if tgt.breaker == "half_open":
                        tgt.probe = True
                    hedges.append((tgt, twin))
        for rep in pings:
            try:
                with rep.txlock:
                    wire.send_frame(rep.sock, {"op": wire.PING,
                                               "seq": rep.ping_seq},
                                    peer=rep.label)
            except OSError as e:
                self._on_replica_death(rep.label, e)
        for label in dead:
            _flight.record("fault", "fleet.half_open", replica=label)
            self._on_replica_death(label, TimeoutError(
                f"replica {label}: no pong and no frame within "
                f"{cfg.heartbeat_timeout_s}s (half-open or wedged link)"))
        for tgt, twin in hedges:
            self._ins.hedges.inc()
            _flight.record("event", "fleet.hedge", replica=tgt.label,
                           id=twin.id, model=twin.model)
            self._send(tgt, twin)

    # ------------------------------------------------------------------ API
    def submit(self, model: str, X=None, *, arrow=None,
               tenant: Optional[str] = None, output_margin: bool = False,
               version: Optional[int] = None) -> Future:
        """Queue one predict; returns a Future of the result rows.  Pass
        ``X`` (numpy, raw path) or ``arrow`` (pyarrow RecordBatch/Table —
        or pre-encoded IPC bytes, forwarded untouched)."""
        if (X is None) == (arrow is None):
            raise ValueError("pass exactly one of X= or arrow=")
        if self._shards is not None:
            # sharded front-end: pure-hash client-side partitioning —
            # the owning shard runs the WHOLE admission path (brownout,
            # AIMD window, shed, shadow) against its own state
            shard = self._shards[shard_of(model, tenant,
                                          len(self._shards))]
            return shard.submit(model, X, arrow=arrow, tenant=tenant,
                                output_margin=output_margin,
                                version=version)
        slo = self.config.resolve_slo(tenant)
        # resource-pressure brownout BEFORE any other work — including
        # the payload encode, which is exactly the CPU/memory cost a
        # degraded host cannot spare: under pressure (overload / memory /
        # disk / fd), low-SLO tenants are refused on the tenant name
        # alone — deterministic cutoff per governor level
        # (docs/reliability.md "Resource pressure & graceful
        # degradation"); higher classes keep their full service
        cutoff = _resources.get_governor().brownout_cutoff()
        if cutoff is not None and slo.priority < cutoff:
            self._ins.brownout.labels(slo.name).inc()
            fut: Future = Future()
            fut.set_exception(QueueFullError(
                f"browned out: resource pressure level "
                f"{_resources.get_governor().max_level()} sheds "
                f"slo={slo.name} (priority {slo.priority} < cutoff "
                f"{cutoff})"))
            return fut
        if X is not None:
            fields, payload = wire.encode_raw(np.asarray(X))
        elif isinstance(arrow, (bytes, bytearray, memoryview)):
            fields, payload = {"enc": wire.ARROW}, memoryview(arrow)
        else:
            fields, payload = wire.encode_arrow(arrow)
        # everything but the queue push happens outside the cv (the lock is
        # the fleet's one contended resource; hot-path critical sections
        # stay tiny and notify-free)
        rid = next(self._next_id)  # itertools.count is atomic
        header = dict(fields)
        # the request's trace id, born here and carried on the wire: the
        # replica tags its replica.execute event with it, so one merged
        # trace shows the whole dispatch->queue->execute->reply path
        header.update({"op": "predict", "id": rid, "model": model,
                       "margin": bool(output_margin),
                       "trace": f"{os.getpid():x}-{rid:x}"})
        if version is not None:
            header["version"] = int(version)
        req = _Request(rid, model, header, payload, slo)
        # the trace id rides on the future too: feedback capture keys its
        # samples off it, so a label producer can join labels to requests
        # (hub.label(fut.trace_id, y)) without a side channel
        req.future.trace_id = header["trace"]
        shadow_req = None
        with self._cv:
            if self._closed:
                raise RuntimeError("ServingFleet is closed")
            if not self._started:
                raise RuntimeError("ServingFleet.start() has not run")
            if self._extinct:
                raise WorkerFailedError(
                    "every fleet replica died and the respawn budget is "
                    "spent", list(self._failures))
            sh = self._shadow.get(model) if version is None else None
            if sh is not None:
                # deterministic 1-in-N selection (a counter, not a PRNG:
                # replayable, and exactly the configured fraction)
                sh["n"] += 1
                if sh["n"] % sh["every"] == 0 and cutoff is not None:
                    # any brownout level sheds the twin (priority -2^31
                    # < every cutoff): the discretionary duplicate load
                    # is the FIRST thing a degraded host stops paying
                    self._ins.brownout.labels(_SHADOW_SLO.name).inc()
                elif sh["n"] % sh["every"] == 0:
                    shadow_header = dict(header)
                    shadow_header["id"] = next(self._next_id)
                    shadow_header["version"] = sh["version"]
                    shadow_header["trace"] = header["trace"] + "-shadow"
                    # same payload buffer: the twin rides zero-copy too
                    shadow_req = _Request(shadow_header["id"], model,
                                          shadow_header, payload,
                                          _SHADOW_SLO)
            limit = self._admit.limit()
            victims = [self._queue.push(req, limit=limit)]
            if shadow_req is not None:
                victims.append(self._queue.push(shadow_req, limit=limit))
        if shadow_req is not None:
            self._attach_shadow(model, req, shadow_req)
        for victim in victims:
            if victim is None:
                continue
            self._ins.shed.labels(victim.slo.name).inc()
            self._admit_pressure()
            self._fail(victim, QueueFullError(
                f"fleet queue full (admission window {limit} of "
                f"{self.config.max_queue}); shed slo={victim.slo.name} "
                f"request {victim.id}"))
        self._pump()  # a free replica takes this request on OUR thread
        return req.future

    def predict(self, model: str, X, *, tenant: Optional[str] = None,
                output_margin: bool = False, version: Optional[int] = None,
                timeout: Optional[float] = None) -> np.ndarray:
        """Blocking predict through the fleet (numpy request path)."""
        slo = self.config.resolve_slo(tenant)
        fut = self.submit(model, X, tenant=tenant,
                          output_margin=output_margin, version=version)
        return self._wait(fut, timeout, slo, model)

    def predict_arrow(self, model: str, batch, *,
                      tenant: Optional[str] = None,
                      output_margin: bool = False,
                      version: Optional[int] = None,
                      timeout: Optional[float] = None) -> np.ndarray:
        """Blocking predict with an Arrow RecordBatch/Table (or IPC
        bytes): the zero-copy request path."""
        slo = self.config.resolve_slo(tenant)
        fut = self.submit(model, arrow=batch, tenant=tenant,
                          output_margin=output_margin, version=version)
        return self._wait(fut, timeout, slo, model)

    def _wait(self, fut: Future, timeout: Optional[float], slo: SLOClass,
              model: str) -> np.ndarray:
        if timeout is None:
            timeout = slo.deadline_s
        try:
            return fut.result(timeout=timeout)
        except FuturesTimeout:
            fut.cancel()
            self._ins.deadline.labels(slo.name).inc()
            raise TimeoutError(
                f"predict({model!r}) missed its {timeout}s deadline "
                f"(slo={slo.name})") from None

    # ----------------------------------------------------- lifecycle control
    @property
    def store_dir(self) -> Optional[str]:
        """The fleet's model-store directory (the lifecycle manager's
        publish target)."""
        return self._store_dir

    def _control_all(self, fields: Dict[str, Any],
                     timeout: float = 300.0) -> List[dict]:
        """Broadcast one control frame to every live replica and collect
        the acks.  A replica that DIES mid-broadcast is tolerated — its
        respawn reads the committed store state at startup and converges —
        but an error *reply* (bad version, refused retire) raises."""
        pending: List[Tuple[str, _Request]] = []
        fields = dict(fields)
        # one trace id per broadcast (lifecycle CycleReports reference it;
        # replicas log it with the applied control op)
        fields.setdefault(
            "trace", f"ctrl-{os.getpid():x}-{next(self._next_id):x}")
        _flight.record("event", f"fleet.{fields.get('op')}",
                       model=str(fields.get("model")),
                       version=fields.get("version"),
                       trace=fields["trace"])
        with self._cv:
            if not self._started or self._closed:
                raise RuntimeError("ServingFleet is not running")
            for rep in self._replicas.values():
                if not rep.alive:
                    continue
                rid = next(self._next_id)
                header = dict(fields)
                header["id"] = rid
                req = _Request(rid, str(fields.get("model", "?")), header,
                               b"", self.config.default_slo)
                rep.ctrl.append(req)
                pending.append((rep.label, req))
        if not pending:
            raise WorkerFailedError(
                "no live replica to broadcast to", list(self._failures))
        self._pump()
        acks: List[dict] = []
        for label, req in pending:
            try:
                acks.append(req.future.result(timeout=timeout))
            except WorkerFailedError:
                with self._cv:
                    gone = label not in self._replicas
                if not gone:  # pragma: no cover - defensive
                    raise
        return acks

    def load_version(self, model: str, version: int,
                     timeout: float = 300.0,
                     trace: Optional[str] = None) -> List[dict]:
        """Double-buffer a published store version onto every replica:
        registry entry, arch-keyed AOT warm attach, fast path, NaN warm
        pass — all while the incumbent keeps serving.  Returns per-replica
        acks carrying aot_hits/aot_compiled (a same-architecture
        continuation shows hits, not compiles)."""
        if self._shards is not None:
            trace = trace or self._broadcast_trace()
            return [a for sh in self._shards
                    for a in sh.load_version(model, version, timeout,
                                             trace)]
        fields = {"op": "load", "model": model, "version": int(version)}
        if trace:
            fields["trace"] = trace
        return self._control_all(fields, timeout)

    def activate_version(self, model: str, version: int,
                         timeout: float = 300.0,
                         trace: Optional[str] = None) -> List[dict]:
        """Repoint ``model``'s unversioned traffic at ``version``.

        Durably commits the store manifest FIRST (``set_active``), then
        broadcasts: a replica that dies between the two reads the
        committed version when it respawns, so the fleet converges on the
        new version through any single failure.  Per replica the activate
        frame is serialized after every previously dispatched predict —
        nothing is dropped, and no request observes a half-swap."""
        from .modelstore import ModelStore

        if self._shards is not None:
            # each shard runs the full commit-first sequence itself;
            # set_active is idempotent under the manifest flock, and the
            # per-shard _versions update keeps each shard's respawn
            # resync frames correct
            trace = trace or self._broadcast_trace()
            return [a for sh in self._shards
                    for a in sh.activate_version(model, version, timeout,
                                                 trace)]
        ModelStore(self._store_dir).set_active(model, int(version))
        with self._cv:
            # fleet view moves WITH the durable commit, before the
            # broadcast: a replica respawning while the acks are collected
            # builds its ready-time resync frames from _versions, and a
            # stale entry here would regress it to the old version
            self._versions[model] = int(version)
        fields = {"op": "activate", "model": model, "version": int(version)}
        if trace:
            fields["trace"] = trace
        return self._control_all(fields, timeout)

    def retire_version(self, model: str, version: int,
                       timeout: float = 300.0,
                       trace: Optional[str] = None) -> List[dict]:
        """Drop a non-active version from every replica.  The retire frame
        rides each replica's serialized connection, so it executes only
        after every predict dispatched before it has drained; replicas
        refuse to retire the active version."""
        if self._shards is not None:
            trace = trace or self._broadcast_trace()
            return [a for sh in self._shards
                    for a in sh.retire_version(model, version, timeout,
                                               trace)]
        fields = {"op": "retire", "model": model, "version": int(version)}
        if trace:
            fields["trace"] = trace
        return self._control_all(fields, timeout)

    def _broadcast_trace(self) -> str:
        """One trace id shared by a sharded broadcast's per-shard legs,
        so lifecycle CycleReports and replica logs correlate the whole
        fan-out as one operation."""
        return f"ctrl-{os.getpid():x}-{next(self._next_id):x}"

    def active_version(self, model: str) -> Optional[int]:
        if self._shards is not None:
            return self._shards[0].active_version(model)
        with self._cv:
            return self._versions.get(model)

    def scrub_replicas(self, timeout: float = 300.0) -> List[dict]:
        """Broadcast an on-demand arena scrub: every live replica
        re-verifies each RESIDENT version's checksum against the store
        meta (the same check its periodic ``XGBOOST_TPU_ARENA_SCRUB_S``
        tick runs).  Healthy replicas ack ``{"verified": n}``; a replica
        whose loaded checksum diverges sends an ``op="quarantine"`` frame
        and dies — its in-flight batch reroutes and :attr:`quarantined`
        records the reason.  Riding the serialized connection means the
        scrub drains behind every predict dispatched before it."""
        if self._shards is not None:
            return [a for sh in self._shards
                    for a in sh.scrub_replicas(timeout)]
        return self._control_all({"op": "scrub", "model": "*"}, timeout)

    def quarantined_replicas(self) -> Dict[str, str]:
        """label -> reason for every self-quarantined replica (retained
        after death)."""
        if self._shards is not None:
            out: Dict[str, str] = {}
            for sh in self._shards:
                out.update(sh.quarantined_replicas())
            return out
        with self._cv:
            return dict(self.quarantined)

    # ------------------------------------------------------ feedback capture
    def set_sampling(self, model: str, every: int,
                     timeout: float = 300.0) -> List[dict]:
        """Broadcast the feedback-capture rate for ``model``: every live
        replica samples 1-in-``every`` of its unversioned requests
        (deterministically, keyed off the request-id half of the trace id)
        and ships features + served scores back as ``op="feedback"``
        frames.  ``every=0`` turns capture off.  Respawned replicas are
        resynced like versions, so the configured rate survives deaths."""
        every = int(every)
        if every < 0:
            raise ValueError(f"every must be >= 0, got {every}")
        if self._shards is not None:
            return [a for sh in self._shards
                    for a in sh.set_sampling(model, every, timeout)]
        with self._cv:
            if every > 0:
                self._sampling[model] = every
            else:
                self._sampling.pop(model, None)
        return self._control_all(
            {"op": "sample", "model": model, "every": every}, timeout)

    def set_feedback_sink(self, sink) -> None:
        """Register the driver-side consumer of decoded feedback records
        (dicts with model/trace/X/scores/replica), called on rx threads.
        ``None`` unregisters.  Sink exceptions are contained (flight
        fault), not propagated into the rx loop."""
        if self._shards is not None:
            for sh in self._shards:
                sh.set_feedback_sink(sink)
            return
        with self._cv:
            self._feedback_sink = sink

    def sampling_rate(self, model: str) -> int:
        """The configured feedback-capture rate (0 = off)."""
        if self._shards is not None:
            return self._shards[0].sampling_rate(model)
        with self._cv:
            return self._sampling.get(model, 0)

    def set_label_sink(self, sink) -> None:
        """Register the consumer for ``op="label"`` frames arriving over
        label-feed connections (called ``sink(trace, y)`` on the feed's
        rx thread).  The online loop registers ``FeedbackHub.label``
        here, so labels produced in another process/host join the same
        bounded symmetric join as in-process ones.  ``None``
        unregisters; sink exceptions are contained like feedback's."""
        if self._shards is not None:
            for sh in self._shards:
                sh.set_label_sink(sink)
            return
        with self._cv:
            self._label_sink = sink

    def label_endpoint(self) -> Tuple[str, int]:
        """(host, port) a label producer connects to — the fleet's frame
        listener.  Open the channel with :func:`wire.label_feed` and
        stream labels with :func:`wire.send_label`.  On a sharded fleet
        this is shard 0's listener (every shard accepts label feeds and
        the sink is fanned out, so any shard's endpoint works)."""
        if self._shards is not None:
            return self._shards[0].label_endpoint()
        if self._listener is None:
            raise RuntimeError("fleet not started: no listener yet")
        host, port = self._listener.getsockname()[:2]
        return str(host), int(port)

    # ------------------------------------------------------- shadow scoring
    def set_shadow(self, model: str, version: int,
                   fraction: float) -> None:
        """Mirror a deterministic ``fraction`` of ``model``'s unversioned
        traffic onto candidate ``version`` (which must be loaded).  The
        twin's result never reaches a caller; the comparator feeds
        ``xtb_lifecycle_shadow_*`` and per-version latency series."""
        if not 0.0 < fraction <= 1.0:
            raise ValueError(f"shadow fraction must be in (0, 1], "
                             f"got {fraction}")
        if self._shards is not None:
            # a model's traffic spans shards (tenant is part of the
            # routing key): every shard mirrors its own slice, stats
            # merge on read
            for sh in self._shards:
                sh.set_shadow(model, version, fraction)
            return
        every = max(1, round(1.0 / fraction))
        with self._cv:
            self._shadow[model] = {
                "version": int(version), "every": every, "n": 0,
                "pairs": 0, "failures": 0, "sum_div": 0.0, "max_div": 0.0,
                "sum_ks": 0.0, "max_ks": 0.0,
                "sum_psi": 0.0, "max_psi": 0.0,
                "sum_cal": 0.0, "max_cal": 0.0,
            }

    @staticmethod
    def _shadow_summary(sh: dict) -> dict:
        pairs = sh["pairs"]
        return {"pairs": pairs, "failures": sh["failures"],
                "mean_div": (sh["sum_div"] / pairs) if pairs else 0.0,
                "max_div": sh["max_div"],
                "mean_ks": (sh["sum_ks"] / pairs) if pairs else 0.0,
                "max_ks": sh["max_ks"],
                "mean_psi": (sh["sum_psi"] / pairs) if pairs else 0.0,
                "max_psi": sh["max_psi"],
                "mean_cal": (sh["sum_cal"] / pairs) if pairs else 0.0,
                "max_cal": sh["max_cal"]}

    @staticmethod
    def _merge_shadow_raw(raws: List[dict]) -> Optional[dict]:
        """Fold per-shard shadow accumulators into one: sums add, maxes
        max — the summary derives means from the folded sums."""
        if not raws:
            return None
        out = dict(raws[0])
        for r in raws[1:]:
            for k in ("pairs", "failures", "sum_div", "sum_ks",
                      "sum_psi", "sum_cal"):
                out[k] += r[k]
            for k in ("max_div", "max_ks", "max_psi", "max_cal"):
                out[k] = max(out[k], r[k])
        return out

    def clear_shadow(self, model: str) -> Optional[dict]:
        """Stop mirroring; returns the accumulated comparator stats
        (pairs, failures, mean/max divergence and KS) or None if never
        set.  On a sharded fleet the per-shard accumulators merge into
        one summary."""
        if self._shards is not None:
            raws = []
            for shard in self._shards:
                with shard._cv:
                    raw = shard._shadow.pop(model, None)
                if raw is not None:
                    raws.append(raw)
            merged = self._merge_shadow_raw(raws)
            return None if merged is None else self._shadow_summary(merged)
        with self._cv:
            sh = self._shadow.pop(model, None)
        if sh is None:
            return None
        return self._shadow_summary(sh)

    def shadow_stats(self, model: str) -> Optional[dict]:
        if self._shards is not None:
            raws = []
            for shard in self._shards:
                with shard._cv:
                    raw = shard._shadow.get(model)
                    if raw is not None:
                        raws.append(dict(raw))
            merged = self._merge_shadow_raw(raws)
            return None if merged is None else self._shadow_summary(merged)
        with self._cv:
            sh = self._shadow.get(model)
            if sh is None:
                return None
            return self._shadow_summary(sh)

    def _attach_shadow(self, model: str, primary: _Request,
                       shadow: _Request) -> None:
        """Compare the pair once BOTH futures settle (runs on whichever rx
        thread finishes second; cheap: one mean-abs-diff)."""
        remaining = [2]
        lock = threading.Lock()

        def done(_fut):
            with lock:
                remaining[0] -= 1
                if remaining[0]:
                    return
            self._compare_shadow(model, primary, shadow)

        primary.future.add_done_callback(done)
        shadow.future.add_done_callback(done)

    def _compare_shadow(self, model: str, primary: _Request,
                        shadow: _Request) -> None:
        sh_live = None
        try:
            a = np.asarray(primary.future.result(timeout=0), np.float64)
            b = np.asarray(shadow.future.result(timeout=0), np.float64)
            if a.shape == b.shape:
                div = float(np.mean(np.abs(a - b)))
                ks = _ks_stat(a, b)
                psi = _psi(a, b)
                cal = _calibration_gap(a, b)
            else:
                div = ks = psi = cal = float("inf")
        except BaseException:
            self._ins.shadow_failures.labels(model).inc()
            with self._cv:
                sh_live = self._shadow.get(model)
                if sh_live is not None:
                    sh_live["failures"] += 1
            return
        self._ins.shadow_requests.labels(model).inc()
        self._ins.shadow_divergence.labels(model).observe(div)
        self._ins.shadow_ks.labels(model).observe(min(ks, 1.0))
        self._ins.shadow_psi.labels(model).observe(
            min(psi, _PSI_BUCKETS[-1] * 10))
        self._ins.shadow_calibration.labels(model).observe(
            min(cal, _SHADOW_BUCKETS[-1] * 10))
        with self._cv:
            sh_live = self._shadow.get(model)
            if sh_live is not None:
                sh_live["pairs"] += 1
                sh_live["sum_div"] += div
                sh_live["max_div"] = max(sh_live["max_div"], div)
                sh_live["sum_ks"] += ks
                sh_live["max_ks"] = max(sh_live["max_ks"], ks)
                sh_live["sum_psi"] += psi
                sh_live["max_psi"] = max(sh_live["max_psi"], psi)
                sh_live["sum_cal"] += cal
                sh_live["max_cal"] = max(sh_live["max_cal"], cal)

    # ---------------------------------------------------------------- admin
    def replica_info(self) -> List[dict]:
        """Ready-frame info per live replica (warmup_s, aot hit/compile
        counts, cache_state) — the cold-start telemetry."""
        if self._shards is not None:
            return [info for sh in self._shards for info in sh.replica_info()]
        with self._cv:
            return [dict(r.ready_info) for r in self._replicas.values()
                    if r.alive and r.ready_info]

    def alive_replicas(self) -> int:
        if self._shards is not None:
            return sum(sh.alive_replicas() for sh in self._shards)
        with self._cv:
            return sum(1 for r in self._replicas.values() if r.alive)

    def queue_depth(self) -> int:
        if self._shards is not None:
            return sum(sh.queue_depth() for sh in self._shards)
        with self._cv:
            return len(self._queue)

    def _reap_locked(self) -> None:
        """Catch replicas that died without a socket event (pre-connect
        crash, kill -9 before EOF surfaces).  Caller holds the cv."""
        dead = [r.label for r in self._replicas.values()
                if r.proc.poll() is not None and not r.alive
                and r.sock is None]
        for label in dead:
            # run the death path without the lock held
            threading.Thread(target=self._on_replica_death,
                             args=(label, RuntimeError(
                                 "replica exited before ready")),
                             daemon=True).start()

    def close(self) -> None:
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
        if self._shards is not None:
            # shards first (they own the sockets and subprocesses), in
            # parallel — each is an independent single-shard fleet
            ts = [threading.Thread(target=sh.close, daemon=True)
                  for sh in self._shards]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if self._tmp_store and self._store_dir:
                import shutil

                shutil.rmtree(self._store_dir, ignore_errors=True)
            return
        self._shutdown()

    def _shutdown(self) -> None:
        with self._cv:
            self._closed = True
            dead = self._queue.drain()
            reps = list(self._replicas.values())
            for rep in reps:  # pending control frames cannot complete now
                dead.extend(rep.ctrl)
                rep.ctrl.clear()
            self._cv.notify_all()
        err = RuntimeError("ServingFleet closed")
        for r in dead:
            self._fail(r, err)
        if self._sched_thread is not None:
            self._sched_thread.join(timeout=5)
        for rep in reps:
            if rep.sock is not None:
                try:
                    with rep.txlock:
                        wire.send_frame(rep.sock, {"op": "close"})
                except OSError as e:
                    _note_os(e, "fleet.shutdown",
                             benign=_SHUTDOWN_BENIGN)
        deadline = time.monotonic() + 10
        for rep in reps:
            while rep.proc.poll() is None and time.monotonic() < deadline:
                time.sleep(0.05)
            if rep.proc.poll() is None:
                rep.proc.kill()
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError as e:
                _note_os(e, "fleet.shutdown", benign=_EBADF_ONLY)
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5)
        for rep in reps:
            if rep.sock is not None:
                try:
                    rep.sock.close()
                except OSError as e:
                    _note_os(e, "fleet.sock_close", benign=_EBADF_ONLY)
        for path in self._err_files.values():
            try:
                os.unlink(path)
            except FileNotFoundError:
                pass
            except OSError as e:
                _resources.note_os_error(e, "fleet.shutdown")
        if self._tmp_store and self._store_dir:
            import shutil

            shutil.rmtree(self._store_dir, ignore_errors=True)

    def __enter__(self) -> "ServingFleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()
