"""TelemetryCallback: per-round phase timings, tree stats, and compile
accounting as an inspectable history.

A TrainingCallback (callback.py contract) that reads the round's span
records (``spans.recent``) and the compile counter at every boosting round,
and the committed model for structural stats — so a training run leaves a
round-by-round record of where the time went and whether any round
retraced, without touching the training loop itself::

    cb = TelemetryCallback()
    xtb.train(params, d, 10, callbacks=[cb])
    cb.history[3]["phases"]["update.update_tree"]  # seconds in round 3
    cb.history[3]["dispatches"]                    # device programs launched
    cb.history[3]["host_syncs"]                    # waits and copies to host
    cb.history[3]["trees"][0]["leaves"]
    cb.history[3]["account"]["self_ns"]            # the period, partitioned
    cb.compiles_steady                             # SLO: 0 after round 0

``account`` is ``spans.round_account``'s entry of the round: its period (to
the next round's opening) split into self times by span that sum to it, with
the collector's and the clocks' shares beside them.  A round's period is over
only when the next round has run, so ``history[i]["account"]`` appears one
round late (the last round's in ``after_training``).

Round 0 is the warm-up round (every level program traces there); compiles
in later rounds are steady-state retraces and feed the registry counter
``xtb_compiles_steady{scope="train"}`` — the same no-retrace SLO gauge the
serving engine keeps (serving/metrics.py), scoped per subsystem.  A load
from the persistent compilation cache is not a compile (compile.py).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from ..callback import TrainingCallback
from . import compile as _compile
from . import flight, spans
from .registry import get_registry

__all__ = ["TelemetryCallback"]

# the spans that launch device work, and those that wait for it or copy from it
_DISPATCH_SPANS = ("grow.build_hist+eval_split", "update.gradient",
                   "grow.margin")
_SYNC_SPANS = ("grow.wait_device", "grow.to_host")


class TelemetryCallback(TrainingCallback):
    """Records per-round telemetry into ``self.history`` (list of dicts).

    Parameters
    ----------
    enable_spans : bool
        Turn the span flag on in before_training (default True), so that
        the registry histogram ``xtb_phase_seconds`` and a configured JSONL
        trace are fed too.  ``history`` does not need it: it reads the ring.
        The flag is left as-is on after_training (process-wide state;
        flipping it back could disable a concurrent consumer's spans).
    straggler : bool
        Distributed only: allgather every rank's round wall + collective
        wait at each round boundary and record a straggler report
        (``history[i]["straggler"]``: per-rank walls, max/min rank,
        spread).  This ADDS one collective per round, so it must be
        enabled on EVERY rank or the job wedges — and it is not for
        elastic runs (the extra gather shifts the relay seq numbering a
        regroup replays).  Default off.
    """

    def __init__(self, enable_spans: bool = True,
                 straggler: bool = False) -> None:
        self.enable_spans = enable_spans
        self.straggler = straggler
        self.history: List[Dict[str, Any]] = []
        self.compiles_warmup = 0
        self.compiles_steady = 0
        self._seq0 = 0
        self._coll0: Dict[Any, Any] = {}
        self._compiles0 = 0
        self._t0 = 0.0
        self._ntrees0 = 0
        self._warm_round: Optional[int] = None  # first round of current run
        self._run0 = 0  # where the current run's rounds begin in history
        self._steady_counter = None

    # ------------------------------------------------- TrainingCallback API
    def before_training(self, model):
        if self.enable_spans and not spans.enabled():
            spans.enable()
        self._ntrees0 = len(getattr(model, "trees", ()))
        # new training run: its first round is warm-up again, even when the
        # callback is reused across train() calls (each run compiles its own
        # level programs; lifetime history must not reclassify them steady)
        self._warm_round = None
        return model

    def after_training(self, model):
        self._account()
        return model

    def before_iteration(self, model, epoch: int, evals_log) -> bool:
        self._seq0 = flight.seq()
        self._coll0 = self._coll_sums()
        self._compiles0 = _compile.compiles_total()
        self._t0 = time.perf_counter()
        return False

    def after_iteration(self, model, epoch: int, evals_log) -> bool:
        seconds = time.perf_counter() - self._t0
        # this round's spans: a round the ring has begun to overwrite (more
        # spans than the ring holds) reads as no phases, never as some
        phases: Dict[str, Dict[str, Any]] = {}
        dispatches = host_syncs = 0
        for r in spans.recent(round_from=epoch):
            if r["round"] != epoch or r["seq"] < self._seq0:
                continue
            p = phases.setdefault(r["name"], {"seconds": 0.0, "count": 0})
            p["seconds"] += r["dur_ns"] / 1e9
            p["count"] += 1
            dispatches += r["name"] in _DISPATCH_SPANS
            if r["name"] in _SYNC_SPANS:
                host_syncs += r.get("copies", 1)
        compiles = _compile.compiles_total() - self._compiles0
        trees = self._tree_stats(model)
        rec: Dict[str, Any] = {
            "round": int(epoch),
            "seconds": seconds,
            "phases": phases,
            "dispatches": dispatches,
            "host_syncs": host_syncs,
            "compiles": int(compiles),
            "trees": trees,
        }
        coll = self._coll_delta(self._coll0)
        if coll["count"]:
            rec["coll_wait"] = coll
        self._round_boundary(rec, seconds, coll)
        if self._warm_round is None:
            self._warm_round = epoch
            self._run0 = len(self.history)
        if compiles:
            if epoch == self._warm_round:  # first round of THIS run
                self.compiles_warmup += compiles
            else:
                self.compiles_steady += compiles
                if self._steady_counter is None:
                    self._steady_counter = get_registry().counter(
                        "xtb_compiles_steady",
                        "backend compiles after warm-up (SLO: 0)",
                        ("scope",)).labels("train")
                self._steady_counter.inc(compiles)
        self.history.append(rec)
        self._account()
        return False

    # ------------------------------------------------------------ internals
    def _account(self) -> None:
        """Give this run's rounds whose period is over their account (a
        period is over one round late: the last two entries can wait)."""
        first = max(self._run0, len(self.history) - 2)
        waiting = {rec["round"]: rec for rec in self.history[first:]
                   if "account" not in rec}
        if waiting:
            for acct in spans.round_account(min(waiting)):
                if acct["round"] in waiting:
                    waiting[acct["round"]]["account"] = acct

    @staticmethod
    def _coll_sums() -> Dict[Any, Any]:
        """Current (op, rank) -> (count, seconds) of the collective-wait
        histogram (empty for single-process runs that never registered
        it)."""
        from .registry import get_registry

        hist = get_registry().get("xtb_coll_wait_seconds")
        return hist.snapshot_sums() if hist is not None else {}

    def _coll_delta(self, base: Dict[Any, Any]) -> Dict[str, float]:
        total_s, total_n = 0.0, 0
        for key, (n, s) in self._coll_sums().items():
            n0, s0 = base.get(key, (0, 0.0))
            total_s += s - s0
            total_n += n - n0
        return {"seconds": total_s, "count": int(total_n)}

    def _round_boundary(self, rec: Dict[str, Any], seconds: float,
                        coll: Dict[str, float]) -> None:
        """Distributed observability at the round boundary: rate-limited
        snapshot ship to the tracker, and the optional cross-rank straggler
        report (one extra allgather).  (The ring has the round as its
        ``train.round`` span record.)"""
        from . import distributed

        try:
            distributed.ship_to_tracker()
        except Exception:  # pragma: no cover - shipping is best-effort
            pass
        if not self.straggler:
            return
        from .. import collective

        if not collective.is_distributed():
            return
        import numpy as np

        walls = collective.allgather(
            np.asarray([seconds, coll["seconds"]], np.float64))
        round_walls = [float(w) for w in walls[:, 0]]
        rec["straggler"] = {
            "walls": round_walls,
            "coll_wait": [float(w) for w in walls[:, 1]],
            "max_rank": int(np.argmax(walls[:, 0])),
            "min_rank": int(np.argmin(walls[:, 0])),
            "spread_s": float(max(round_walls) - min(round_walls)),
        }

    def _tree_stats(self, model) -> List[Dict[str, int]]:
        """Stats of the trees committed since the last look.  cv() hands the
        callbacks an aggregate stand-in without .trees — record nothing."""
        trees = getattr(model, "trees", None)
        if trees is None:
            return []
        out = []
        for t in trees[self._ntrees0:]:
            out.append({
                "nodes": int(t.n_nodes),
                "leaves": int(t.num_leaves),
                "depth": int(t.max_depth),
            })
        self._ntrees0 = len(trees)
        return out
