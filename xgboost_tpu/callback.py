"""Training callbacks (reference: python-package/xgboost/callback.py).

Same contract as the reference: ``TrainingCallback`` subclasses get
before/after-iteration hooks with an ``evals_log`` history;
``CallbackContainer`` drives them from train()/cv() (callback.py:149).
"""
from __future__ import annotations

import collections
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

_Score = Union[float, Tuple[float, float]]
_EvalsLog = Dict[str, Dict[str, List[_Score]]]


class TrainingCallback:
    """(reference: callback.py:51)"""

    def before_training(self, model):
        return model

    def after_training(self, model):
        return model

    def before_iteration(self, model, epoch: int, evals_log: _EvalsLog) -> bool:
        return False

    def after_iteration(self, model, epoch: int, evals_log: _EvalsLog) -> bool:
        """Return True to stop training."""
        return False

    # checkpoint/resume protocol (reliability/checkpoint.py): stateful
    # callbacks override both so an interrupted run resumes with the same
    # decisions (EarlyStopping patience, scheduler position, ...) as an
    # uninterrupted one.  State must be JSON-serializable.
    def state_dict(self) -> Optional[dict]:
        return None

    def load_state(self, state: dict) -> None:
        pass


class CallbackContainer:
    """Driver for a list of callbacks (reference: callback.py:149)."""

    def __init__(self, callbacks: Sequence[TrainingCallback], metric=None,
                 output_margin: bool = True, is_cv: bool = False):
        self.callbacks = list(callbacks)
        self.metric = metric
        self.history: _EvalsLog = collections.OrderedDict()
        self.is_cv = is_cv

    def before_training(self, model):
        for cb in self.callbacks:
            model = cb.before_training(model)
        return model

    def after_training(self, model):
        for cb in self.callbacks:
            model = cb.after_training(model)
        return model

    def before_iteration(self, model, epoch, dtrain, evals) -> bool:
        return any(cb.before_iteration(model, epoch, self.history) for cb in self.callbacks)

    def update_history(self, eval_str: str) -> None:
        # parse "[i]\tname-metric:v\t..." into history
        parts = eval_str.strip().split("\t")[1:]
        for p in parts:
            key, v = p.rsplit(":", 1)
            name, metric = key.split("-", 1)
            self.history.setdefault(name, collections.OrderedDict()).setdefault(
                metric, []
            ).append(float(v))

    def after_iteration(self, model, epoch, dtrain, evals) -> bool:
        if evals:
            from .telemetry.spans import wait_span

            with wait_span("eval.eval_set"):
                msg = model.eval_set(evals, epoch, feval=self.metric)
            self.update_history(msg)
        return any(cb.after_iteration(model, epoch, self.history) for cb in self.callbacks)


class LearningRateScheduler(TrainingCallback):
    """(reference: callback.py:272)"""

    def __init__(self, learning_rates: Union[Callable[[int], float], Sequence[float]]):
        if callable(learning_rates):
            self.fn = learning_rates
        else:
            rates = list(learning_rates)
            self.fn = lambda epoch: rates[epoch]

    def after_iteration(self, model, epoch, evals_log) -> bool:
        return False

    def before_iteration(self, model, epoch, evals_log) -> bool:
        model.set_param("eta", self.fn(epoch))
        return False


class EarlyStopping(TrainingCallback):
    """(reference: callback.py:311) — stop when the watched metric stops improving."""

    def __init__(self, rounds: int, metric_name: Optional[str] = None,
                 data_name: Optional[str] = None, maximize: Optional[bool] = None,
                 save_best: bool = False, min_delta: float = 0.0):
        self.rounds = rounds
        self.metric_name = metric_name
        self.data_name = data_name
        self.maximize = maximize
        self.save_best = save_best
        self.min_delta = min_delta
        self.stopping_history: _EvalsLog = {}
        self.current_rounds = 0
        self.best_scores: List[float] = []

    _MAXIMIZE_METRICS = ("auc", "aucpr", "map", "ndcg", "pre")

    def _is_maximize(self, metric: str) -> bool:
        if self.maximize is not None:
            return self.maximize
        base = metric.split("@")[0].split(":")[0]
        return base in self._MAXIMIZE_METRICS

    def after_iteration(self, model, epoch, evals_log) -> bool:
        if not evals_log:
            return False
        data = self.data_name or list(evals_log.keys())[-1]
        log = evals_log[data]
        metric = self.metric_name or list(log.keys())[-1]
        score = log[metric][-1]
        if isinstance(score, (tuple, list)):  # cv (mean, std): stop on mean
            score = score[0]
        maximize = self._is_maximize(metric)
        if not self.best_scores:
            improved = True
        elif maximize:
            improved = score > self.best_scores[-1] + self.min_delta
        else:
            improved = score < self.best_scores[-1] - self.min_delta
        if improved:
            self.best_scores.append(score)
            self.current_rounds = 0
            model.best_iteration = epoch
            model.best_score = score
            model.set_attr(best_iteration=str(epoch), best_score=str(score))
        else:
            self.current_rounds += 1
        return self.current_rounds >= self.rounds

    def after_training(self, model):
        if self.save_best and model.best_iteration is not None and not getattr(model, "_is_cv", False):
            model = model[: model.best_iteration + 1]
        return model

    def state_dict(self) -> dict:
        return {"best_scores": list(self.best_scores),
                "current_rounds": int(self.current_rounds)}

    def load_state(self, state: dict) -> None:
        self.best_scores = [float(s) for s in state.get("best_scores", [])]
        self.current_rounds = int(state.get("current_rounds", 0))


class EvaluationMonitor(TrainingCallback):
    """Log eval results each round (reference: callback.py:511).

    ``rank``: only that rank prints under multi-process training (the
    reference's printer_rank — every worker logging the same line N times
    is noise).  ``show_stdv``: render cv (mean, std) scores as
    ``mean+std``.  ``logger=None`` routes through ``utils.logging``
    (respects ``register_log_callback`` redirection and verbosity=0
    silencing); pass a callable to capture lines directly."""

    def __init__(self, rank: int = 0, period: int = 1, show_stdv: bool = False,
                 logger: Optional[Callable[[str], None]] = None):
        self.printer_rank = int(rank)
        self.period = max(period, 1)
        self.show_stdv = show_stdv
        self.logger = logger
        self._latest: Optional[str] = None

    def _fmt_metric(self, data: str, metric: str, score: _Score) -> str:
        if isinstance(score, (tuple, list)) and len(score) == 2:
            if self.show_stdv:
                return f"\t{data}-{metric}:{score[0]:.5f}+{score[1]:.5f}"
            score = score[0]
        return f"\t{data}-{metric}:{score:.5f}"

    def _emit(self, msg: str) -> None:
        if self.logger is not None:
            self.logger(msg)
        else:
            from .utils import logging as _logging

            _logging.console(msg)

    def after_iteration(self, model, epoch, evals_log) -> bool:
        if not evals_log:
            return False
        from . import collective

        if collective.get_rank() != self.printer_rank:
            return False
        msg = f"[{epoch}]"
        for data, metrics in evals_log.items():
            for metric, hist in metrics.items():
                msg += self._fmt_metric(data, metric, hist[-1])
        if epoch % self.period:
            # off-period round: keep the line so after_training can flush
            # the FINAL scores (reference caches _latest the same way)
            self._latest = msg
        else:
            self._emit(msg)
            self._latest = None
        return False

    def after_training(self, model):
        if self._latest is not None:
            self._emit(self._latest)
            self._latest = None
        return model


class TrainingCheckPoint(TrainingCallback):
    """Save the model every N iterations (reference: callback.py:586)."""

    def __init__(self, directory: str, name: str = "model", as_pickle: bool = False,
                 interval: int = 100):
        import os

        self.dir = directory
        self.name = name
        self.interval = max(interval, 1)
        self.as_pickle = as_pickle
        os.makedirs(directory, exist_ok=True)

    def after_iteration(self, model, epoch, evals_log) -> bool:
        import os
        import pickle

        if epoch % self.interval == 0:
            if self.as_pickle:
                with open(os.path.join(self.dir, f"{self.name}_{epoch}.pkl"), "wb") as fh:
                    pickle.dump(model, fh)
            else:
                model.save_model(os.path.join(self.dir, f"{self.name}_{epoch}.json"))
        return False
