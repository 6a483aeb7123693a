"""train() / cv() drivers (reference: python-package/xgboost/training.py:53,435).

The loop shape matches the reference exactly: callbacks wrap a plain
``bst.update`` per round; cv() builds stratified/group folds (CVPack,
training.py:212) and aggregates fold metrics.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .callback import CallbackContainer, EarlyStopping, EvaluationMonitor, TrainingCallback
from .core import Booster
from .data.dmatrix import DMatrix
from .data.extmem import ExtMemConfig
from .elastic import ElasticConfig, RegroupRequired, ShardMap

__all__ = ["train", "cv"]


def _elastic_data(cfg: ElasticConfig, shard_map: ShardMap, rank: int,
                  world: int, default_evals: list):
    """(dtrain, evals) from the user's data_fn — which may return just the
    DMatrix or a (DMatrix, evals) pair when evals re-shard too."""
    built = cfg.data_fn(shard_map, rank, world)
    if isinstance(built, tuple):
        dtrain, ev = built
        return dtrain, list(ev) if ev else []
    return built, default_evals


def _elastic_shard_map(cfg: ElasticConfig, resumed, world: int) -> ShardMap:
    """The canonical shard map at ``world``: restored from the checkpoint
    when one exists (the dead rank's shards re-assign from what was
    actually saved), else created fresh; rebalanced if the world moved.
    ``cfg.num_shards`` is resolved to the initial world size at train()
    entry, so a fresh restart after a pre-checkpoint death keeps the
    ORIGINAL shard universe — absorption back to full strength stays
    possible."""
    smap = None
    if resumed is not None and resumed.shard_map:
        smap = ShardMap.from_dict(resumed.shard_map)
    if smap is None:
        smap = ShardMap.create(cfg.num_shards or world, world)
    if smap.world != world:
        if world > smap.num_shards:
            raise RuntimeError(
                f"cannot regroup to world {world}: this run's shard "
                f"universe has only {smap.num_shards} shards and a rank "
                "with no data cannot train; set ElasticConfig(num_shards=) "
                "to at least the largest world you intend to absorb to "
                "(e.g. 2x the worker count)")
        smap = smap.rebalance(world)
    return smap


def _restore_booster(params, resumed) -> Booster:
    """Booster from a checkpoint's serialized bytes — shared by the
    resume_from start path and in-process elastic regroup recovery so the
    restore semantics (config re-apply, early-stopping best re-exposure)
    cannot drift apart."""
    bst = Booster(params)
    bst.unserialize(resumed.booster_bytes)
    bst.set_param(params)
    bi = bst.attr("best_iteration")
    if bi is not None:  # re-expose early-stopping bests on the object
        bst.best_iteration = int(bi)
        bs = bst.attr("best_score")
        bst.best_score = float(bs) if bs is not None else None
    return bst


def _elastic_regroup(params, cfg: ElasticConfig, cbs, callbacks, ckpt_cb,
                     evals, completed_hint: int):
    """Round-boundary regroup with re-entry: join the new epoch, reload
    training state from the newest checkpoint, rebuild this rank's data
    from the rebalanced shard map.  Membership can change AGAIN while
    recovery is in flight (another death, a replacement arriving) — the
    new epoch's first collective then raises RegroupRequired from inside
    recovery itself, so the whole sequence simply re-enters.  Returns
    (bst, dtrain, evals, next_round)."""
    while True:
        try:
            return _elastic_regroup_once(params, cfg, cbs, callbacks,
                                         ckpt_cb, evals, completed_hint)
        except RegroupRequired:
            continue


def _elastic_regroup_once(params, cfg: ElasticConfig, cbs, callbacks,
                          ckpt_cb, evals, completed_hint: int):
    import time

    from . import collective
    from .elastic import instruments as _elastic_ins
    from .reliability.checkpoint import (latest_checkpoint,
                                         restore_callback_state)

    t0 = time.perf_counter()
    rank, world = collective.regroup(completed_hint)
    resumed = latest_checkpoint(cfg.checkpoint_dir)
    smap = _elastic_shard_map(cfg, resumed, world)
    dtrain, evals = _elastic_data(cfg, smap, rank, world, evals)
    if resumed is not None:
        bst = _restore_booster(params, resumed)
        # REPLACE the in-memory history with the checkpoint's: the partial
        # round being abandoned must not leave duplicate eval entries when
        # the round is re-run at the new world size
        cbs.history.clear()
        for name, metrics in resumed.history.items():
            cbs.history[name] = {k: list(v) for k, v in metrics.items()}
        restore_callback_state(callbacks, resumed.callback_state)
        next_round = resumed.round
    else:
        # death before the first checkpoint: the survivors restart from
        # round 0 at the reduced world size — with callback state reset
        # too (EarlyStopping best/patience from the abandoned rounds must
        # not leak into the restarted run)
        bst = Booster(params, cache=[dtrain])
        cbs.history.clear()
        for cb in callbacks:
            fn = getattr(cb, "load_state", None)
            if fn is not None and getattr(cb, "state_dict", None) is not None:
                fn({})
        next_round = 0
    ckpt_cb.shard_map = smap.to_dict()
    ins = _elastic_ins()
    ins[0].inc()
    ins[2].observe(time.perf_counter() - t0)
    return bst, dtrain, evals, next_round


def train(
    params: Dict[str, Any],
    dtrain: Optional[DMatrix] = None,
    num_boost_round: int = 10,
    *,
    evals: Optional[Sequence[Tuple[DMatrix, str]]] = None,
    obj: Optional[Callable] = None,
    maximize: Optional[bool] = None,
    early_stopping_rounds: Optional[int] = None,
    evals_result: Optional[dict] = None,
    verbose_eval: Union[bool, int, None] = True,
    xgb_model: Optional[Union[str, Booster]] = None,
    callbacks: Optional[Sequence[TrainingCallback]] = None,
    custom_metric: Optional[Callable] = None,
    resume_from: Optional[str] = None,
    elastic: Optional[ElasticConfig] = None,
) -> Booster:
    """``resume_from``: a checkpoint directory written by
    :class:`~xgboost_tpu.reliability.CheckpointCallback`.  When it holds a
    valid checkpoint, training continues from it (overriding ``xgb_model``)
    and ``num_boost_round`` is the TOTAL round target, so an interrupted-
    and-resumed run finishes at the same round — and, under deterministic
    config, the same bits — as an uninterrupted one.  An empty or missing
    directory falls through to a normal start, so the same command line
    works for launch and relaunch (docs/reliability.md).

    ``dtrain`` may also be an
    :class:`~xgboost_tpu.data.extmem.ExtMemConfig`: this rank then builds
    an out-of-core :class:`~xgboost_tpu.data.extmem.ExtMemQuantileDMatrix`
    over its page shard (``ShardMap`` round-robin), with cuts merged by
    the streaming page-wise sketch and per-level histograms allreduced
    across ranks — the launcher-composed full-scale path
    (docs/extmem.md).

    ``elastic``: an :class:`~xgboost_tpu.elastic.ElasticConfig` makes the
    run survive worker loss at reduced world size and absorb replacement
    workers at round boundaries.  ``dtrain`` may then be omitted — the
    config's ``data_fn`` builds it from this rank's shards (and rebuilds
    it after every regroup); a CheckpointCallback on the config's
    directory is appended automatically and ``resume_from`` defaults to
    it.  ``num_boost_round`` is always the TOTAL round target under
    elastic mode.  Requires an elastic-capable collective backend
    (tracker relay or in-memory) — docs/reliability.md § Elastic
    training."""
    from .telemetry import pauses, profiler

    # default-on wall sampler (XGBOOST_TPU_PROF_HZ=0 disables): training
    # rounds show up in the merged flame view; sampling only reads
    # frames, so the trained model is bitwise-identical either way.  It
    # watches this thread: its ticks are what a slow round's line quotes
    profiler.maybe_start("train")
    # the collector's pauses, counted from here on (telemetry/pauses.py)
    pauses.install()
    callbacks = list(callbacks) if callbacks else []
    evals = list(evals) if evals else []
    if isinstance(dtrain, ExtMemConfig):
        # out-of-core multi-process composition (docs/extmem.md): this
        # rank builds its page shard's ExtMemQuantileDMatrix — streaming
        # sketch merge and per-level histogram allreduce happen inside the
        # normal distributed paths once the DMatrix is paged
        if elastic is not None:
            raise ValueError(
                "train(ExtMemConfig, elastic=...) is not supported: "
                "elastic re-sharding rebuilds data through "
                "ElasticConfig.data_fn — return the paged DMatrix there "
                "instead")
        dtrain, extmem_evals = dtrain.build()
        if not evals:
            evals = extmem_evals
    if dtrain is None and elastic is None:
        raise TypeError("train() needs dtrain (or an elastic config whose "
                        "data_fn builds it)")
    if early_stopping_rounds is not None:
        if not evals and (elastic is None or dtrain is not None):
            # elastic data_fn may supply evals; re-validated after it runs
            raise ValueError(
                "Must have at least 1 validation dataset for early stopping."
            )
        callbacks.append(EarlyStopping(rounds=early_stopping_rounds, maximize=maximize))
    if verbose_eval:
        period = 1 if verbose_eval is True else int(verbose_eval)
        callbacks.append(EvaluationMonitor(period=period))
    ckpt_cb = None
    if elastic is not None:
        from .reliability.checkpoint import CheckpointCallback

        # regroup recovery reloads from elastic.checkpoint_dir: make sure
        # something is writing there, and resume from it by default so the
        # same invocation serves launch, relaunch, and replacement workers
        ckpt_cb = next((cb for cb in callbacks
                        if isinstance(cb, CheckpointCallback)), None)
        if ckpt_cb is None:
            ckpt_cb = CheckpointCallback(
                elastic.checkpoint_dir, interval=elastic.checkpoint_interval,
                keep_last=elastic.keep_last)
            callbacks.append(ckpt_cb)
        elif (os.path.abspath(ckpt_cb.manager.directory)
              != os.path.abspath(elastic.checkpoint_dir)):
            # a mismatch would silently break regroup recovery: the run
            # would checkpoint to one directory and reload from an
            # empty other, discarding every completed round on a death
            raise ValueError(
                f"CheckpointCallback directory "
                f"{ckpt_cb.manager.directory!r} != "
                f"ElasticConfig.checkpoint_dir "
                f"{elastic.checkpoint_dir!r}: regroup recovery reloads "
                "from the elastic directory, so they must match")
        if resume_from is None:
            resume_from = elastic.checkpoint_dir
    # run-last callbacks (CheckpointCallback) dispatch after the rest so a
    # checkpoint captures the CURRENT round's EarlyStopping state, not the
    # previous round's (stable sort keeps every other relative order)
    callbacks.sort(key=lambda cb: bool(getattr(cb, "_run_last", False)))
    cbs = CallbackContainer(callbacks, metric=custom_metric)
    for cb in callbacks:
        bind = getattr(cb, "_bind_container", None)
        if bind is not None:  # CheckpointCallback snapshots history + peers
            bind(cbs)

    resumed = None
    if resume_from is not None:
        from .reliability.checkpoint import (latest_checkpoint,
                                             restore_callback_state)

        resumed = latest_checkpoint(resume_from)
    from . import collective

    if elastic is not None:
        rank, world = collective.get_rank(), collective.get_world_size()
        if elastic.num_shards is None:
            # pin the shard universe to the INITIAL world: a fresh restart
            # after a pre-checkpoint death must not shrink it, or
            # absorption back to full strength becomes impossible.  Pin on
            # a copy — the caller's config object must stay reusable for
            # a later run at a different world size.
            import copy

            elastic = copy.copy(elastic)
            elastic.num_shards = world
        smap = _elastic_shard_map(elastic, resumed, world)
        if dtrain is None:
            dtrain, evals = _elastic_data(elastic, smap, rank, world, evals)
            if early_stopping_rounds is not None and not evals:
                raise ValueError(
                    "Must have at least 1 validation dataset for early "
                    "stopping (the elastic data_fn returned none)."
                )
        ckpt_cb.shard_map = smap.to_dict()
        from .reliability import watchdog as _wd

        # the shard map rides the liveness markers to the tracker, whose
        # journal then carries it across a coordinator respawn
        _wd.progress("shard_map", map=ckpt_cb.shard_map)
    if resumed is not None:
        bst = _restore_booster(params, resumed)
        for name, metrics in resumed.history.items():
            cbs.history.setdefault(name, {}).update(metrics)
        restore_callback_state(callbacks, resumed.callback_state)
    elif isinstance(xgb_model, (str, bytes, bytearray)):
        bst = Booster(params)
        bst.load_model(xgb_model)
        bst.set_param(params)
    elif isinstance(xgb_model, Booster):
        bst = xgb_model.copy()
        bst.set_param(params)
    else:
        bst = Booster(params, cache=[dtrain])

    bst = cbs.before_training(bst)
    start = bst.num_boosted_rounds()
    # resumed runs count num_boost_round as the TOTAL target (so relaunching
    # the same command converges on the same final round); a fresh or
    # xgb_model continuation keeps the additive reference semantics.
    # Elastic runs are always total: survivors and replacements must agree
    # on the final round whatever state they entered with.
    # process_type=update appends nothing — iterations are tree-SEGMENT
    # indices into the existing model (the reference train() always starts
    # at 0), so a refresh/prune pass over an xgb_model continuation walks
    # rounds 0..num_boost_round-1 instead of past the end of the ensemble.
    if getattr(bst, "process_type", "default") == "update":
        start = 0
    total = (resumed is not None or elastic is not None
             or getattr(bst, "process_type", "default") == "update")
    end = num_boost_round if total else start + num_boost_round
    from .reliability import watchdog as _wd
    from .reliability.faults import maybe_inject
    from .telemetry import span, step_span
    from .telemetry.compile import counting
    from .telemetry.distributed import ship_to_tracker

    # the loop's three top-level spans (train.boundary, train.round,
    # train.after_iteration) tile a round's period; the watch keeps the last
    # periods and says once, in the ring and the log, why one ran long (at
    # the boundary after the round that followed it)
    watch = pauses.RoundWatch()
    closed = {}  # the boundary's round: the one whose period it lies in
    i = start
    while i < end:
        try:
            with counting(watch.top(span("train.boundary", next=i, **closed))):
                watch.tell()
                if elastic is not None and collective.regroup_pending():
                    # round-boundary absorption/shrink: membership changed
                    # while this worker was between rounds
                    raise RegroupRequired("membership changed between rounds")
                # liveness marker + (tracker mode) a rate-limited snapshot
                # ship: the tracker's stall watchdog distinguishes a slow
                # round from a frozen one by whether this marker advances,
                # and its journal tracks the per-rank resume round from it
                _wd.progress("train.round", round=i)
                ship_to_tracker()
                # fault seam (kill/exception/delay; no-op without a plan):
                # the round boundary is where a worker death is injected for
                # the kill->resume parity tests
                maybe_inject("train.round", round=i, rank=collective.get_rank)
            # the round span closes before after_iteration on purpose:
            # callbacks start and stop profiler sessions there, and an
            # annotation that straddles a session's edge is lost
            with counting(watch.top(step_span("train.round", i))) as opened:
                watch.opened(opened)
                with span("train.before_iteration"):
                    stop = cbs.before_iteration(bst, i, dtrain, evals)
                if not stop:
                    bst.update(dtrain, i, fobj=obj)
            if stop:
                break
            with counting(watch.top(span("train.after_iteration", round=i))):
                stop = cbs.after_iteration(bst, i, dtrain, evals)
        except RegroupRequired:
            if elastic is None:
                raise
            # a peer died (or a replacement arrived) mid-round or between
            # rounds: abandon the partial round, regroup, and re-enter from
            # the last checkpoint
            bst, dtrain, evals, i = _elastic_regroup(
                params, elastic, cbs, callbacks, ckpt_cb, evals,
                bst.num_boosted_rounds())
            _wd.progress("shard_map", map=ckpt_cb.shard_map)
            watch.reset()
            closed = {}
            continue
        if stop:
            break
        closed = {"round": i}
        i += 1
    watch.finished()
    bst = cbs.after_training(bst)

    if evals_result is not None:
        evals_result.update(cbs.history)
    return bst


class CVPack:
    """One fold (reference: training.py:212)."""

    def __init__(self, dtrain: DMatrix, dtest: DMatrix, params):
        self.dtrain = dtrain
        self.dtest = dtest
        self.watchlist = [(dtrain, "train"), (dtest, "test")]
        self.bst = Booster(params, cache=[dtrain, dtest])

    def update(self, iteration: int, fobj) -> None:
        self.bst.update(self.dtrain, iteration, fobj)

    def eval(self, iteration: int, feval) -> str:
        return self.bst.eval_set(self.watchlist, iteration, feval)


def _make_folds(dall: DMatrix, nfold: int, params, seed: int, shuffle: bool,
                stratified: bool, folds) -> List[CVPack]:
    R = dall.num_row()
    rng = np.random.default_rng(seed)
    if folds is not None:
        splits = [(np.asarray(tr), np.asarray(te)) for tr, te in folds]
    else:
        idx = np.arange(R)
        label = dall.get_label()
        if stratified:
            if shuffle:
                # random within equal-label blocks, stratified across folds
                order = np.lexsort((rng.random(R), label))
            else:
                order = np.argsort(label, kind="stable")
            fold_of = np.empty(R, np.int64)
            fold_of[order] = np.arange(R) % nfold
        else:
            if shuffle:
                idx = rng.permutation(R)
            fold_of = np.empty(R, np.int64)
            fold_of[idx] = np.arange(R) % nfold
        splits = [
            (np.nonzero(fold_of != k)[0], np.nonzero(fold_of == k)[0]) for k in range(nfold)
        ]
    return [CVPack(dall.slice(tr), dall.slice(te), params) for tr, te in splits]


def cv(
    params: Dict[str, Any],
    dtrain: DMatrix,
    num_boost_round: int = 10,
    nfold: int = 3,
    *,
    stratified: bool = False,
    folds=None,
    metrics: Sequence[str] = (),
    obj: Optional[Callable] = None,
    maximize: Optional[bool] = None,
    early_stopping_rounds: Optional[int] = None,
    as_pandas: bool = True,
    verbose_eval: Union[bool, int, None] = None,
    show_stdv: bool = True,
    seed: int = 0,
    callbacks: Optional[Sequence[TrainingCallback]] = None,
    shuffle: bool = True,
    custom_metric: Optional[Callable] = None,
):
    """K-fold CV (reference: training.py:435). Returns a dict/DataFrame of
    per-round mean/std metric values."""
    params = dict(params)
    if metrics:
        params["eval_metric"] = list(metrics) if len(list(metrics)) > 1 else list(metrics)[0]
    packs = _make_folds(dtrain, nfold, params, seed, shuffle, stratified, folds)

    callbacks = list(callbacks) if callbacks else []
    if early_stopping_rounds is not None:
        callbacks.append(EarlyStopping(rounds=early_stopping_rounds, maximize=maximize))
    if verbose_eval:
        callbacks.append(EvaluationMonitor(
            period=1 if verbose_eval is True else int(verbose_eval),
            show_stdv=show_stdv))
    cbs = CallbackContainer(callbacks, is_cv=True)
    from .telemetry import step_span

    class _Agg:
        """Aggregate booster stand-in handed to callbacks (reference _PackedBooster)."""

        best_iteration: Optional[int] = None
        best_score: Optional[float] = None
        _is_cv = True  # EarlyStopping(save_best=) must not slice this

        def set_attr(self, **kw):
            for p in packs:
                p.bst.set_attr(**kw)

        def set_param(self, k, v=None):
            for p in packs:
                p.bst.set_param(k, v)

        def eval_set(self, evals, iteration):  # unused; cv aggregates manually
            return ""

    agg = _Agg()
    # full callback lifecycle like train(): TelemetryCallback and friends
    # hook before/after_training (the loop below otherwise never fires them)
    agg = cbs.before_training(agg)
    results: Dict[str, List[float]] = {}
    for i in range(num_boost_round):
        if cbs.before_iteration(agg, i, dtrain, []):
            break
        fold_metrics: Dict[str, List[float]] = {}
        with step_span("train.round", i):
            for p in packs:
                p.update(i, obj)
                msg = p.eval(i, custom_metric)
                for part in msg.strip().split("\t")[1:]:
                    key, v = part.rsplit(":", 1)
                    fold_metrics.setdefault(key, []).append(float(v))
        for key, vals in fold_metrics.items():
            mean, std = float(np.mean(vals)), float(np.std(vals))
            results.setdefault(f"{key}-mean", []).append(mean)
            results.setdefault(f"{key}-std", []).append(std)
            # callbacks see (mean, std) tuples (the reference's cv score
            # shape): EvaluationMonitor renders +std under show_stdv,
            # EarlyStopping stops on the mean
            cbs.history.setdefault(key.split("-", 1)[0], {}).setdefault(
                key.split("-", 1)[1], []
            ).append((mean, std))
        if any(cb.after_iteration(agg, i, cbs.history) for cb in cbs.callbacks):
            break
    cbs.after_training(agg)
    if as_pandas:
        try:
            import pandas as pd

            return pd.DataFrame.from_dict(results)
        except ImportError:
            pass
    return results
