"""Booster: the trained model + training-step engine.

TPU-native analogue of the reference Learner + GBTree + Python Booster
(src/learner.cc:1030 LearnerImpl, src/gbm/gbtree.cc:225 DoBoost,
python-package/xgboost/core.py:1749 Booster).  One object plays all three
roles: it owns the objective, the tree list, per-DMatrix training caches
(binned Ellpack + margin cache — the prediction cache of
include/xgboost/cache.h:26), and the save/load surface.

Call stack for one boosting iteration (mirrors SURVEY §3.1):
  train() -> Booster.update(dtrain, i)
    -> objective.get_gradient on the cached margin           [device]
    -> HistTreeGrower.grow per output group                  [device loop]
    -> leaf_margin_delta updates the margin cache            [device]
    -> RegTree.from_grown appends the host model
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .context import Context
from .data.dmatrix import DMatrix
from .metric import create_metric
from .models.tree import RegTree
from .objective import ObjFunction, create_objective
from .ops.histogram import hist_is_row_pass
from .ops.predict import predict_leaf_ids
from .ops.split import SplitParams
from .params import TrainParam, canonicalize, split_unknown
from .telemetry import span
from .telemetry.spans import wait_span
from .tree.grow import HistTreeGrower, leaf_margin_delta

__all__ = ["Booster"]


class _Cache:
    """Per-DMatrix training cache: margin (+ binned Ellpack for training).

    Eval-only DMatrices never pay for sketching/binning: the Ellpack is built
    lazily on first training touch (finding: eval sets only need the raw
    feature matrix for the predictor)."""

    def __init__(self, dmat: DMatrix, max_bin: int, ref: Optional[DMatrix] = None,
                 mesh=None, distributed: bool = False):
        self.dmat = dmat
        self.max_bin = max_bin
        self.ref = ref
        self.mesh = mesh
        self.distributed = distributed
        self.ellpack = None
        self.n_padded = dmat.num_row()  # grows to the padded size on ensure_train
        self.margin: Optional[Any] = None  # (n_padded, K) device
        self.n_trees_applied = 0
        self.weights_version = 0  # DART tree-weight epoch this margin reflects
        self.raw_X: Optional[Any] = None  # lazily staged raw matrix for eval predict

    @property
    def is_extmem(self) -> bool:
        return hasattr(self.dmat, "_pages")

    def ensure_train_raw(self) -> None:
        """Label/weight/valid arrays WITHOUT sketching or binning: the exact
        updater walks raw host values, so the quantile sketch + Ellpack +
        device upload would be pure wasted startup cost."""
        import jax.numpy as jnp

        if self.ellpack is not None or getattr(self, "_raw_ready", False):
            return  # binned arrays already cover the raw path's needs
        R = self.dmat.num_row()
        self.valid = jnp.ones(R, bool)
        self.labels = jnp.asarray(self.dmat.get_label())
        w = self.dmat.get_weight()
        self.weights = None if w is None else jnp.asarray(w)
        self.n_padded = R
        self._raw_ready = True

    def ensure_train(self) -> None:
        """Build the binned page + padded label/weight/valid device arrays."""
        import jax.numpy as jnp

        if self.is_extmem:
            if getattr(self, "_extmem_ready", False):
                return
            d = self.dmat
            R_pad = d.n_padded_total
            self.valid = jnp.asarray(d.valid_mask())
            lab = d.padded_labels()
            self.labels = jnp.asarray(lab if lab is not None
                                      else np.zeros(R_pad, np.float32))
            w = d.padded_weights()
            self.weights = None if w is None else jnp.asarray(w)
            if self.margin is not None and self.margin.shape[0] != R_pad:
                extra = R_pad - self.margin.shape[0]
                self.margin = jnp.concatenate(
                    [self.margin, jnp.zeros((extra, self.margin.shape[1]), jnp.float32)], 0)
            self.n_padded = R_pad
            self._extmem_ready = True
            return
        if self.ellpack is not None:
            return
        # pages must split evenly over the mesh: row_align = lcm(1024, n)
        # (VERDICT r3 #10 — arbitrary device counts, not just powers of two)
        import math

        align = 1024 if self.mesh is None else math.lcm(
            1024, self.mesh.devices.size)
        self.ellpack = self.dmat.ensure_ellpack(max_bin=self.max_bin,
                                                ref=self.ref,
                                                distributed=self.distributed,
                                                row_align=align)
        if self.mesh is not None:
            from .parallel import shard_rows

            # sharded COPY kept on the cache; the DMatrix's page stays intact
            # for later single-device training on the same matrix
            (self.bins,) = shard_rows(self.mesh, self.ellpack.bins)
        else:
            self.bins = self.ellpack.bins
        R_pad = self.ellpack.n_padded
        R = self.ellpack.n_rows
        self.valid = jnp.arange(R_pad) < R
        lab = self.dmat.get_label()
        pad = ((0, R_pad - R),) + tuple((0, 0) for _ in range(lab.ndim - 1))
        self.labels = jnp.asarray(np.pad(lab, pad))
        w = self.dmat.get_weight()
        self.weights = None if w is None else jnp.asarray(np.pad(w, (0, R_pad - R)))
        if self.margin is not None and self.margin.shape[0] != R_pad:
            extra = R_pad - self.margin.shape[0]
            self.margin = jnp.concatenate(
                [self.margin, jnp.zeros((extra, self.margin.shape[1]), jnp.float32)], axis=0
            )
        self.n_padded = R_pad

    def base_margin_init(self, base_score, K: int):
        import jax.numpy as jnp

        R_pad = self.n_padded
        user = self.dmat.info.base_margin
        if user is not None and self.is_extmem:
            m = self.dmat.padded_base_margin().reshape(R_pad, -1)
            if m.shape[1] != K:
                m = np.broadcast_to(m, (R_pad, K))
            return jnp.asarray(m.astype(np.float32))
        if user is not None:
            m = np.asarray(user, np.float32).reshape(len(user), -1)
            if m.shape[1] != K:
                m = np.broadcast_to(m, (m.shape[0], K))
            out = np.zeros((R_pad, K), np.float32)
            out[: m.shape[0]] = m
            return jnp.asarray(out)
        base = np.broadcast_to(np.asarray(base_score, np.float32).reshape(-1), (K,))
        return jnp.broadcast_to(jnp.asarray(base), (R_pad, K)).astype(jnp.float32)


class Booster:
    """Gradient-boosted tree model (reference: core.py:1749, learner.cc:1030)."""

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        cache: Sequence[DMatrix] = (),
        model_file: Optional[str] = None,
    ) -> None:
        self.params: Dict[str, Any] = canonicalize(dict(params or {}))
        self.trees: List[RegTree] = []
        self.tree_info: List[int] = []  # group id per tree
        self.attributes: Dict[str, str] = {}
        self.feature_names: Optional[List[str]] = None
        self.feature_types: Optional[List[str]] = None
        self._caches: Dict[int, _Cache] = {}
        self._configured = False
        self.best_iteration: Optional[int] = None
        self.best_score: Optional[float] = None
        if model_file is not None:
            self.load_model(model_file)
        for d in cache:
            self._get_cache(d)

    # ------------------------------------------------------------------ config
    def _configure(self) -> None:
        """Lazy config (reference: learner.cc:521 Configure on every call)."""
        if self._configured:
            return
        p = self.params
        unknown = split_unknown(p)
        if unknown and str(p.get("validate_parameters", "")).lower() in ("1", "true"):
            raise ValueError(f"Unknown parameters: {unknown}")
        self.tparam = TrainParam.from_dict(p)
        self.context = Context.create(p.get("device"),
                                      nthread=int(p.get("nthread", 0) or 0),
                                      seed=int(p.get("seed", 0)))
        self.context.check_device()
        # nthread reaches the native ParallelFor pool here (params dict /
        # XGBoosterSetParam("nthread") both land in p); results are bitwise
        # independent of the value (docs/native_threading.md)
        self.context.apply_nthread()
        obj_name = str(p.get("objective", "reg:squarederror"))
        self.objective: ObjFunction = create_objective(obj_name, p)
        self.num_class = int(p.get("num_class", 0))
        self.n_groups = max(1, self.objective.n_groups())
        self._base_score_param = p.get("base_score", None)
        if not hasattr(self, "_base_margin_value"):
            self._base_margin_value: Optional[np.ndarray] = None
        booster = str(p.get("booster", "gbtree"))
        if booster not in ("gbtree", "dart", "gblinear"):
            raise ValueError(f"unknown booster {booster}")
        self.booster_kind = booster
        # multi-chip data parallelism: n_devices = int | "all" (SURVEY §2 L1:
        # row sharding + histogram psum is the reference's whole comm pattern)
        nd = p.get("n_devices", 1)
        if isinstance(nd, bool) or (not isinstance(nd, int) and nd != "all"):
            raise ValueError(f"n_devices must be an int or 'all', got {nd!r}")
        if isinstance(nd, int) and nd < 1:
            raise ValueError(f"n_devices must be >= 1, got {nd}")
        self.n_devices = nd if isinstance(nd, int) else -1  # -1 = all
        self._mesh = None
        self.num_parallel_tree = int(p.get("num_parallel_tree", 1))
        # process_type=update re-processes an existing model's trees with
        # the non-growing updaters (gbtree.cc InitUpdater)
        self.tree_method = str(p.get("tree_method", "hist"))
        if self.tree_method in ("auto", "gpu_hist"):
            self.tree_method = "hist"
        if self.tree_method not in ("hist", "approx", "exact"):
            raise ValueError(f"unknown tree_method {self.tree_method!r}")
        self.process_type = str(p.get("process_type", "default"))
        if self.process_type not in ("default", "update"):
            raise ValueError(f"unknown process_type {self.process_type!r}")
        upd = p.get("updater")
        self.updater_seq = ([u.strip() for u in str(upd).split(",") if u.strip()]
                            if upd else None)
        self.refresh_leaf = str(p.get("refresh_leaf", "1")).lower() in ("1", "true")
        # fixed-point limb histograms (ops/quantise.py): bitwise-identical
        # trees on every chip x process topology — the reference's
        # GradientQuantiser behaviour (src/tree/gpu_hist/quantiser.cuh),
        # exposed as an opt-in because the f32 path is the faster default
        self.deterministic_histogram = str(
            p.get("deterministic_histogram", "0")).lower() in ("1", "true")
        # vector-leaf trees (multi_target_tree_model.h): one tree carries all
        # K outputs when multi_strategy="multi_output_tree"
        self.multi_strategy = str(p.get("multi_strategy", "one_output_per_tree"))
        if self.multi_strategy not in ("one_output_per_tree", "multi_output_tree"):
            raise ValueError(f"unknown multi_strategy {self.multi_strategy!r}")
        if not hasattr(self, "tree_weights"):
            self.tree_weights: List[float] = []
        if not hasattr(self, "linear_weights"):
            self.linear_weights: Optional[np.ndarray] = None  # (F, K)
            self.linear_bias: Optional[np.ndarray] = None  # (K,)
        # DART (reference: src/gbm/gbtree.cc Dart booster)
        self.rate_drop = float(p.get("rate_drop", 0.0))
        self.skip_drop = float(p.get("skip_drop", 0.0))
        self.one_drop = str(p.get("one_drop", "0")).lower() in ("1", "true")
        self.sample_type = str(p.get("sample_type", "uniform"))
        self.normalize_type = str(p.get("normalize_type", "tree"))
        if self.tparam.monotone_constraints is not None:
            pass  # length checked on first training touch (needs n_features)
        self._split_params = SplitParams(
            eta=float(self.tparam.eta),
            gamma=float(self.tparam.gamma),
            min_child_weight=float(self.tparam.min_child_weight),
            lambda_=float(self.tparam.lambda_),
            alpha=float(self.tparam.alpha),
            max_delta_step=float(self.tparam.max_delta_step),
            monotone=self.tparam.monotone_constraints,
            max_cat_to_onehot=int(self.tparam.max_cat_to_onehot),
        )
        self._configured = True

    # params whose change invalidates binned data / margins / objective state
    _STRUCTURAL_KEYS = {"max_bin", "objective", "num_class", "device", "booster",
                        "tree_method", "base_score", "num_target", "multi_strategy"}

    def _invalidate_config(self, structural: bool = True):
        self._configured = False
        if structural:
            self._caches.clear()
            # a TRAINED model's base score is model state, not configuration
            # (learner.cc saves it with the model; continuation never
            # re-estimates): clearing it here would silently rebuild every
            # continued-training margin from base 0
            if not self.trees and getattr(self, "linear_weights", None) is None:
                self._base_margin_value = None

    def set_param(self, params, value=None) -> None:
        if isinstance(params, str):
            params = {params: value}
        elif isinstance(params, (list, tuple)):
            params = dict(params)
        params = canonicalize(params)
        structural = any(
            k in self._STRUCTURAL_KEYS and self.params.get(k) != v
            for k, v in params.items()
        )
        self.params.update(params)
        self._invalidate_config(structural=structural)

    # ------------------------------------------------------------------ caches
    def _get_cache(self, dmat: DMatrix, ref: Optional[DMatrix] = None) -> _Cache:
        self._configure()
        key = id(dmat)
        if key not in self._caches:
            self._caches[key] = _Cache(dmat, self.tparam.max_bin, ref=ref,
                                       mesh=self._get_mesh(),
                                       distributed=self._process_parallel())
            if getattr(self, "_num_feature", None) is None:
                self._num_feature = dmat.num_col()
        return self._caches[key]

    def _ensure_base_margin(self, cache: _Cache):
        if self._base_margin_value is None:
            # InitEstimation / FitStump (src/tree/fit_stump.cc:34)
            if self._base_score_param is not None:
                prob = np.asarray(float(self._base_score_param), np.float32)
                bm = np.asarray(self.objective.prob_to_margin(prob))
            elif len(self.trees) == 0 and (
                cache.ellpack is not None
                or getattr(cache, "_raw_ready", False)
                or (cache.is_extmem and getattr(cache, "_extmem_ready", False))
            ):
                import jax.numpy as jnp

                v = np.asarray(cache.valid)
                lab = np.asarray(cache.labels)[v]
                wts = (None if cache.weights is None
                       else np.asarray(cache.weights)[v])
                if self._process_parallel():
                    # InitEstimation must agree across workers (the reference
                    # allreduces inside FitStump, fit_stump.cc:52); gather the
                    # shards so every process estimates on the global labels
                    from . import collective

                    lab = collective.allgather_ragged(lab)
                    if wts is not None:
                        wts = collective.allgather_ragged(wts)
                bm = np.asarray(
                    self.objective.init_estimation(
                        jnp.asarray(lab),
                        None if wts is None else jnp.asarray(wts),
                    )
                )
            else:
                bm = np.zeros(self.n_groups, np.float32)
            self._base_margin_value = np.broadcast_to(
                np.asarray(bm, np.float32).reshape(-1), (self.n_groups,)
            ).copy()
        if cache.margin is None:
            cache.margin = cache.base_margin_init(self._base_margin_value, self.n_groups)
            cache.n_trees_applied = 0

    def _sync_margin(self, cache: _Cache) -> None:
        """Catch the cached margin up to all committed trees (the prediction
        cache semantics of include/xgboost/cache.h:26) — covers continued
        training via xgb_model= and caches rebuilt mid-train."""
        import jax.numpy as jnp

        if cache.is_extmem:
            cache.ensure_train()
        self._ensure_base_margin(cache)
        if self.booster_kind == "gblinear":
            rounds = getattr(self, "_linear_rounds", 0)
            if self.linear_weights is None or cache.n_trees_applied == rounds > 0:
                if cache.margin is None:
                    cache.margin = cache.base_margin_init(
                        self._base_margin_value, self.n_groups)
                return
            cache.margin = self._linear_margin(cache)
            cache.n_trees_applied = rounds
            return
        if cache.weights_version != getattr(self, "_weights_version", 0):
            # DART rescaled historical trees: rebuild this cache from scratch
            cache.margin = cache.base_margin_init(self._base_margin_value, self.n_groups)
            cache.n_trees_applied = 0
            cache.weights_version = getattr(self, "_weights_version", 0)
        if cache.n_trees_applied < len(self.trees):
            new = slice(cache.n_trees_applied, len(self.trees))
            if cache.is_extmem:
                delta = jnp.asarray(self._predict_extmem(cache.dmat, new))
                cache.margin = cache.margin + delta  # page-padded, aligned
                cache.n_trees_applied = len(self.trees)
                return
            elif self._use_streamed_predict(cache.dmat):
                # large sparse eval/train matrix: never cache a dense copy
                delta = jnp.asarray(self._margin_delta_streamed(cache.dmat, new))
                pad = cache.margin.shape[0] - delta.shape[0]
                if pad:
                    delta = jnp.concatenate(
                        [delta, jnp.zeros((pad, delta.shape[1]), jnp.float32)],
                        axis=0)
                cache.margin = cache.margin + delta
                cache.n_trees_applied = len(self.trees)
                return
            elif (cache.ellpack is not None and self._get_mesh() is None
                  and all(t.split_bins is not None
                          and t.leaf_vector is None
                          for t in self.trees[new])
                  and self._try_rebind_split_bins(new, cache.ellpack.cuts)):
                # binned pages already on device: route through them instead
                # of materializing a second raw f32 copy (the reference's
                # UpdatePredictionCache also reuses the training partition);
                # loaded models without split_bins fall through to raw.
                # Accumulating INTO the existing margin keeps the training
                # loop's f32 addition order: a rebuilt cache is bitwise-
                # identical to the incrementally-updated one, so continued
                # training (xgb_model=) equals one straight run exactly
                cache.margin = self._margin_delta_binned_cache(
                    cache, new, init=cache.margin)
                cache.n_trees_applied = len(self.trees)
                return
            else:
                if cache.raw_X is None:
                    cache.raw_X = jnp.asarray(self.dmat_host_dense(cache), jnp.float32)
                R_raw = cache.raw_X.shape[0]
                m = self._margin_delta_for(cache.raw_X, new,
                                           init=cache.margin[:R_raw])
                if R_raw != cache.margin.shape[0]:
                    m = jnp.concatenate([m, cache.margin[R_raw:]], axis=0)
                cache.margin = m
            cache.n_trees_applied = len(self.trees)

    def dmat_host_dense(self, cache: _Cache) -> np.ndarray:
        return self._host_dense_recoded(cache.dmat)

    def _host_dense_recoded(self, data: DMatrix) -> np.ndarray:
        """Raw matrix with categorical codes remapped onto the TRAINING
        frame's category ordering (encoder/ordinal.h Recode): a frame whose
        pandas categories differ train->inference would otherwise route its
        codes through the wrong split sets silently."""
        from .data.dmatrix import recode_dense

        return recode_dense(data.host_dense(),
                            getattr(self, "_cat_categories", None),
                            getattr(data, "cat_categories", None))

    @property
    def base_score(self) -> np.ndarray:
        self._configure()
        if self._base_margin_value is None:
            return np.full(self.n_groups, 0.5, np.float32)
        return self._base_margin_value

    # ------------------------------------------------------------------ train
    def _prepare_update(self, dtrain: DMatrix) -> _Cache:
        """What a round needs before its margin: the configuration, the
        training cache of ``dtrain``, and the objective's and the booster's
        bindings to the matrix (bounds, query groups, categories, names)."""
        self._configure()
        cache = self._get_cache(dtrain)
        if self.tree_method == "exact" and not cache.is_extmem:
            cache.ensure_train_raw()
        else:
            cache.ensure_train()
        if hasattr(self.objective, "set_bounds"):
            lo = dtrain.info.label_lower_bound
            hi = dtrain.info.label_upper_bound
            if lo is not None:
                self.objective.set_bounds(lo, hi)
        if hasattr(self.objective, "set_group_info"):
            gp = dtrain.info.group_ptr
            # keyed on the DMatrix and a set_group version counter (NOT array
            # id(): the allocator can reuse addresses) so continued training
            # with different query groups rebuilds the layout
            owner = (id(dtrain), getattr(dtrain, "group_version", 0))
            if gp is None:
                gp = np.array([0, dtrain.num_row()], np.int64)
            if getattr(self.objective, "_gidx_owner", None) != owner:
                self.objective.set_group_info(gp, cache.labels)
                self.objective._gidx_owner = owner
        if getattr(dtrain, "cat_categories", None):
            cats = {int(k): list(v) for k, v in dtrain.cat_categories.items()}
            if getattr(self, "_cat_categories", None) is None:
                # remember the training frame's category->code mapping so
                # frames with different orderings recode at inference
                # (reference: src/encoder/ordinal.h:350 Recode)
                self._cat_categories = cats
            elif cats != self._cat_categories:
                # the binned page would be built from the RAW (mismatched)
                # codes while margins are recoded — fail loudly instead of
                # training trees against the wrong code space
                raise ValueError(
                    "continued training requires the training frame's "
                    "category ordering; re-declare the categorical columns "
                    "with the original categories")
        if self.feature_names is None and dtrain.feature_names:
            # inherit the training frame's column names (reference python
            # package: train() carries dtrain.feature_names onto the booster)
            # so dumps, importance and get_categories key by name
            self.feature_names = list(dtrain.feature_names)
        return cache

    def update(self, dtrain: DMatrix, iteration: int, fobj=None) -> None:
        """One boosting iteration (learner.cc:1108 UpdateOneIter)."""
        with span("update.prepare"):
            cache = self._prepare_update(dtrain)
        if self.process_type == "update":
            # the update flow keeps its own running margin over the already-
            # updated prefix; the full-model margin/gradient pass below would
            # be recomputed work that is then discarded
            if fobj is not None:
                raise NotImplementedError(
                    "process_type='update' with a custom objective is not "
                    "supported (refresh recomputes gradients internally)")
            self._ensure_base_margin(cache)
            self._update_existing_trees(cache, iteration)
            return
        with span("update.sync_margin"):
            self._sync_margin(cache)
            drop_idx = self._select_dart_drops(iteration)
        if drop_idx:
            # DART drop round: the gradient must be evaluated on the reduced
            # margin, which _boost_trees builds — skip the full-margin pass
            # so a custom fobj is invoked exactly once
            gpair = None
        else:
            # the objective's programs (eager ones for the elementwise
            # objectives) and the product with the rows' validity mask
            with span("update.gradient"):
                if fobj is not None:
                    # custom objectives receive RAW margins (reference:
                    # Booster.update passes output_margin=True predictions
                    # to fobj, core.py:2277)
                    gpair = self._fobj_gpair(cache, fobj, cache.margin,
                                             dtrain)
                else:
                    gpair = self.objective.get_gradient(
                        cache.margin, cache.labels, cache.weights, iteration
                    )  # (R_pad, K, 2)
                gpair = gpair * cache.valid[:, None, None]
        from .utils import observer

        if observer.enabled():
            observer.observe_margin(cache.margin, iteration)
            if gpair is not None:
                observer.observe_gradients(gpair, iteration)
        with span("update.update_tree"):
            if self.booster_kind == "gblinear":
                self._boost_linear(cache, gpair)
            else:
                self._boost_trees(cache, gpair, iteration, fobj=fobj,
                                  drop_idx=drop_idx)
        if observer.enabled() and self.trees:
            observer.observe_tree(self.trees[-1], iteration)

    def _fobj_gpair(self, cache, fobj, margin, dmat):
        """Densify a custom objective's (grad, hess) over the padded rows."""
        import jax.numpy as jnp

        valid_np = np.asarray(cache.valid).astype(bool)
        m = np.asarray(margin)[valid_np]
        preds = m[:, 0] if self.n_groups == 1 else m
        grad, hess = fobj(preds, dmat)
        R = int(valid_np.sum())
        grad = np.asarray(grad, np.float32).reshape(R, -1)
        hess = np.asarray(hess, np.float32).reshape(R, -1)
        gp_dense = np.zeros((margin.shape[0], grad.shape[1], 2), np.float32)
        gp_dense[valid_np] = np.stack([grad, hess], axis=-1)
        return jnp.asarray(gp_dense)

    def boost(self, dtrain: DMatrix, grad, hess, iteration: int = 0) -> None:
        """Custom-gradient boost (reference: XGBoosterBoostOneIter)."""
        import jax.numpy as jnp

        self._configure()
        if self.process_type == "update":
            raise NotImplementedError(
                "boost() with raw grad/hess cannot drive process_type="
                "'update' (the refresh updater recomputes gradients per "
                "round); use update() instead")
        if self._select_dart_drops(iteration):
            # this round actually drops trees: gradients would have to be
            # re-evaluated on the reduced margin, impossible with raw values
            raise NotImplementedError(
                "boost() with raw grad/hess cannot honour a DART dropout "
                "round; use update(fobj=...) or set rate_drop=0")
        cache = self._get_cache(dtrain)
        if self.tree_method == "exact" and not cache.is_extmem:
            cache.ensure_train_raw()
        else:
            cache.ensure_train()
        self._sync_margin(cache)
        R = dtrain.num_row()
        g = np.asarray(grad, np.float32).reshape(R, -1)
        h = np.asarray(hess, np.float32).reshape(R, -1)
        valid_np = np.asarray(cache.valid)
        gp_dense = np.zeros((cache.margin.shape[0], g.shape[1], 2), np.float32)
        gp_dense[valid_np] = np.stack([g, h], axis=-1)
        gpair = jnp.asarray(gp_dense)
        gpair = gpair * cache.valid[:, None, None]
        if self.booster_kind == "gblinear":
            self._boost_linear(cache, gpair)
        else:
            self._boost_trees(cache, gpair, iteration)

    def _linear_margin(self, cache: _Cache):
        """Full (padded) margin of the current linear model for a cache."""
        import jax.numpy as jnp

        from .models.gblinear import linear_predict

        if cache.raw_X is None:
            cache.raw_X = jnp.asarray(self._host_dense_recoded(cache.dmat), jnp.float32)
        base = jnp.asarray(self._base_margin_value)[None, :]
        m = linear_predict(cache.raw_X, jnp.asarray(self.linear_weights),
                           jnp.asarray(self.linear_bias)) + base
        pad = (cache.margin.shape[0] if cache.margin is not None else cache.n_padded) - m.shape[0]
        if pad:
            m = jnp.concatenate([m, jnp.zeros((pad, m.shape[1]), jnp.float32)], 0)
        return m

    def _boost_linear(self, cache: _Cache, gpair) -> None:
        """gblinear round (reference: src/gbm/gblinear.cc GBLinear::DoBoost)."""
        import jax.numpy as jnp

        from .models.gblinear import linear_predict, linear_update

        F = cache.dmat.num_col()
        K = gpair.shape[1]
        if self.linear_weights is None:
            self.linear_weights = np.zeros((F, K), np.float32)
            self.linear_bias = np.zeros(K, np.float32)
        if cache.raw_X is None:
            cache.raw_X = jnp.asarray(self._host_dense_recoded(cache.dmat), jnp.float32)
        Xz = jnp.nan_to_num(cache.raw_X, nan=0.0)
        updater = str(self.params.get("updater", "coord_descent"))
        if updater not in ("coord_descent", "shotgun"):
            raise ValueError(
                f"unknown gblinear updater {updater!r}; expected "
                "'coord_descent' or 'shotgun'")
        # reference defaults (coordinate_common.h): shotgun shuffles its
        # visit order every round, coord_descent walks features cyclically
        from .models.gblinear import (SELECTORS, effective_top_k,
                                      linear_update_greedy, selector_order,
                                      thrifty_order)

        selector = str(self.params.get(
            "feature_selector",
            "shuffle" if updater == "shotgun" else "cyclic"))
        if selector not in SELECTORS:
            raise ValueError(
                f"unknown feature_selector {selector!r}; expected one of "
                f"{SELECTORS}")
        top_k = int(self.params.get("top_k", 0) or 0)
        order = None
        if selector not in ("greedy", "thrifty"):
            order = jnp.asarray(selector_order(
                selector, F, getattr(self, "_linear_rounds", 0),
                int(self.params.get("seed", 0))))
        W = jnp.asarray(self.linear_weights)
        b = jnp.asarray(self.linear_bias)
        R = cache.dmat.num_row()
        eta, lam, alpha = (float(self.tparam.eta),
                           float(self.tparam.lambda_),
                           float(self.tparam.alpha))
        for k in range(K):
            if selector == "greedy":
                wk, bk, _ = linear_update_greedy(
                    Xz, gpair[:R, k, :], W[:, k], b[k],
                    steps=effective_top_k(top_k, F), eta=eta, lambda_=lam,
                    alpha=alpha)
            else:
                if selector == "thrifty":
                    # gain-ranked per group from the round-start gradients
                    order = jnp.asarray(thrifty_order(
                        Xz, gpair[:R, k, :], W[:, k], top_k=top_k,
                        alpha=alpha, lambda_=lam))
                wk, bk = linear_update(
                    Xz, gpair[:R, k, :], W[:, k], b[k], order,
                    eta=eta, lambda_=lam, alpha=alpha,
                )
            W = W.at[:, k].set(wk)
            b = b.at[k].set(bk)
        self.linear_weights = np.asarray(W)
        self.linear_bias = np.asarray(b)
        self._linear_rounds = getattr(self, "_linear_rounds", 0) + 1
        cache.margin = self._linear_margin(cache)
        cache.n_trees_applied = self._linear_rounds

    def _resolve_max_depth(self, lossguide: bool) -> int:
        """Default depth cap for the level-synchronous growers when
        max_depth<=0: 6 depthwise (the reference's default max_depth), 10
        heap levels under lossguide.  The latter is the round-1
        approximation of lossguide (a split budget a level over a heap), and
        only ``max_leaves <= 1`` still reaches it (no leaf budget, so nothing
        for a priority queue to spend) and the paged grower
        (tree/stream.py): lossguide with a leaf budget is the best-first
        grower's (tree/bestfirst.py), the serial driver's tree, which
        resolves 0 as "unbounded" and does not come here."""
        md = self.tparam.max_depth
        if md <= 0:
            md = 10 if lossguide else 6
        return md

    def _boost_trees_extmem(self, cache: _Cache, gpair, iteration: int) -> None:
        """Streaming boost over host-resident pages (ExtMemQuantileDMatrix)."""
        from .tree.stream import StreamingHistTreeGrower

        d = cache.dmat
        lossguide = self.tparam.grow_policy == "lossguide"
        max_depth = self._resolve_max_depth(lossguide)
        mesh_ext = self._get_mesh()
        if mesh_ext is not None and 1024 % mesh_ext.devices.size != 0:
            raise ValueError(
                f"external-memory pages are {1024}-row aligned at write time "
                f"(data/extmem.py PAGE_ALIGN); n_devices="
                f"{mesh_ext.devices.size} must divide 1024 for extmem "
                f"training — use a power-of-two device count or in-memory "
                f"DMatrix (which re-aligns to lcm(1024, n_devices))")
        grower = StreamingHistTreeGrower(
            max_depth, self._split_params,
            interaction_sets=self.tparam.interaction_constraints,
            max_leaves=self.tparam.max_leaves, lossguide=lossguide,
            mesh=self._get_mesh(),
            distributed=self._process_parallel(),
            # bench hook: "_extmem_prefetch": 0 serializes page transfer
            # against compute so the prefetch-overlap gain is measurable
            prefetch=str(self.params.get("_extmem_prefetch", "1")).lower()
            in ("1", "true"),
            quantised=self.deterministic_histogram,
            # gradient-based sampling decides page residency: a page whose
            # rows all sampled out is loaded once per tree, not per level
            # ("_extmem_page_skip": 0 keeps every page level-resident — the
            # measurement/parity baseline, tests/test_extmem.py)
            page_skip=(self.tparam.subsample < 1.0
                       and self.tparam.sampling_method == "gradient_based"
                       and str(self.params.get("_extmem_page_skip",
                                               "1")).lower()
                       in ("1", "true")),
        )
        K = gpair.shape[1]
        new_margin = cache.margin
        cat_ft = d.info.feature_types
        cat_mask_np = (np.asarray([t == "c" for t in cat_ft], bool)
                       if cat_ft and "c" in cat_ft else None)
        for p_idx in range(max(self.num_parallel_tree, 1)):
            fmask_fn = self._feature_masks(iteration * 131 + p_idx, p_idx, d.num_col(),
                                           d.info.feature_weights)
            gp_all = self._subsample_mask(gpair, iteration * 131 + p_idx)
            for k in range(K):
                state = grower.grow(
                    d._pages, d.page_offsets(), gp_all[:, k, :], cache.valid,
                    d.cuts_pad, d.n_bins, feature_masks=fmask_fn,
                    cat_mask=cat_mask_np,
                )
                delta = leaf_margin_delta(state.pos, state.leaf_val)
                new_margin = new_margin.at[:, k].add(delta)
                tree = RegTree.from_grown(StreamingHistTreeGrower.to_host(state))
                tree.cuts_token = d._cuts.token
                self.trees.append(tree)
                self.tree_info.append(k)
                self.tree_weights.append(1.0)
        cache.margin = new_margin
        cache.n_trees_applied = len(self.trees)

    def _margin_delta_binned_cache(self, cache: _Cache, tree_slice: slice,
                                   init=None):
        """Margin over the cache's resident binned page (page-padded layout,
        rows align with cache.margin).  With ``init`` the result REPLACES the
        margin (accumulated in training order — bitwise-faithful rebuild)."""
        from .ops.predict import predict_margin_delta_binned

        stacked, groups, depth = self._stacked(tree_slice)
        Bw = cache.ellpack.cuts_pad.shape[1]
        args = (cache.bins, stacked["feat"], stacked["sbin"],
                stacked["dleft"], stacked["left"], stacked["right"],
                stacked["value"], groups)
        if stacked["catm"] is not None:
            args += (stacked["is_cat"], stacked["catm"])
        else:
            args += (None, None)
        return predict_margin_delta_binned(
            *args, init, n_groups=self.n_groups, depth=depth, n_bin=Bw)

    def _predict_extmem(self, data, tree_slice: slice) -> np.ndarray:
        """Batched binned prediction over host pages (no raw data needed)."""
        import jax.numpy as jnp

        from .ops.predict import predict_margin_delta_binned

        self._ensure_split_bins(tree_slice, data)
        stacked, groups, depth = self._stacked(tree_slice)
        Bw = data.cuts_pad.shape[1]
        outs = []
        for i, page in enumerate(data._pages):
            dev = jnp.asarray(np.ascontiguousarray(page))
            if stacked["catm"] is not None:
                m = predict_margin_delta_binned(
                    dev, stacked["feat"], stacked["sbin"], stacked["dleft"],
                    stacked["left"], stacked["right"], stacked["value"], groups,
                    stacked["is_cat"], stacked["catm"],
                    n_groups=self.n_groups, depth=depth, n_bin=Bw)
            else:
                m = predict_margin_delta_binned(
                    dev, stacked["feat"], stacked["sbin"], stacked["dleft"],
                    stacked["left"], stacked["right"], stacked["value"], groups,
                    n_groups=self.n_groups, depth=depth, n_bin=Bw)
            outs.append(np.asarray(m))  # PAGE-PADDED layout (padding rows kept)
        return np.concatenate(outs, axis=0)

    def _try_rebind_split_bins(self, tree_slice: slice, cuts) -> bool:
        """Gate for the binned margin route: True iff every tree's split_bins
        verifiably index THESE cuts.  Trees grown against a different cuts
        object (continued training on a new DMatrix / changed max_bin) are
        re-mapped exactly when possible; unmappable thresholds mean the cuts
        genuinely differ and the caller must take the raw-threshold route."""
        if all(t.cuts_token == cuts.token for t in self.trees[tree_slice]):
            return True
        try:
            self._ensure_split_bins(tree_slice, cuts=cuts)
        except ValueError:
            return False
        return True

    def _ensure_split_bins(self, tree_slice: slice, data=None, *, cuts=None) -> None:
        """Reconstruct split_bins for loaded models (split_bins is internal and
        not serialized): thr == cuts[f][sbin] exactly, so sbin is recoverable
        by an exact searchsorted against this matrix's cuts."""
        if cuts is None:
            cuts = data._cuts
        for t in self.trees[tree_slice]:
            if t.split_bins is not None and t.cuts_token == cuts.token:
                continue
            n = t.n_nodes
            sbin = np.zeros(n, np.int32)
            for nid in range(n):
                if t.left_children[nid] == -1:
                    continue
                if t.split_type is not None and t.split_type[nid] == 1:
                    continue  # categorical routes via the set, sbin unused
                f = int(t.split_indices[nid])
                seg = cuts.feature_cuts(f)
                b = int(np.searchsorted(seg, t.split_conditions[nid], side="left"))
                if b >= len(seg) or seg[b] != t.split_conditions[nid]:
                    raise ValueError(
                        "cannot map split threshold onto this matrix's bin "
                        "cuts; was the model trained with different cuts, or "
                        "with tree_method='exact' (raw-value thresholds)? "
                        "Use an in-memory DMatrix for prediction."
                    )
                sbin[nid] = b
            t.split_bins = sbin
            t.cuts_token = cuts.token

    def _rng(self, iteration: int, tag: int) -> np.random.Generator:
        seed = int(self.params.get("seed", 0))
        return np.random.default_rng((seed * 1_000_003 + iteration * 131 + tag) % (2**63))

    def _feature_masks(self, iteration: int, group: int, n_features: int,
                       feature_weights=None):
        """ColumnSampler (reference: src/common/random.h ColumnSampler):
        each level samples exactly max(1, frac*n_avail) of the surviving
        features without replacement; with ``feature_weights`` set the draw
        is weighted (WeightedSamplingWithoutReplacement — the
        Efraimidis-Spirakis exponential-key method)."""
        tp = self.tparam
        fw = None
        if feature_weights is not None:
            # validate unconditionally (accept-and-ignore is how the silent
            # no-op the reference never had slips back in)
            fw = np.asarray(feature_weights, np.float64).reshape(-1)
            if fw.shape[0] != n_features:
                raise ValueError(
                    f"feature_weights has {fw.shape[0]} entries for "
                    f"{n_features} features")
            if (fw < 0).any():
                raise ValueError("feature_weights must be non-negative")
            if not (fw > 0).any():
                raise ValueError("feature_weights sums to zero")
        if tp.colsample_bytree >= 1.0 and tp.colsample_bylevel >= 1.0 and tp.colsample_bynode >= 1.0:
            return None
        rng = self._rng(iteration, 17 + group)

        def sample(prev_mask, frac):
            if frac >= 1.0:
                return prev_mask
            m2 = np.atleast_2d(prev_mask)
            rows, F = m2.shape
            # exponential keys / weight, k smallest per row = a weighted
            # (uniform when fw is None) draw of k features w/o replacement,
            # vectorized across nodes
            w_row = np.ones(F, np.float64) if fw is None else fw
            with np.errstate(divide="ignore"):
                keys = rng.exponential(size=(rows, F)) / w_row
            keys = np.where(m2 & (w_row > 0), keys, np.inf)
            n_ok = np.isfinite(keys).sum(axis=1)
            if np.any(n_ok == 0):
                raise ValueError(
                    "feature_weights leaves no sampleable feature")
            k = np.minimum(
                np.maximum(1, (frac * m2.sum(axis=1)).astype(np.int64)),
                n_ok)
            order = np.argsort(keys, axis=1, kind="stable")
            ranks = np.empty_like(order)
            np.put_along_axis(
                ranks, order,
                np.broadcast_to(np.arange(F), (rows, F)).copy(), axis=1)
            out = ranks < k[:, None]
            return out if prev_mask.ndim == 2 else out[0]

        tree_mask = sample(np.ones(n_features, bool), tp.colsample_bytree)

        def per_level(depth: int, n_nodes: int):
            import jax.numpy as jnp

            m = sample(tree_mask, tp.colsample_bylevel)
            if tp.colsample_bynode < 1.0:
                mm = np.broadcast_to(m, (n_nodes, n_features)).copy()
                mm = sample(mm, tp.colsample_bynode)
                return jnp.asarray(mm)
            return jnp.asarray(m[None, :])

        return per_level

    def _subsample_mask(self, gpair, iteration: int):
        """Row subsampling: zeroed gpairs drop rows from hist + leaves.

        uniform: Bernoulli(subsample) (reference: src/tree/hist/sampler.cc).
        gradient_based: keep-probability proportional to the gradient norm
        sqrt(g^2 + lambda h^2) with 1/p reweighting so histogram sums stay
        unbiased (reference: src/tree/gpu_hist/sampler.cuh:129-135, the
        Ou 2020 out-of-core sampler).
        """
        import jax
        import jax.numpy as jnp

        if self.tparam.subsample >= 1.0:
            return gpair
        key = jax.random.PRNGKey(
            (int(self.params.get("seed", 0)) * 7919 + iteration) % (2**31)
        )
        if self.tparam.sampling_method == "gradient_based":
            lam = float(self.tparam.lambda_)
            norm = jnp.sqrt(gpair[..., 0] ** 2 + lam * gpair[..., 1] ** 2)
            norm = jnp.max(norm, axis=1)  # (R_pad,) across output groups
            total = jnp.maximum(jnp.sum(norm), 1e-12)
            target = self.tparam.subsample * jnp.sum(norm > 0)
            p = jnp.clip(norm * target / total, 0.0, 1.0)
            keep = jax.random.uniform(key, p.shape) < p
            scale = jnp.where(keep, 1.0 / jnp.maximum(p, 1e-12), 0.0)
            return gpair * scale[:, None, None]
        mask = jax.random.bernoulli(key, self.tparam.subsample, (gpair.shape[0],))
        return gpair * mask[:, None, None]

    def _process_parallel(self) -> bool:
        """True when training spans multiple processes (jax.distributed):
        each process holds a row shard and histograms cross processes via the
        host collective (the reference's rabit/NCCL role)."""
        from . import collective

        return collective.is_distributed()

    def _get_mesh(self):
        if self.n_devices == 1:
            return None
        if self._mesh is None:
            import jax

            from .parallel import make_mesh

            n = (self.n_devices if self.n_devices > 0
                 else jax.local_device_count())
            if n <= 1:
                return None
            self._mesh = make_mesh(n)
        return self._mesh

    def _boost_trees_exact_loop(self, cache: _Cache, gpair, iteration: int,
                                fobj, drop_idx) -> None:
        """The tree_method='exact' boosting round: host colmaker growth,
        reusing the DART / parallel-forest / column-sample machinery of the
        hist path without its sketch/Ellpack/jitted-grower startup."""
        drop_margin = None
        if drop_idx:
            gpair, drop_margin = self._dart_gpair(cache, drop_idx, fobj,
                                                  iteration)
        K = gpair.shape[1]
        if self.multi_strategy == "multi_output_tree" and K > 1:
            raise NotImplementedError(
                "tree_method='exact' with multi_output_tree is not "
                "supported yet")
        new_margin = cache.margin
        n_new = 0
        n_features = cache.dmat.num_col()
        for p_idx in range(max(self.num_parallel_tree, 1)):
            fmask_fn = self._feature_masks(iteration * 131 + p_idx, p_idx,
                                           n_features,
                                           cache.dmat.info.feature_weights)
            gp = self._subsample_mask(gpair, iteration * 131 + p_idx)
            for k in range(K):
                tree, delta = self._grow_exact_one(cache, gp, k, fmask_fn,
                                                   new_margin)
                new_margin = new_margin.at[:, k].add(delta)
                self.trees.append(tree)
                self.tree_info.append(k)
                self.tree_weights.append(1.0)
                n_new += 1
        if drop_idx:
            new_margin = self._dart_commit(cache, new_margin, n_new,
                                           drop_idx, drop_margin)
        cache.margin = new_margin
        cache.n_trees_applied = len(self.trees)

    def _grow_exact_one(self, cache: _Cache, gp, k: int, fmask_fn,
                        new_margin=None):
        """One tree_method="exact" round: host greedy enumeration over raw
        values (updater_colmaker.cc ColMaker) chained with the pruner the
        way the reference chains "grow_colmaker,prune"; returns
        (RegTree, margin delta padded to the cache layout)."""
        from .models.updaters import prune_tree
        from .tree.exact import grow_exact

        tp = self.tparam
        proc = self._process_parallel()
        if self._get_mesh() is not None:
            raise NotImplementedError(
                "tree_method='exact' is host-side greedy enumeration; an "
                "in-process device mesh gives it nothing — use hist")
        if cache.dmat.cat_mask() is not None and np.any(cache.dmat.cat_mask()):
            raise NotImplementedError(
                "tree_method='exact' does not support categorical features "
                "(same as the reference updater)")
        if tp.monotone_constraints is not None or tp.interaction_constraints:
            raise NotImplementedError(
                "constraints are not supported with tree_method='exact'; "
                "use hist or approx")
        if tp.grow_policy == "lossguide":
            raise ValueError("tree_method='exact' only supports depthwise "
                             "growth (driver.h lossguide needs hist/approx)")
        # X and its column argsort are round-invariant: cache both (the
        # colmaker builds its SortedCSC once per Update too); reuse the DART
        # path's device copy rather than recoding a second host copy
        if getattr(cache, "exact_X", None) is None:
            X_local = (np.asarray(cache.raw_X)
                       if cache.raw_X is not None
                       else self._host_dense_recoded(cache.dmat))
            if proc:
                # distributed exact, the updater_sync.cc pattern: every rank
                # sees the FULL row set (exact is a small-data method — the
                # reference steers big data to hist), trees are grown from
                # identical inputs and rank 0's copy is broadcast so the
                # model is bitwise-identical everywhere
                from . import collective

                sizes = collective.allgather(
                    np.asarray([X_local.shape[0]], np.int64))[:, 0]
                cache.exact_row_start = int(
                    sizes[: collective.get_rank()].sum())
                cache.exact_n_local = int(X_local.shape[0])
                cache.exact_X = collective.allgather_ragged(X_local)
            else:
                cache.exact_X = X_local
            cache.exact_order = np.argsort(cache.exact_X, axis=0,
                                           kind="stable").astype(np.int32)
        X = cache.exact_X
        R = X.shape[0]
        R_local = getattr(cache, "exact_n_local", R)
        row_start = getattr(cache, "exact_row_start", 0)

        def gather_rows(a: np.ndarray) -> np.ndarray:
            if not proc:
                return a
            from . import collective

            return collective.allgather_ragged(np.asarray(a))

        gh = np.asarray(
            gather_rows(np.asarray(gp[:R_local, k, :], np.float64)),
            np.float64)
        tree, pos = grow_exact(
            X, gh[:, 0], gh[:, 1],
            max_depth=int(tp.max_depth), max_leaves=int(tp.max_leaves),
            lambda_=float(tp.lambda_), alpha=float(tp.alpha),
            min_child_weight=float(tp.min_child_weight),
            max_delta_step=float(tp.max_delta_step),
            eta=float(tp.eta), feature_masks=fmask_fn,
            col_order=cache.exact_order,
        )
        tree, n_pruned = prune_tree(tree, gamma=float(tp.gamma),
                                    eta=float(tp.eta))
        if n_pruned:
            # node ids changed: re-route rows through the pruned tree
            from .models.updaters import _route_masks

            masks = _route_masks(tree, X)
            leaf_ids = np.nonzero(tree.left_children == -1)[0]
            pos = np.zeros(R, np.int32)
            for nid in leaf_ids:
                pos[masks[nid]] = nid
        if (hasattr(self.objective, "adaptive_leaf")
                and self.objective.adaptive_leaf()):
            # ObjFunction::UpdateTreeLeaf (src/objective/adaptive.cc):
            # refit each leaf to the weighted alpha-quantile of residuals
            # (against the RUNNING margin so num_parallel_tree>1 members
            # see earlier members' contributions, like the hist path)
            if getattr(cache, "exact_adaptive_meta", None) is None:
                # labels/valid/weights are round-invariant: gather once
                cache.exact_adaptive_meta = (
                    gather_rows(np.asarray(cache.labels)[:R_local]),
                    gather_rows(
                        np.asarray(cache.valid)[:R_local]).astype(bool),
                    (gather_rows(np.asarray(cache.weights)[:R_local])
                     if cache.weights is not None else None),
                )
            labels, valid, w = cache.exact_adaptive_meta
            margin_src = cache.margin if new_margin is None else new_margin
            margin_k = gather_rows(np.asarray(margin_src)[:R_local, k])
            residual = labels - margin_k
            alpha_q = float(self.objective.adaptive_alpha(k))
            for nid in np.nonzero(tree.left_children == -1)[0]:
                m = (pos == nid) & valid
                if not np.any(m):
                    continue
                res = residual[m]
                if w is None:
                    q = np.quantile(res, alpha_q)
                else:
                    srt = np.argsort(res)
                    cw = np.cumsum(w[m][srt])
                    q = res[srt][np.searchsorted(cw, alpha_q * cw[-1])]
                tree.split_conditions[nid] = np.float32(float(tp.eta) * q)
        if proc:
            # sync role (updater_sync.cc TreeSyncher): rank 0's tree is
            # authoritative — identical by construction, broadcast makes it
            # bitwise-guaranteed
            from . import collective
            from .models.tree import RegTree

            tree = RegTree.from_json_dict(
                collective.broadcast(tree.to_json_dict(0, 0), 0))
        delta = np.zeros(cache.margin.shape[0], np.float32)
        delta[:R_local] = tree.split_conditions[pos][
            row_start:row_start + R_local]
        return tree, delta

    def _boost_multi_target(self, cache: _Cache, gpair, iteration: int,
                            K: int, scalar_grower, cat_mask_np) -> None:
        """One vector-leaf tree per round: 2K-channel histogram, summed-gain
        splits, K-vector leaves (multi_target_tree_model.h,
        multi_evaluate_splits.cu)."""
        from .tree.grow_multi import (MultiTargetTreeGrower,
                                      leaf_margin_delta_multi)

        if self.booster_kind == "dart":
            raise NotImplementedError(
                "multi_strategy='multi_output_tree' with DART is not supported")
        if self.deterministic_histogram:
            raise NotImplementedError(
                "deterministic_histogram is not supported with "
                "multi_output_tree yet")
        if cat_mask_np is not None and np.any(cat_mask_np):
            raise NotImplementedError(
                "multi_output_tree with categorical features is not supported "
                "yet (same restriction as early reference versions)")
        mono = self.tparam.monotone_constraints
        if mono is not None and any(c != 0 for c in mono):
            raise NotImplementedError(
                "multi_output_tree with monotone constraints is not supported")
        mesh = self._get_mesh()
        proc_par = self._process_parallel()
        if mesh is not None and proc_par:
            raise NotImplementedError(
                "n_devices > 1 within a process is not combined with "
                "multi-process multi-target training yet")
        lossguide = self.tparam.grow_policy == "lossguide"
        # level-synchronous growth only here (no best-first node table), so
        # resolve the depth cap locally — the scalar grower may be a
        # BestFirstGrower whose max_depth of 0 means "unbounded"
        max_depth = self._resolve_max_depth(lossguide)
        ell = cache.ellpack
        mkey = ("multi", max_depth, self._split_params, K,
                id(mesh), proc_par, lossguide, self.tparam.max_leaves)
        grower = self._grower_cache.get(mkey)
        if grower is None:
            if mesh is not None:
                from .parallel.grower import ShardedMultiTargetGrower

                grower = ShardedMultiTargetGrower(
                    max_depth, self._split_params, K, mesh,
                    max_leaves=self.tparam.max_leaves, lossguide=lossguide)
            else:
                grower = MultiTargetTreeGrower(
                    max_depth, self._split_params, K,
                    max_leaves=self.tparam.max_leaves, lossguide=lossguide,
                    distributed=proc_par)
            self._grower_cache[mkey] = grower
        new_margin = cache.margin
        for p_idx in range(max(self.num_parallel_tree, 1)):
            fmask_fn = self._feature_masks(iteration * 131 + p_idx, p_idx,
                                           ell.n_features,
                                           cache.dmat.info.feature_weights)
            gp = self._subsample_mask(gpair, iteration * 131 + p_idx)
            state = grower.grow(cache.bins, gp, cache.valid, ell.cuts_pad,
                                ell.n_bins, feature_masks=fmask_fn)
            delta = leaf_margin_delta_multi(state.pos, state.leaf_val)
            new_margin = new_margin + delta
            tree = RegTree.from_grown_multi(
                MultiTargetTreeGrower.to_host(state), K)
            tree.cuts_token = ell.cuts.token
            self.trees.append(tree)
            self.tree_info.append(0)
            self.tree_weights.append(1.0)
        cache.margin = new_margin
        cache.n_trees_applied = len(self.trees)

    def _update_existing_trees(self, cache: _Cache, iteration: int) -> None:
        """process_type=update: run the non-growing updater sequence over
        one boosting round's worth of existing trees (gbtree.cc DoBoost with
        process_type=kUpdate; updaters prune/refresh/sync).

        Boosting semantics match the reference: round i's gradients come
        from a margin holding only the already-UPDATED trees 0..i-1 — the
        not-yet-updated tail of the old model is excluded, exactly as in
        ordinary boosting."""
        import jax.numpy as jnp

        from .models.updaters import prune_tree, refresh_tree, sync_trees

        if not self.updater_seq:
            raise ValueError(
                "process_type='update' requires updater=..., e.g. "
                "updater='refresh,prune'")
        bad = set(self.updater_seq) - {"prune", "refresh", "sync"}
        if bad:
            raise ValueError(f"unsupported updater(s) for process_type="
                             f"'update': {sorted(bad)}")
        tpr = self.trees_per_round
        start = iteration * tpr
        if start >= len(self.trees):
            raise ValueError(
                f"process_type='update' round {iteration} exceeds the "
                f"model's {len(self.trees) // tpr} boosted rounds")
        if cache.raw_X is None:
            cache.raw_X = jnp.asarray(self._host_dense_recoded(cache.dmat),
                                      jnp.float32)
        if getattr(cache, "_upd_margin_round", None) != iteration:
            # (re)build the margin of the already-updated prefix — correct
            # for a fresh cache at any starting round, not just round 0
            margin = cache.base_margin_init(self._base_margin_value,
                                            self.n_groups)
            if start > 0:
                delta = self._margin_for_trees(cache.raw_X,
                                               list(range(0, start)))
                pad = margin.shape[0] - delta.shape[0]
                if pad:
                    delta = jnp.concatenate(
                        [delta, jnp.zeros((pad, delta.shape[1]), jnp.float32)],
                        axis=0)
                margin = margin + delta
            cache._upd_margin = margin
        gpair = self.objective.get_gradient(
            cache._upd_margin, cache.labels, cache.weights, iteration
        ) * cache.valid[:, None, None]
        gp = np.asarray(gpair)
        valid = np.asarray(cache.valid).astype(bool)
        X = np.asarray(cache.raw_X)
        reduce = None
        if self._process_parallel():
            from . import collective

            reduce = collective.allreduce
        for tid in range(start, min(start + tpr, len(self.trees))):
            k = self.tree_info[tid]
            tree = self.trees[tid]
            for upd in self.updater_seq:
                if upd == "refresh":
                    tree = refresh_tree(
                        tree, X, gp[valid, k, 0], gp[valid, k, 1],
                        eta=float(self.tparam.eta),
                        lambda_=float(self.tparam.lambda_),
                        alpha=float(self.tparam.alpha),
                        refresh_leaf=self.refresh_leaf,
                        reduce=reduce)
                elif upd == "prune":
                    tree, _ = prune_tree(
                        tree, gamma=float(self.tparam.gamma),
                        eta=float(self.tparam.eta),
                        max_depth=max(int(self.tparam.max_depth), 0))
            self.trees[tid] = tree
        if "sync" in self.updater_seq:
            self.trees, self.tree_info, self.tree_weights = sync_trees(
                self.trees, self.tree_info, self.tree_weights)
        # advance the running margin by this round's UPDATED trees
        delta = self._margin_for_trees(
            cache.raw_X, list(range(start, min(start + tpr, len(self.trees)))))
        pad = cache._upd_margin.shape[0] - delta.shape[0]
        if pad:
            delta = jnp.concatenate(
                [delta, jnp.zeros((pad, delta.shape[1]), jnp.float32)], axis=0)
        cache._upd_margin = cache._upd_margin + delta
        cache._upd_margin_round = iteration + 1
        # structure/values changed: every cached margin must rebuild (the
        # weights_version mismatch makes _sync_margin start from scratch)
        self._weights_version = getattr(self, "_weights_version", 0) + 1

    def _select_dart_drops(self, iteration: int) -> List[int]:
        """Draw the round's dropped-tree set (gbtree.cc Dart::DropTrees).
        Deterministic per iteration; empty when dropout does not fire."""
        if not (self.booster_kind == "dart" and self.trees
                and self.rate_drop > 0.0):
            return []
        rng = self._rng(iteration, 97)
        if rng.random() < self.skip_drop:
            return []
        n = len(self.trees)
        if self.sample_type == "weighted":
            wts = np.asarray(self.tree_weights, np.float64)
            prob = wts / max(wts.sum(), 1e-16)
            k_drop = int(rng.binomial(n, self.rate_drop))
            if k_drop == 0 and self.one_drop:
                k_drop = 1
            if k_drop == 0:
                return []
            return list(rng.choice(n, size=min(k_drop, n), replace=False,
                                   p=prob))
        mask = rng.random(n) < self.rate_drop
        drop_idx = list(np.nonzero(mask)[0])
        if not drop_idx and self.one_drop:
            drop_idx = [int(rng.integers(0, n))]
        return drop_idx

    def _boost_trees(self, cache: _Cache, gpair, iteration: int,
                     fobj=None, drop_idx=()) -> None:
        """gpair may be None when drop_idx is non-empty (DART round): the
        gradient is then computed here, on the dropout-reduced margin."""
        import jax.numpy as jnp

        if cache.is_extmem:
            if self.tree_method == "exact":
                raise NotImplementedError(
                    "tree_method='exact' needs raw in-memory values; it is "
                    "not supported with ExtMemQuantileDMatrix (the reference "
                    "restricts exact to SimpleDMatrix too)")
            if self.booster_kind == "dart":
                raise ValueError("booster='dart' is not supported with "
                                 "ExtMemQuantileDMatrix yet")
            # process-DP x chip-DP composes here too: pages GSPMD-shard
            # over the local mesh inside _page_step and the level histogram
            # crosses processes via the host allreduce (the same layering
            # as ProcessHistTreeGrower; exact under deterministic_histogram)
            return self._boost_trees_extmem(cache, gpair, iteration)
        exact = self.tree_method == "exact"
        if exact and self.deterministic_histogram:
            raise NotImplementedError(
                "deterministic_histogram applies to histogram growers; "
                "tree_method='exact' has no histogram")
        if exact:
            # the exact branch walks raw host values: no sketch, no Ellpack,
            # no jitted grower — building them here would be pure waste
            if self.tparam.max_depth <= 0 and self.tparam.max_leaves <= 0:
                raise ValueError(
                    "tree_method='exact' with max_depth=0 needs a positive "
                    "max_leaves to bound the tree")
            self._boost_trees_exact_loop(cache, gpair, iteration, fobj,
                                         drop_idx)
            return
        ell = cache.ellpack
        mono = self.tparam.monotone_constraints
        if mono is not None and len(mono) != ell.n_features:
            raise ValueError(
                f"monotone_constraints has {len(mono)} entries but data has "
                f"{ell.n_features} features"
            )
        lossguide = self.tparam.grow_policy == "lossguide"
        mesh = self._get_mesh()
        proc_par = self._process_parallel()
        # true global best-first for lossguide with a leaf budget (driver.h
        # priority queue): unbounded depth, node-table layout — under mesh
        # sharding (GSPMD hist psum) and process parallelism (host
        # AllReduceHist per expansion) alike, so distributed lossguide grows
        # the same trees as single-device
        best_first = lossguide and self.tparam.max_leaves > 1
        max_depth = self.tparam.max_depth
        if max_depth <= 0:
            # best-first: depth bounded only by the leaf budget
            max_depth = 0 if best_first else self._resolve_max_depth(lossguide)
        det = self.deterministic_histogram
        gkey = (max_depth, id(mesh), self._split_params,
                self.tparam.interaction_constraints, self.tparam.max_leaves,
                lossguide, proc_par, best_first, det)
        if not hasattr(self, "_grower_cache"):
            self._grower_cache = {}
        grower = self._grower_cache.get(gkey)
        if grower is None:
            if best_first:
                from .tree.bestfirst import BestFirstGrower

                if det:
                    raise NotImplementedError(
                        "deterministic_histogram is not supported with the "
                        "best-first (lossguide + max_leaves) grower yet")
                if proc_par and mesh is not None:
                    raise NotImplementedError(
                        "n_devices > 1 within a process is not combined "
                        "with multi-process training yet; give each process "
                        "one device")
                grower = BestFirstGrower(
                    max_depth,
                    self._split_params,
                    max_leaves=self.tparam.max_leaves,
                    interaction_sets=self.tparam.interaction_constraints,
                    distributed=proc_par,
                    mesh=mesh,
                )
            elif proc_par:
                from .parallel.process import ProcessHistTreeGrower

                # mesh may be non-None here: process-DP x chip-DP — each
                # process shards its rows over its LOCAL chips (GSPMD psum)
                # and histograms cross processes via the host collective
                # (rabit x NCCL layering, src/collective/comm.cuh:51)
                grower = ProcessHistTreeGrower(
                    max_depth,
                    self._split_params,
                    interaction_sets=self.tparam.interaction_constraints,
                    max_leaves=self.tparam.max_leaves,
                    lossguide=lossguide,
                    mesh=mesh,
                    quantised=det,
                )
            elif mesh is not None:
                from .parallel import ShardedHistTreeGrower

                # cached: ShardedHistTreeGrower wraps fresh shard_map jits, so
                # rebuilding per round would recompile every level program
                grower = ShardedHistTreeGrower(
                    max_depth,
                    self._split_params,
                    mesh,
                    interaction_sets=self.tparam.interaction_constraints,
                    max_leaves=self.tparam.max_leaves,
                    lossguide=lossguide,
                    quantised=det,
                )
            else:
                grower = HistTreeGrower(
                    max_depth,
                    self._split_params,
                    interaction_sets=self.tparam.interaction_constraints,
                    max_leaves=self.tparam.max_leaves,
                    lossguide=lossguide,
                    quantised=det,
                )
            self._grower_cache[gkey] = grower
        adaptive = (
            hasattr(self.objective, "adaptive_leaf") and self.objective.adaptive_leaf()
        )

        # ---- DART dropout (reference: gbtree.cc Dart::DoBoost + DropTrees) ----
        drop_margin = None
        if drop_idx:
            gpair, drop_margin = self._dart_gpair(cache, drop_idx, fobj,
                                                  iteration)

        K = gpair.shape[1]
        new_margin = cache.margin
        n_new = 0
        cat_mask_np = cache.dmat.cat_mask()
        if self.multi_strategy == "multi_output_tree" and K > 1:
            if self.tree_method in ("approx", "exact"):
                raise NotImplementedError(
                    f"tree_method={self.tree_method!r} with multi_output_tree "
                    "is not supported yet")
            return self._boost_multi_target(cache, gpair, iteration, K,
                                            grower, cat_mask_np)
        bins_use, cuts_use, nbins_use = cache.bins, ell.cuts_pad, ell.n_bins
        cuts_token_use = ell.cuts.token
        # the one-hot a column's bins tall (ops/histogram.py bin_tiers): one
        # chip's float32 matmul over the resident page; a mesh, processes,
        # the int8 limbs and a page binned anew each round build one tier
        resident = (mesh is None and not proc_par and not det
                    and self.tree_method != "approx"
                    and not hist_is_row_pass())
        tiers_use = ell.tiers if resident else None
        tiers_arg = {} if tiers_use is None else {"tiers": tiers_use}
        if resident and not best_first:
            # the same page tree after tree: the depth-wise grower keeps a
            # transposed copy of it for the levels that the one-pass kernel
            # builds (ops/histogram.py hist_form)
            tiers_arg["resident"] = True
        if self.tree_method == "approx":
            # grow_histmaker (updater_approx.cc): fresh hessian-weighted
            # sketch every iteration, then the same hist machinery; cut
            # width pinned to max_bin so the jitted level programs are
            # shared across rounds
            from .data.ellpack import build_ellpack
            from .data.quantile import sketch_dense, sketch_distributed

            valid_np = np.asarray(cache.valid).astype(bool)
            hess_w = np.asarray(gpair)[..., 1].sum(axis=1)[valid_np]
            Xh = self._host_dense_recoded(cache.dmat)
            if self._process_parallel():
                # per-shard grids must merge or workers bin against
                # different value ranges (quantile.cc AllreduceV role)
                cuts = sketch_distributed(Xh, self.tparam.max_bin,
                                          weights=hess_w.astype(np.float64),
                                          cat_mask=cache.dmat.cat_mask())
            else:
                cuts = sketch_dense(Xh, self.tparam.max_bin,
                                    weights=hess_w.astype(np.float64),
                                    use_device=False,
                                    cat_mask=cache.dmat.cat_mask())
            # must pad exactly like the resident cache page (lcm alignment
            # for arbitrary device counts — see _Cache.ensure)
            import math

            mesh_a = self._get_mesh()
            align_a = 1024 if mesh_a is None else math.lcm(
                1024, mesh_a.devices.size)
            ell_iter = build_ellpack(Xh, cuts, row_align=align_a)
            if ell_iter.n_padded != cache.bins.shape[0]:
                raise AssertionError("approx page padding mismatch")
            bins_use = jnp.asarray(ell_iter.bins)
            cuts_use = jnp.asarray(cuts.padded(self.tparam.max_bin))
            nbins_use = jnp.asarray(cuts.n_bins_array())
            # these trees' split_bins index the per-iteration sketch, NOT the
            # resident ellpack: stamping the ellpack's token would falsely
            # certify the binned cached-margin route
            cuts_token_use = cuts.token
            if self._get_mesh() is not None:
                from .parallel import shard_rows

                (bins_use,) = shard_rows(self._get_mesh(), bins_use)
        # Lockstep class batching (opt-in, _lockstep=1): the K independent
        # per-class trees of a round advance level-by-level together in ONE
        # jitted program per level, sharing the split scan and position
        # rewrite (the reference's all-targets-per-pass shape,
        # src/tree/hist/histogram.h:44).  Bitwise-identical trees to the
        # sequential loop (tests/test_lockstep.py).  Default OFF: on the
        # CPU backend the K-stacked level intermediates measured ~1.5x
        # SLOWER than the sequential padded-level grower at covertype
        # shapes; the batched formulation is aimed at the TPU matmul path,
        # where the class axis widens the MXU output tile — to be
        # re-evaluated on hardware.
        lockstep_ok = (
            K > 1 and mesh is None and not proc_par and not best_first
            and not det and cat_mask_np is None and not adaptive
            and str(self.params.get("_lockstep", "0")).lower()
            in ("1", "true"))
        for p_idx in range(max(self.num_parallel_tree, 1)):
            with span("update.sample"):
                fmask_fn = self._feature_masks(
                    iteration * 131 + p_idx, p_idx, ell.n_features,
                    cache.dmat.info.feature_weights)
                # one independent subsample per parallel tree (reference:
                # each member of the forest draws its own rows)
                gp = self._subsample_mask(gpair, iteration * 131 + p_idx)
            if lockstep_ok and fmask_fn is None:
                from .tree.grow_lockstep import (LockstepHistGrower,
                                                 leaf_margin_delta_k)

                lk_key = ("lockstep", max_depth, self._split_params,
                          self.tparam.interaction_constraints,
                          self.tparam.max_leaves, lossguide)
                lk = self._grower_cache.get(lk_key)
                if lk is None:
                    lk = LockstepHistGrower(
                        max_depth, self._split_params,
                        interaction_sets=self.tparam.interaction_constraints,
                        max_leaves=self.tparam.max_leaves,
                        lossguide=lossguide)
                    self._grower_cache[lk_key] = lk
                state = lk.grow(bins_use, gp, cache.valid, cuts_use,
                                nbins_use)
                new_margin = new_margin + leaf_margin_delta_k(
                    state.pos, state.leaf_val).T
                for k in range(K):
                    tree = RegTree.from_grown(lk.to_host_class(state, k))
                    tree.cuts_token = cuts_token_use
                    self.trees.append(tree)
                    self.tree_info.append(k)
                    self.tree_weights.append(1.0)
                    n_new += 1
                continue
            for k in range(K):
                with span("update.class_gradient"):  # an eager slice
                    gp_k = gp[:, k, :]
                state = grower.grow(
                    bins_use,
                    gp_k,
                    cache.valid,
                    cuts_use,
                    nbins_use,
                    feature_masks=fmask_fn,
                    cat_mask=cat_mask_np,
                    **tiers_arg,
                )
                pos = state.pos
                if best_first:
                    tree, leaf_val = grower.to_regtree(state, cuts_use)
                else:
                    tree = None
                    leaf_val = state.leaf_val
                if adaptive:
                    if best_first:
                        is_leaf = jnp.zeros(grower.n_slots, bool).at[
                            : tree.n_nodes].set(
                                jnp.asarray(tree.left_children == -1))
                        n_slots = grower.n_slots
                    else:
                        is_leaf, n_slots = state.is_leaf, grower.max_nodes
                    # exact quantile leaves (ObjFunction::UpdateTreeLeaf,
                    # src/objective/adaptive.cc)
                    from .ops.adaptive import segment_quantile_leaf

                    residual = cache.labels - new_margin[:, k]
                    q_pos, q_res, q_valid = pos, residual, cache.valid
                    if proc_par:
                        # the quantile must see the GLOBAL leaf population
                        # or ranks refit different leaf values from their
                        # local shards (adaptive.cc runs under the
                        # collective); gather like the exact path does
                        from . import collective

                        q_pos = jnp.asarray(collective.allgather_ragged(
                            np.asarray(pos)))
                        q_res = jnp.asarray(collective.allgather_ragged(
                            np.asarray(residual)))
                        q_valid = jnp.asarray(collective.allgather_ragged(
                            np.asarray(cache.valid)))
                    leaf_val = segment_quantile_leaf(
                        q_pos, q_res, q_valid, is_leaf,
                        float(self.objective.adaptive_alpha(k)),
                        float(self.tparam.eta), max_nodes=n_slots,
                    )
                    if best_first:
                        lv = np.asarray(leaf_val)[: tree.n_nodes]
                        lm = tree.left_children == -1
                        tree.split_conditions[lm] = lv[lm]
                    else:
                        state = state._replace(leaf_val=leaf_val)
                with span("grow.margin"):
                    delta = leaf_margin_delta(pos, leaf_val)
                    new_margin = new_margin.at[:, k].add(delta)
                if tree is None:
                    grown = HistTreeGrower.to_host(state)
                    with span("tree.from_grown"):
                        tree = RegTree.from_grown(grown)
                tree.cuts_token = cuts_token_use
                self.trees.append(tree)
                self.tree_info.append(k)
                self.tree_weights.append(1.0)
                n_new += 1

        if drop_idx:
            new_margin = self._dart_commit(cache, new_margin, n_new,
                                           drop_idx, drop_margin)

        cache.margin = new_margin
        cache.n_trees_applied = len(self.trees)

    def _dart_gpair(self, cache: _Cache, drop_idx, fobj, iteration: int):
        """Gradients for a DART drop round, computed on the margin WITHOUT
        the dropped trees (gbtree.cc Dart::DoBoost; the caller skipped its
        own gradient pass so a custom fobj runs exactly once per round)."""
        import jax.numpy as jnp

        if cache.raw_X is None:
            cache.raw_X = jnp.asarray(self._host_dense_recoded(cache.dmat),
                                      jnp.float32)
        drop_margin = self._margin_for_trees(cache.raw_X, drop_idx)
        pad = cache.margin.shape[0] - drop_margin.shape[0]
        if pad:
            drop_margin = jnp.concatenate(
                [drop_margin,
                 jnp.zeros((pad, drop_margin.shape[1]), jnp.float32)],
                axis=0,
            )
        reduced = cache.margin - drop_margin
        if fobj is not None:
            # custom objective: invoke on the reduced RAW margin (advisor
            # round-1: silently falling back to the built-in objective
            # trained the drop round on the wrong loss)
            gpair = self._fobj_gpair(cache, fobj, reduced, cache.dmat)
        else:
            gpair = self.objective.get_gradient(
                reduced, cache.labels, cache.weights, iteration
            )
        return gpair * cache.valid[:, None, None], drop_margin

    def _dart_commit(self, cache: _Cache, new_margin, n_new: int, drop_idx,
                     drop_margin):
        """DART post-round rescale (Dart::NormalizeTrees): with k dropped and
        lr=eta — 'tree': new *= 1/(k+lr), dropped *= k/(k+lr); 'forest':
        both /(1+lr).  Returns the rebuilt margin."""
        k_d = len(drop_idx)
        lr = float(self.tparam.eta)
        if self.normalize_type == "forest":
            new_w = 1.0 / (1.0 + lr)
            factor = 1.0 / (1.0 + lr)
        else:
            new_w = 1.0 / (k_d + lr)
            factor = k_d / (k_d + lr)
        for t in range(len(self.trees) - n_new, len(self.trees)):
            self.tree_weights[t] = new_w
        for t in drop_idx:
            self.tree_weights[t] *= factor
        # margin: dropped trees shrank by `factor`, new trees contribute
        # scaled by new_w; rebuild incrementally
        new_contrib = new_margin - cache.margin  # unscaled new trees
        new_margin = (
            cache.margin
            - (1.0 - factor) * drop_margin
            + new_w * new_contrib
        )
        self._weights_version = getattr(self, "_weights_version", 0) + 1
        cache.weights_version = self._weights_version
        return new_margin

    # ------------------------------------------------------------------ eval
    def eval_set(self, evals: Sequence[Tuple[DMatrix, str]], iteration: int = 0,
                 feval=None, output_margin: bool = True) -> str:
        """(reference: learner.cc:1159 EvalOneIter)"""
        self._configure()
        msgs = [f"[{iteration}]"]
        metrics = self._eval_metric_list()
        proc_par = self._process_parallel()
        for dmat, name in evals:
            with wait_span("eval.predict"):
                margin = self._eval_margin(dmat)
            preds = np.asarray(self.objective.pred_transform(margin))
            if self.n_groups == 1:
                preds = preds[:, 0]
            labels = dmat.get_label()
            weights = dmat.get_weight()
            mkw = dict(group_ptr=dmat.info.group_ptr)
            if dmat.info.label_lower_bound is not None:
                mkw["y_lower"] = dmat.info.label_lower_bound
                ub = dmat.info.label_upper_bound
                mkw["y_upper"] = (np.full_like(mkw["y_lower"], np.inf)
                                  if ub is None else ub)
            if hasattr(self.objective, "dist"):
                mkw["dist"] = self.objective.dist
                mkw["sigma"] = self.objective.sigma
            if "huber_slope" in self.params:
                mkw["slope"] = float(self.params["huber_slope"])
            if hasattr(self.objective, "_alphas") and self.n_groups > 1:
                mkw["alphas"] = self.objective._alphas()
            for fn, mname in metrics:
                kw = dict(mkw)
                lab = labels
                if "alphas" in kw:
                    import inspect

                    base_fn = getattr(fn, "__wrapped__", fn)
                    if "alphas" not in inspect.signature(base_fn).parameters:
                        # generic elementwise metric on a multi-alpha model:
                        # tile labels so (R, Q) preds broadcast per level
                        kw.pop("alphas")
                        if np.ndim(preds) == 2 and np.ndim(lab) == 1:
                            lab = np.repeat(np.asarray(lab)[:, None],
                                            preds.shape[1], axis=1)
                if proc_par:
                    # distributed eval: every rank reports the GLOBAL metric
                    # via per-metric partial-sum allreduce (the reference's
                    # aggregator.h GlobalSum/GlobalRatio design) — O(local)
                    # memory per rank, early stopping stays in lockstep
                    from .metric import distributed_reduction

                    with distributed_reduction():
                        v = fn(preds, lab, weights, **kw)
                else:
                    v = fn(preds, lab, weights, **kw)
                msgs.append(f"{name}-{mname}:{v:g}")
            if feval is not None:
                res = feval(margin if output_margin else preds, dmat)
                res = [res] if isinstance(res, tuple) else res
                for mname, v in res:
                    # under process parallelism feval sees only the local
                    # shard while built-in metrics reduce globally; average
                    # it across ranks so eval logs (and early stopping keyed
                    # to it) stay in lockstep (ADVICE r3)
                    if proc_par:
                        from . import collective

                        num, den = collective.global_sum(
                            np.array([float(v), 1.0], np.float64))
                        v = num / den
                    msgs.append(f"{name}-{mname}:{v:g}")
        return "\t".join(msgs)

    def _eval_metric_list(self):
        self._configure()
        names = self.params.get("eval_metric", None)
        if names is None:
            if str(self.params.get("disable_default_eval_metric", "0")).lower() in ("1", "true"):
                return []
            names = [self.objective.default_metric()]
        elif isinstance(names, str):
            names = [names]
        return [create_metric(n) for n in names]

    def _eval_margin(self, dmat: DMatrix) -> np.ndarray:
        """Margin for an eval/predict DMatrix using the incremental cache."""
        import jax.numpy as jnp

        cache = self._get_cache(dmat)
        self._sync_margin(cache)
        if cache.is_extmem:
            cache.ensure_train()
            return np.asarray(cache.margin)[np.asarray(cache.valid)]
        R = dmat.num_row()
        return np.asarray(cache.margin[:R])

    # ------------------------------------------------------------------ predict
    def _stacked(self, tree_slice: slice, tree_ids: Optional[Sequence[int]] = None):
        if tree_ids is not None:
            trees = [self.trees[i] for i in tree_ids]
            info = [self.tree_info[i] for i in tree_ids]
            wts = [self.tree_weights[i] if self.tree_weights else 1.0 for i in tree_ids]
        else:
            trees = self.trees[tree_slice]
            info = self.tree_info[tree_slice]
            wts = (self.tree_weights[tree_slice]
                   if self.tree_weights else [1.0] * len(trees))
        from .ops.predict import bucket_width

        # pow2 node-pad width: stacked shape (and the compiled program) stays
        # put as trees drift in size across rounds (ops/predict.py bucket cache)
        width = bucket_width(max((t.n_nodes for t in trees), default=1))
        depth = max((t.max_depth for t in trees), default=0) + 1
        has_cat = any(t.has_categorical for t in trees)
        is_multi = any(t.leaf_vector is not None for t in trees)
        keys = ("feat", "thr", "dleft", "left", "right", "value", "is_cat",
                "sbin") + (("value_vec",) if is_multi else ())
        cols = {k: [] for k in keys}
        cats = []
        n_cats = max((t.max_category for t in trees), default=-1) + 1 if has_cat else 0
        for t, w in zip(trees, wts):
            arrs = t.padded_arrays(width)
            if w != 1.0:  # DART per-tree weight (gbtree.cc weight_drop_)
                arrs = dict(arrs)
                arrs["value"] = arrs["value"] * np.float32(w)
            for k in cols:
                cols[k].append(arrs[k])
            if has_cat:
                cats.append(t.cat_matrix(width, n_cats))
        import jax.numpy as jnp

        stacked = {k: jnp.asarray(np.stack(v)) for k, v in cols.items()}
        stacked["catm"] = jnp.asarray(np.stack(cats)) if has_cat else None
        groups = jnp.asarray(np.asarray(info, np.int32))
        return stacked, groups, depth

    def _margin_for_trees(self, X_dev, tree_ids: Sequence[int]):
        stacked, groups, depth = self._stacked(slice(0, 0), tree_ids=tree_ids)
        return self._run_predict(X_dev, stacked, groups, depth)

    def _run_predict(self, X_dev, stacked, groups, depth, init=None):
        """Dispatch one stacked-ensemble margin pass through the shared row
        bucket cache (ops/predict.py): rows pad to the bucket shape so repeat
        callers — eval sets, serving, continuation — reuse compiled programs;
        a batch already at its bucket shape is passed through untouched."""
        from .ops.predict import bucket_rows, pad_margin, pad_rows

        R = X_dev.shape[0]
        bucket = bucket_rows(R)
        X_dev = pad_rows(X_dev, bucket)
        init = pad_margin(init, bucket)
        out = self._run_predict_padded(X_dev, stacked, groups, depth, init)
        return out if bucket == R else out[:R]

    def _run_predict_padded(self, X_dev, stacked, groups, depth, init=None):
        from .ops.predict import run_stacked_margin

        return run_stacked_margin(X_dev, stacked, groups, depth,
                                  self.n_groups, init)

    # past this many dense f32 elements (256 MB) sparse inputs are predicted
    # in fixed-size row windows instead of one dense device matrix
    _PREDICT_BUFFER_ELEMS = 1 << 26

    def _margin_delta_for(self, X_dev, tree_slice: slice, init=None):
        stacked, groups, depth = self._stacked(tree_slice)
        return self._run_predict(X_dev, stacked, groups, depth, init=init)

    def _use_streamed_predict(self, data: DMatrix) -> bool:
        """Sparse matrices whose dense form would not fit the predict buffer
        stream through fixed row windows (the role of the SparsePage loader
        vs dense loader split, gpu_predictor.cu:43-90)."""
        if getattr(data, "_kind", "dense") != "csr":
            return False
        R, F = data.num_row(), data.num_col()
        return R * F > self._PREDICT_BUFFER_ELEMS

    def _margin_delta_streamed(self, data: DMatrix, tree_slice: slice) -> np.ndarray:
        """Margin delta over a sparse matrix in bounded memory: densify one
        fixed-shape row window at a time (padded so every window hits the same
        compiled program) and accumulate on host."""
        import jax.numpy as jnp

        stacked, groups, depth = self._stacked(tree_slice)
        R, F = data.num_row(), data.num_col()
        win = max(1024, int((1 << 22) // max(F, 1)))  # ~16 MB dense window
        out = np.empty((R, self.n_groups), np.float32)
        for lo in range(0, R, win):
            hi = min(lo + win, R)
            chunk = data.host_dense_rows(lo, hi)
            if hi - lo < win:  # pad the tail window to the static shape
                chunk = np.pad(chunk, ((0, win - (hi - lo)), (0, 0)),
                               constant_values=np.nan)
            delta = self._run_predict(jnp.asarray(chunk, jnp.float32),
                                      stacked, groups, depth)
            out[lo:hi] = np.asarray(delta)[: hi - lo]
        return out

    def predict(
        self,
        data: DMatrix,
        output_margin: bool = False,
        pred_leaf: bool = False,
        pred_contribs: bool = False,
        approx_contribs: bool = False,
        pred_interactions: bool = False,
        validate_features: bool = True,
        training: bool = False,
        iteration_range: Tuple[int, int] = (0, 0),
        strict_shape: bool = False,
    ) -> np.ndarray:
        """(reference: core.py:2424 Booster.predict)"""
        import jax.numpy as jnp

        self._configure()
        if self.booster_kind == "gblinear":
            if pred_leaf:
                raise ValueError("pred_leaf is not defined for the gblinear booster")
            if pred_interactions:
                raise ValueError("pred_interactions is not supported for gblinear")
            if pred_contribs:
                return self._linear_contribs(data)
            return self._predict_linear(data, output_margin, strict_shape)
        lo, hi = iteration_range
        n_rounds = self.num_boosted_rounds()
        if hi == 0:
            hi = n_rounds
        if self.best_iteration is not None and iteration_range == (0, 0) and not training:
            pass  # reference keeps all trees unless user slices
        tpr = self.trees_per_round
        tree_slice = slice(lo * tpr, hi * tpr)
        if hasattr(data, "_pages"):  # external-memory: binned page predict
            if pred_leaf or pred_contribs or pred_interactions:
                raise ValueError(
                    "pred_leaf/pred_contribs are not supported for "
                    "ExtMemQuantileDMatrix; predict on an in-memory DMatrix"
                )
            base = np.broadcast_to(self.base_score.reshape(-1), (self.n_groups,))
            if len(self.trees) and tree_slice.start < tree_slice.stop:
                if getattr(data, "has_raw_pages", False):
                    # SparsePageDMatrix: raw-value traversal page by page —
                    # exact float thresholds, works for any model (incl.
                    # ones trained on other cuts or with tree_method=exact)
                    import jax.numpy as jnp

                    margin = np.concatenate([
                        np.asarray(self._margin_delta_for(
                            jnp.asarray(pg), tree_slice))
                        for pg in data.raw_dense_pages()
                    ]) + base[None, :]
                else:
                    padded = self._predict_extmem(data, tree_slice)
                    margin = padded[data.valid_mask()] + base[None, :]
            else:
                margin = np.broadcast_to(base, (data.num_row(), self.n_groups)).copy()
            if data.info.base_margin is not None:
                um = np.asarray(data.info.base_margin, np.float32).reshape(
                    data.num_row(), -1)
                margin = margin - base[None, :] + um
            if output_margin:
                out = margin
            else:
                import jax.numpy as jnp

                out = np.asarray(self.objective.pred_transform(jnp.asarray(margin)))
            return out[:, 0] if self.n_groups == 1 and not strict_shape else out
        streamed = self._use_streamed_predict(data)
        X = None if streamed else jnp.asarray(self._host_dense_recoded(data), jnp.float32)
        if pred_leaf:
            if streamed:
                raise ValueError(
                    "pred_leaf on a large sparse matrix would materialize the "
                    "dense form; predict in row slices instead")
            if not self.trees[tree_slice]:
                return np.zeros((data.num_row(), 0), np.int32)
            stacked, groups, depth = self._stacked(tree_slice)
            out = predict_leaf_ids(
                X, stacked["feat"], stacked["thr"], stacked["dleft"],
                stacked["left"], stacked["right"], depth=depth,
            )
            return np.asarray(out)
        if pred_contribs or pred_interactions:
            from .interpret import predict_contribs, predict_interactions

            if pred_interactions:
                return predict_interactions(self, data, tree_slice)
            return predict_contribs(self, data, tree_slice, approx=approx_contribs)
        base = np.broadcast_to(self.base_score.reshape(-1), (self.n_groups,))
        if len(self.trees) and tree_slice.start < tree_slice.stop:
            if streamed:
                margin = self._margin_delta_streamed(data, tree_slice) + base[None, :]
            else:
                margin = np.asarray(self._margin_delta_for(X, tree_slice)) + base[None, :]
        else:
            margin = np.broadcast_to(base, (data.num_row(), self.n_groups)).copy()
        if data.info.base_margin is not None:
            um = np.asarray(data.info.base_margin, np.float32).reshape(data.num_row(), -1)
            margin = margin - base[None, :] + um
        if output_margin:
            out = margin
        else:
            out = np.asarray(self.objective.pred_transform(jnp.asarray(margin)))
        if self.n_groups == 1 and not strict_shape:
            out = out[:, 0]
        return out

    def _linear_contribs(self, data: DMatrix) -> np.ndarray:
        """Linear contributions: phi_f = w_f * x_f, bias column last
        (reference: gblinear.cc PredictContribution)."""
        self._configure()
        X = np.nan_to_num(self._host_dense_recoded(data), nan=0.0)
        R, F = X.shape
        K = self.n_groups
        W = self.linear_weights if self.linear_weights is not None else np.zeros((F, K), np.float32)
        b = self.linear_bias if self.linear_bias is not None else np.zeros(K, np.float32)
        base = np.broadcast_to(self.base_score.reshape(-1), (K,))
        out = np.zeros((R, K, F + 1), np.float64)
        for k in range(K):
            out[:, k, :F] = X * W[:, k][None, :]
            out[:, k, F] = b[k] + base[k]
        return out[:, 0, :] if K == 1 else out

    def _predict_linear(self, data: DMatrix, output_margin: bool, strict_shape: bool):
        import jax.numpy as jnp

        from .models.gblinear import linear_predict

        self._configure()
        X = jnp.asarray(self._host_dense_recoded(data), jnp.float32)
        base = np.broadcast_to(self.base_score.reshape(-1), (self.n_groups,))
        if self.linear_weights is None:
            margin = np.broadcast_to(base, (data.num_row(), self.n_groups)).copy()
        else:
            margin = np.asarray(
                linear_predict(X, jnp.asarray(self.linear_weights),
                               jnp.asarray(self.linear_bias))
            ) + base[None, :]
        if output_margin:
            out = margin
        else:
            out = np.asarray(self.objective.pred_transform(jnp.asarray(margin)))
        if self.n_groups == 1 and not strict_shape:
            out = out[:, 0]
        return out

    def inference_snapshot(self):
        """Freeze this booster into an immutable, device-resident
        :class:`xgboost_tpu.serving.InferenceSnapshot` — the unit the serving
        engine registers, batches over, and LRU-caches.  Mutating the booster
        afterwards (continued training, set_attr) does not affect snapshots
        already taken."""
        from .serving.snapshot import InferenceSnapshot

        return InferenceSnapshot.from_booster(self)

    def get_categories(self) -> Optional[Dict[str, list]]:
        """Train-time category mapping ``{feature name (or index): values}``
        for categorical features, or None when the model was trained without
        frame-level categories (reference: ``XGBoosterGetCategories``,
        src/data/cat_container.h).  Inference frames are recoded against this
        mapping; exporting it lets non-Python consumers do the same."""
        from .data.dmatrix import categories_by_name

        self._configure()
        return categories_by_name(getattr(self, "_cat_categories", None),
                                  self.feature_names)

    def inplace_predict(self, data, iteration_range=(0, 0), predict_type="value",
                        missing=np.nan, validate_features=True, base_margin=None,
                        strict_shape=False):
        """(reference: core.py:2561) — wraps raw arrays without a DMatrix."""
        d = DMatrix(data, missing=missing)
        if base_margin is not None:
            d.set_base_margin(base_margin)
        return self.predict(
            d, output_margin=(predict_type == "margin"),
            iteration_range=iteration_range, strict_shape=strict_shape,
        )

    # ------------------------------------------------------------------ model IO
    @property
    def trees_per_round(self) -> int:
        if getattr(self, "multi_strategy", "") == "multi_output_tree" \
                and self.n_groups > 1:
            return max(self.num_parallel_tree, 1)  # one vector tree per round
        return max(self.n_groups, 1) * max(self.num_parallel_tree, 1)

    def num_boosted_rounds(self) -> int:
        self._configure()
        if self.booster_kind == "gblinear":
            return getattr(self, "_linear_rounds", 0)
        return len(self.trees) // self.trees_per_round

    def num_features(self) -> int:
        if getattr(self, "_num_feature", None):
            return self._num_feature
        for c in self._caches.values():
            return c.dmat.num_col()
        if self.trees:
            return int(max(t.split_indices.max(initial=0) for t in self.trees)) + 1
        return 0

    def save_model(self, fname: Union[str, os.PathLike]) -> None:
        """JSON (``.json``) or UBJSON (``.ubj``) model file
        (reference: learner.cc:950 SaveModel; schema doc/model.schema)."""
        obj = self.save_raw_dict()
        fname = os.fspath(fname)
        if fname.endswith(".ubj"):
            from .utils.ubjson import dump_ubjson

            with open(fname, "wb") as fh:
                dump_ubjson(obj, fh)
        else:
            with open(fname, "w") as fh:
                json.dump(obj, fh)

    def _base_score_str(self) -> str:
        """base_score in probability space, reference model-JSON form
        (scalar, or upstream ≥3.x bracketed vector for per-group offsets)."""
        base_margins = np.asarray(self.base_score, np.float32).reshape(-1)
        base_probs = [
            float(np.asarray(self.objective.margin_to_prob(np.float32(m))))
            for m in base_margins
        ]
        if len(base_probs) > 1 and not np.allclose(base_probs, base_probs[0]):
            return "[" + ",".join(f"{p:.9E}" for p in base_probs) + "]"
        return f"{base_probs[0]:.9E}"

    def save_raw_dict(self) -> dict:
        self._configure()
        n_feat = self.num_features()
        base = self._base_score_str()
        obj_conf = {"name": self.objective.name}
        if self.objective.name.startswith("multi:"):
            obj_conf["softmax_multiclass_param"] = {"num_class": str(self.num_class)}
        if self.booster_kind == "gblinear":
            # reference schema: gblinear.cc SaveModel — feature-major weights,
            # per-group bias at the end
            W = self.linear_weights if self.linear_weights is not None else np.zeros(
                (n_feat, self.n_groups), np.float32)
            b = self.linear_bias if self.linear_bias is not None else np.zeros(
                self.n_groups, np.float32)
            gb = {
                "model": {"weights": [float(x) for x in
                                      np.concatenate([W.reshape(-1), b])],
                          "param": {"num_feature": str(n_feat),
                                    "num_output_group": str(self.n_groups),
                                    "num_boosted_rounds": str(
                                        getattr(self, "_linear_rounds", 0))}},
                "name": "gblinear",
            }
        else:
            trees = [t.to_json_dict(n_feat, tree_id=i)
                     for i, t in enumerate(self.trees)]
            model = {
                "gbtree_model_param": {
                    "num_trees": str(len(self.trees)),
                    "num_parallel_tree": str(self.num_parallel_tree),
                },
                "trees": trees,
                "tree_info": list(self.tree_info),
            }
            if self.booster_kind == "dart":
                gb = {"gbtree": {"model": model},
                      "weight_drop": [float(w) for w in self.tree_weights],
                      "name": "dart"}
            else:
                gb = {"model": model, "name": "gbtree"}
        # exact f32 margin stashed as an attribute (string map — upstream
        # ignores unknown keys): prob<->margin transforms do not round-trip
        # bitwise in f32, so reloading from base_score alone perturbs margins
        attrs = dict(self.attributes)
        attrs["base_margin_exact"] = " ".join(
            repr(float(v)) for v in np.asarray(self.base_score).reshape(-1))
        if getattr(self, "_cat_categories", None):
            # training categories, for inference-time recode (the role of
            # the reference's cat container in the model blob)
            attrs["cat_categories"] = json.dumps(self._cat_categories)
        return {
            "version": [3, 1, 0],
            "learner": {
                "attributes": attrs,
                "feature_names": self.feature_names or [],
                "feature_types": self.feature_types or [],
                "gradient_booster": gb,
                "learner_model_param": {
                    "base_score": base,
                    "boost_from_average": "1",
                    "num_class": str(self.num_class),
                    "num_feature": str(n_feat),
                    "num_target": str(self.n_groups if self.num_class == 0
                                      else 1),
                },
                "objective": obj_conf,
            },
        }

    def load_model(self, fname: Union[str, os.PathLike, bytes, bytearray]) -> None:
        if isinstance(fname, (bytes, bytearray)):
            try:
                obj = json.loads(fname)
            except (UnicodeDecodeError, json.JSONDecodeError):
                import io

                from .utils.ubjson import load_ubjson

                obj = load_ubjson(io.BytesIO(bytes(fname)))
        else:
            fname = os.fspath(fname)
            if fname.endswith(".ubj"):
                from .utils.ubjson import load_ubjson

                with open(fname, "rb") as fh:
                    obj = load_ubjson(fh)
            else:
                with open(fname) as fh:
                    obj = json.load(fh)
        self.load_model_dict(obj)

    def load_model_dict(self, obj: dict) -> None:
        learner = obj["learner"]
        lmp = learner["learner_model_param"]
        self.params.setdefault("objective", learner["objective"]["name"])
        nc = int(lmp.get("num_class", "0"))
        if nc > 0:
            self.params["num_class"] = nc
        nt = int(lmp.get("num_target", "1") or 1)
        if nt > 1:
            self.params["num_target"] = nt
        self._invalidate_config()
        self._configure()
        exact = learner.get("attributes", {}).get("base_margin_exact")
        if exact is not None:
            vals = np.asarray([float(v) for v in str(exact).split()], np.float32)
            self._base_margin_value = np.broadcast_to(
                vals if vals.size > 1 else vals.reshape(-1)[0],
                (self.n_groups,)).astype(np.float32).copy()
        else:
            # upstream ≥3.x may write a bracketed array "[4.5E-1]" (vector
            # leaf support, learner.cc LearnerModelParamLegacy); accept both
            raw = str(lmp["base_score"]).strip().strip("[]()")
            probs = np.asarray([float(v) for v in raw.replace(",", " ").split()],
                               np.float32)
            if probs.size == 0:
                raise ValueError(
                    f"Cannot parse base_score {lmp['base_score']!r}")
            if probs.size not in (1, self.n_groups):
                raise ValueError(
                    f"base_score has {probs.size} entries but the model has "
                    f"{self.n_groups} output groups (multi-target vector "
                    "leaves are not supported yet)")
            base_prob = probs if probs.size > 1 else probs.reshape(-1)[0]
            self._base_margin_value = np.broadcast_to(
                np.asarray(self.objective.prob_to_margin(base_prob), np.float32),
                (self.n_groups,)).astype(np.float32).copy()
        self._num_feature = int(lmp.get("num_feature", "0")) or None
        gbooster = learner["gradient_booster"]
        name = gbooster.get("name", "gbtree")
        self.params.setdefault("booster", name)
        self._invalidate_config(structural=False)
        self._configure()
        if name == "gblinear":
            flat = np.asarray(gbooster["model"]["weights"], np.float32)
            F = self._num_feature or (len(flat) // max(self.n_groups, 1) - 1)
            K = max(self.n_groups, 1)
            self.linear_weights = flat[: F * K].reshape(F, K)
            self.linear_bias = flat[F * K : F * K + K]
            self._linear_rounds = int(
                gbooster["model"].get("param", {}).get("num_boosted_rounds", "0") or 0)
            self.trees, self.tree_info, self.tree_weights = [], [], []
        else:
            gb = gbooster["gbtree"]["model"] if name == "dart" else gbooster["model"]
            self.trees = [RegTree.from_json_dict(t) for t in gb["trees"]]
            self.tree_info = [int(i) for i in gb["tree_info"]]
            self.tree_weights = [float(w) for w in gbooster.get(
                "weight_drop", [1.0] * len(self.trees))]
            self.num_parallel_tree = int(
                gb.get("gbtree_model_param", {}).get("num_parallel_tree", "1") or 1)
            self.params.setdefault("num_parallel_tree", self.num_parallel_tree)
            if any(t.leaf_vector is not None for t in self.trees):
                self.params["multi_strategy"] = "multi_output_tree"
                self.multi_strategy = "multi_output_tree"
        self.attributes = dict(learner.get("attributes", {}))
        self.attributes.pop("base_margin_exact", None)
        cc = self.attributes.pop("cat_categories", None)
        if cc:
            self._cat_categories = {int(k): list(v)
                                    for k, v in json.loads(cc).items()}
        self.feature_names = learner.get("feature_names") or None
        self.feature_types = learner.get("feature_types") or None

    def save_raw(self, raw_format: str = "ubj") -> bytearray:
        obj = self.save_raw_dict()
        if raw_format == "json":
            return bytearray(json.dumps(obj).encode())
        from io import BytesIO

        from .utils.ubjson import dump_ubjson

        buf = BytesIO()
        dump_ubjson(obj, buf)
        return bytearray(buf.getvalue())

    # ---- training-configuration IO (reference: learner.cc:625 SaveConfig /
    # :570 LoadConfig; C API XGBoosterSaveJsonConfig, c_api.cc:1379 area).
    # The model files above carry the MODEL; these carry the training
    # configuration, so a restored process continues training identically.
    def _config_dict(self) -> dict:
        import dataclasses as _dc

        from .params import KNOWN_LEARNER_KEYS, TrainParam

        self._configure()

        def s(v):
            if isinstance(v, bool):
                return "1" if v else "0"
            if isinstance(v, (list, tuple, dict)):
                return json.dumps(v)
            return str(v)

        params = {k: v for k, v in self.params.items() if v is not None}
        tree_keys = {("lambda" if f.name == "lambda_" else f.name)
                     for f in _dc.fields(TrainParam)}
        hist_param = {}
        for k in sorted(tree_keys):
            v = getattr(self.tparam, "lambda_" if k == "lambda" else k)
            if v is not None:
                hist_param[k] = s(v)
        placed = set(tree_keys)

        def take(section: dict, key: str, default=None) -> None:
            if key in params:
                section[key] = s(params[key])
                placed.add(key)
            elif default is not None:
                section[key] = s(default)

        learner_train = {"booster": self.booster_kind,
                         "objective": self.objective.name}
        placed |= {"booster", "objective"}
        take(learner_train, "disable_default_eval_metric", 0)
        take(learner_train, "multi_strategy",
             getattr(self, "multi_strategy", "one_output_per_tree"))

        generic = {}
        take(generic, "device")  # recorded only where the user asserted one
        take(generic, "seed", 0)
        take(generic, "seed_per_iteration", 0)
        take(generic, "nthread", 0)
        take(generic, "validate_parameters", 0)

        gb: dict = {"name": self.booster_kind}
        if self.booster_kind == "gblinear":
            lin = {}
            for k in ("updater", "feature_selector", "top_k", "eta"):
                take(lin, k)
            lin["lambda"] = hist_param.get("lambda", "0")
            lin["alpha"] = hist_param.get("alpha", "0")
            gb["gblinear_train_param"] = lin
        else:
            gbt = {"num_parallel_tree": s(self.num_parallel_tree)}
            placed.add("num_parallel_tree")
            take(gbt, "process_type", "default")
            take(gbt, "tree_method", "hist")
            take(gbt, "updater")
            gb["gbtree_train_param"] = gbt
            gb["updater"] = {
                "grow_quantile_histmaker": {"hist_train_param": hist_param}}
            if self.booster_kind == "dart":
                dart = {}
                for k in ("rate_drop", "one_drop", "skip_drop",
                          "sample_type", "normalize_type"):
                    take(dart, k)
                gb["dart_train_param"] = dart

        obj_sec: dict = {"name": self.objective.name}
        obj_keys = ("scale_pos_weight", "num_class", "tweedie_variance_power",
                    "huber_slope", "quantile_alpha", "expectile_alpha",
                    "aft_loss_distribution", "aft_loss_distribution_scale",
                    "lambdarank_num_pair_per_sample", "lambdarank_pair_method",
                    "ndcg_exp_gain", "lambdarank_unbiased",
                    "lambdarank_bias_norm")
        for k in obj_keys:
            take(obj_sec, k)

        metric_names = params.get("eval_metric")
        if metric_names is None:
            metrics = []
        elif isinstance(metric_names, (list, tuple)):
            metrics = [{"name": str(m)} for m in metric_names]
        else:
            metrics = [{"name": str(metric_names)}]
        placed.add("eval_metric")

        # user-set params not covered by a named section ride in
        # generic_param (the reference Context also carries a grab-bag of
        # runtime keys there) so load_config restores EVERYTHING
        for k in sorted(params):
            if k not in placed and k in (KNOWN_LEARNER_KEYS | tree_keys):
                generic[k] = s(params[k])

        return {
            "version": [3, 1, 0],
            "learner": {
                "generic_param": generic,
                "gradient_booster": gb,
                "learner_model_param": {
                    "base_score": ("5E-1" if self._base_margin_value is None
                                   else self._base_score_str()),
                    "num_class": str(self.num_class),
                    "num_feature": str(self.num_features()),
                    "num_target": str(self.n_groups if self.num_class == 0
                                      else 1),
                },
                "learner_train_param": learner_train,
                "metrics": metrics,
                "objective": obj_sec,
            },
        }

    def save_config(self) -> str:
        """Current training configuration as a JSON string (reference:
        Booster.save_config / XGBoosterSaveJsonConfig)."""
        return json.dumps(self._config_dict())

    def load_config(self, config: Union[str, bytes, dict]) -> None:
        """Restore a save_config() snapshot (reference learner.cc:570
        LoadConfig): collects every parameter leaf from the reference-shaped
        sections and applies it, so continued training behaves identically."""
        import dataclasses as _dc

        from .params import KNOWN_LEARNER_KEYS, TrainParam

        obj = config if isinstance(config, dict) else json.loads(config)
        learner = obj.get("learner", obj)
        tree_keys = {("lambda" if f.name == "lambda_" else f.name)
                     for f in _dc.fields(TrainParam)}
        known = KNOWN_LEARNER_KEYS | tree_keys
        collected: Dict[str, Any] = {}

        def walk(d: dict) -> None:
            for k, v in d.items():
                if k == "learner_model_param":
                    continue  # model state, not configuration
                if isinstance(v, dict):
                    walk(v)
                elif k != "name" and isinstance(v, (str, int, float, bool)):
                    if k in known:
                        collected[k] = v

        walk(learner)
        metrics = learner.get("metrics") or []
        names = [m["name"] if isinstance(m, dict) else str(m) for m in metrics]
        if names:
            collected["eval_metric"] = names
        else:
            collected.pop("eval_metric", None)
        booster_name = learner.get("gradient_booster", {}).get("name")
        if booster_name:
            collected["booster"] = booster_name
        if collected:
            self.set_param(collected)

    def serialize(self) -> bytearray:
        """Full-state snapshot {"Model": ..., "Config": ...} in UBJSON
        (reference learner.cc:987 Save; C API XGBoosterSerializeToBuffer,
        learner.cc:992): model + training configuration in one buffer."""
        from io import BytesIO

        from .utils.ubjson import dump_ubjson

        snap = {"Model": self.save_raw_dict(), "Config": self._config_dict()}
        buf = BytesIO()
        dump_ubjson(snap, buf)
        return bytearray(buf.getvalue())

    def unserialize(self, buf: Union[bytes, bytearray]) -> None:
        """Restore a serialize() snapshot (learner.cc:1003 Load)."""
        import io

        from .utils.ubjson import load_ubjson

        try:
            snap = json.loads(buf)
        except (UnicodeDecodeError, json.JSONDecodeError):
            snap = load_ubjson(io.BytesIO(bytes(buf)))
        self.load_model_dict(snap["Model"])
        self.load_config(snap["Config"])

    # attributes API (reference: core.py attr/set_attr)
    def attr(self, key: str) -> Optional[str]:
        return self.attributes.get(key)

    def set_attr(self, **kwargs: Optional[str]) -> None:
        for k, v in kwargs.items():
            if v is None:
                self.attributes.pop(k, None)
            else:
                self.attributes[k] = str(v)

    def __getitem__(self, val: slice) -> "Booster":
        """Tree-slice (reference: Booster.__getitem__ / Learner::Slice)."""
        if not isinstance(val, slice):
            raise TypeError("Booster slicing requires a slice of rounds")
        self._configure()
        if self.booster_kind == "gblinear":
            raise ValueError("Slice is not supported by the gblinear booster")
        lo = val.start or 0
        hi = val.stop if val.stop is not None else self.num_boosted_rounds()
        out = Booster(dict(self.params))
        out._configure()
        k = out.trees_per_round
        out.trees = self.trees[lo * k : hi * k]
        out.tree_info = self.tree_info[lo * k : hi * k]
        out.tree_weights = list(self.tree_weights[lo * k : hi * k])
        out._base_margin_value = self._base_margin_value
        out._num_feature = getattr(self, "_num_feature", None)
        out.feature_names = self.feature_names
        out.feature_types = self.feature_types
        out.attributes = dict(self.attributes)
        out.best_iteration = self.best_iteration
        out.best_score = self.best_score
        return out

    def copy(self) -> "Booster":
        return self[0 : self.num_boosted_rounds()]

    def get_dump(self, fmap: str = "", with_stats: bool = False, dump_format: str = "text"):
        names = self.feature_names
        if fmap:
            # feature-map file: "<id>\t<name>\t<type>" per line
            # (reference: src/common/feature_map.h LoadText)
            names = list(names or [f"f{i}" for i in range(self.num_features())])
            with open(fmap) as fh:
                for line in fh:
                    # tab-separated like FeatureMap::LoadText, so names may
                    # contain spaces; whitespace split only as a fallback
                    line = line.rstrip("\n")
                    parts = line.split("\t") if "\t" in line else line.split()
                    if len(parts) >= 2:
                        fid = int(parts[0])
                        while len(names) <= fid:
                            names.append(f"f{len(names)}")
                        names[fid] = parts[1]
        if dump_format == "json":
            return [t.dump_json(names, with_stats) for t in self.trees]
        return [t.dump_text(names, with_stats) for t in self.trees]

    def get_score(self, fmap: str = "", importance_type: str = "weight") -> Dict[str, float]:
        """Feature importance (reference: core.py get_score)."""
        self._configure()
        names = self.feature_names or [f"f{i}" for i in range(self.num_features())]
        acc: Dict[str, float] = {}
        cnt: Dict[str, int] = {}
        for t in self.trees:
            for nid in range(t.n_nodes):
                if t.left_children[nid] == -1:
                    continue
                f = names[t.split_indices[nid]]
                cnt[f] = cnt.get(f, 0) + 1
                if importance_type in ("gain", "total_gain"):
                    acc[f] = acc.get(f, 0.0) + float(t.loss_changes[nid])
                elif importance_type in ("cover", "total_cover"):
                    acc[f] = acc.get(f, 0.0) + float(t.sum_hessian[nid])
                else:
                    acc[f] = acc.get(f, 0.0) + 1.0
        if importance_type in ("gain", "cover"):
            return {k: v / cnt[k] for k, v in acc.items()}
        return acc
