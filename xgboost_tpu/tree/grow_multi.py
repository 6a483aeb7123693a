"""Vector-leaf (multi-target) tree growing — ``multi_strategy="multi_output_tree"``.

TPU-native equivalent of the reference's MultiTargetTree training
(include/xgboost/multi_target_tree_model.h:38; GPU evaluator
src/tree/gpu_hist/multi_evaluate_splits.cu; driver updater_quantile_hist.cc:156).
One tree carries all K targets: the histogram gets 2K channels (one matmul on
the MXU — K does not multiply the number of passes over the data), the split
is chosen by the SUM of per-target gains, and every leaf stores a K-vector.

Reuses the scalar grower's heap/level machinery (``_update_positions``) and
layout conventions; the state mirrors TreeState with K-wide value arrays.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.histogram import build_histogram, combine_sibling_hists
from ..ops.split import SplitParams, calc_weight, evaluate_splits_multi
from .grow import _update_positions, max_nodes_for_depth

_EPS = 1e-6


class MultiTreeState(NamedTuple):
    pos: jnp.ndarray        # (R_pad,) int32
    alive: jnp.ndarray      # (max_nodes,) bool
    totals: jnp.ndarray     # (max_nodes, K, 2)
    feat: jnp.ndarray       # (max_nodes,) int32
    sbin: jnp.ndarray       # (max_nodes,) int32
    thr: jnp.ndarray        # (max_nodes,) f32
    dleft: jnp.ndarray      # (max_nodes,) bool
    is_leaf: jnp.ndarray    # (max_nodes,) bool
    leaf_val: jnp.ndarray   # (max_nodes, K) eta-scaled leaf vector
    gain: jnp.ndarray       # (max_nodes,) f32
    base_weight: jnp.ndarray  # (max_nodes, K) raw node weights
    sum_hess: jnp.ndarray   # (max_nodes,) mean per-target hessian
    splits_left: jnp.ndarray  # (1,) int32


@functools.partial(jax.jit, static_argnames=("max_nodes", "n_targets",
                                             "axis_name", "max_splits"))
def init_multi_state(gpair, valid, *, max_nodes: int, n_targets: int,
                     axis_name: Optional[str] = None, max_splits: int = 0):
    """gpair: (R_pad, K, 2).  All rows at the root."""
    R = gpair.shape[0]
    K = n_targets
    pos = jnp.where(valid, 0, -1).astype(jnp.int32)
    # a reduction, not a dot over every row (ops/histogram.node_sums says why)
    root = jnp.sum(jnp.where((pos == 0)[:, None, None], gpair, 0.0),
                   axis=0)  # (K, 2)
    if axis_name is not None:
        root = lax.psum(root, axis_name)
    mn = max_nodes
    budget = max_splits if max_splits > 0 else jnp.iinfo(jnp.int32).max
    return MultiTreeState(
        pos=pos,
        alive=jnp.zeros(mn, bool).at[0].set(True),
        totals=jnp.zeros((mn, K, 2), jnp.float32).at[0].set(root),
        feat=jnp.full(mn, -1, jnp.int32),
        sbin=jnp.zeros(mn, jnp.int32),
        thr=jnp.zeros(mn, jnp.float32),
        dleft=jnp.ones(mn, bool),
        is_leaf=jnp.zeros(mn, bool),
        leaf_val=jnp.zeros((mn, K), jnp.float32),
        gain=jnp.zeros(mn, jnp.float32),
        base_weight=jnp.zeros((mn, K), jnp.float32),
        sum_hess=jnp.zeros(mn, jnp.float32),
        splits_left=jnp.full((1,), budget, jnp.int32),
    )


class _ScalarBest(NamedTuple):
    # the subset of split fields the scalar partitioner needs
    feature: jnp.ndarray
    bin: jnp.ndarray
    default_left: jnp.ndarray
    is_cat: jnp.ndarray
    cat_set: jnp.ndarray


def _finalize_leaves_multi(state, params, depth: int):
    """Last level: every surviving node becomes a leaf."""
    node0 = (1 << depth) - 1
    N = 1 << depth
    idx = node0 + jnp.arange(N, dtype=jnp.int32)
    totals_lvl = lax.dynamic_slice_in_dim(state.totals, node0, N, axis=0)
    alive_lvl = lax.dynamic_slice_in_dim(state.alive, node0, N, axis=0)
    w = calc_weight(totals_lvl[..., 0], totals_lvl[..., 1], params)
    return state._replace(
        is_leaf=state.is_leaf.at[idx].set(alive_lvl),
        leaf_val=state.leaf_val.at[idx].set(
            jnp.where(alive_lvl[:, None], params.eta * w, 0.0)),
        base_weight=state.base_weight.at[idx].set(w),
        sum_hess=state.sum_hess.at[idx].set(totals_lvl[..., 1].mean(-1)),
    )


def _decide_body(state: MultiTreeState, hist, bins, cuts_pad, n_bins,
                 feature_mask, *, depth: int, params: SplitParams,
                 lossguide: bool):
    """evaluate + record + partition for one level, given the FINAL (already
    reduced + sibling-combined) level histogram (N, F, B, K, 2)."""
    node0 = (1 << depth) - 1
    N = 1 << depth
    B = cuts_pad.shape[1]
    idx = node0 + jnp.arange(N, dtype=jnp.int32)
    totals_lvl = lax.dynamic_slice_in_dim(state.totals, node0, N, axis=0)
    alive_lvl = lax.dynamic_slice_in_dim(state.alive, node0, N, axis=0)
    w = calc_weight(totals_lvl[..., 0], totals_lvl[..., 1], params)  # (N,K)

    fm = feature_mask if feature_mask.ndim == 2 else feature_mask[None, :]
    best = evaluate_splits_multi(hist, totals_lvl, n_bins, params, fm)

    gamma_eps = max(params.gamma, _EPS)
    can_split = alive_lvl & (best.gain > gamma_eps)

    # split budget (max_leaves): best-first under lossguide, node-order under
    # depthwise — same driver semantics as the scalar level_step (driver.h)
    budget = state.splits_left[0]
    prio = best.gain if lossguide else -idx.astype(jnp.float32)
    prio = jnp.where(can_split, prio, -jnp.inf)
    ranks = jnp.argsort(jnp.argsort(-prio)).astype(jnp.int32)
    can_split = can_split & (ranks < budget)
    new_budget = budget - jnp.sum(can_split).astype(jnp.int32)

    new_leaf = alive_lvl & ~can_split
    thr_lvl = cuts_pad[best.feature, jnp.minimum(best.bin, B - 1)]

    st = state._replace(
        feat=state.feat.at[idx].set(jnp.where(can_split, best.feature, -1)),
        sbin=state.sbin.at[idx].set(jnp.where(can_split, best.bin, 0)),
        thr=state.thr.at[idx].set(jnp.where(can_split, thr_lvl, 0.0)),
        dleft=state.dleft.at[idx].set(best.default_left),
        is_leaf=state.is_leaf.at[idx].set(new_leaf),
        leaf_val=state.leaf_val.at[idx].set(
            jnp.where(new_leaf[:, None], params.eta * w, 0.0)),
        gain=state.gain.at[idx].set(jnp.where(can_split, best.gain, 0.0)),
        base_weight=state.base_weight.at[idx].set(w),
        sum_hess=state.sum_hess.at[idx].set(totals_lvl[..., 1].mean(-1)),
        splits_left=jnp.full((1,), new_budget, jnp.int32),
    )
    left_ids = 2 * idx + 1
    right_ids = 2 * idx + 2
    st = st._replace(
        alive=st.alive.at[left_ids].set(can_split).at[right_ids].set(can_split),
        totals=st.totals.at[left_ids].set(best.left_sum)
                        .at[right_ids].set(best.right_sum),
    )
    bb = _ScalarBest(best.feature, best.bin, best.default_left,
                     jnp.zeros(N, bool), jnp.zeros((N, B), bool))
    st = st._replace(
        pos=_update_positions(bins, st.pos, bb, can_split, node0, N, B, False))
    return st


@functools.partial(
    jax.jit,
    static_argnames=("node0", "n_nodes", "n_bin", "n_targets", "stride"),
)
def build_level_hist_multi(bins, gpair, pos, *, node0: int, n_nodes: int,
                           n_bin: int, n_targets: int, stride: int = 1):
    """Local 2K-channel level histogram (n_nodes, F, B, K, 2) — the piece a
    multi-process grower allreduces before deciding."""
    R, K = gpair.shape[0], n_targets
    h = build_histogram(bins, gpair.reshape(R, K * 2), pos, node0=node0,
                        n_nodes=n_nodes, n_bin=n_bin, stride=stride)
    return h.reshape(n_nodes, bins.shape[1], n_bin, K, 2)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "params", "n_targets", "lossguide"),
)
def decide_level_multi(state: MultiTreeState, hist, bins, cuts_pad, n_bins,
                       feature_mask, *, depth: int, params: SplitParams,
                       n_targets: int, lossguide: bool = False):
    return _decide_body(state, hist, bins, cuts_pad, n_bins, feature_mask,
                        depth=depth, params=params, lossguide=lossguide)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "params", "last_level", "n_targets",
                     "subtract_on", "axis_name", "lossguide"),
)
def level_step_multi(state: MultiTreeState, bins, gpair, cuts_pad, n_bins,
                     feature_mask, hist_prev=None, *, depth: int,
                     params: SplitParams, last_level: bool, n_targets: int,
                     subtract_on: bool = False,
                     axis_name: Optional[str] = None, lossguide: bool = False):
    """One level: 2K-channel hist -> summed-gain split -> apply.

    Returns (state, hist) with hist (N, F, B, K, 2) for the next level's
    subtraction trick (right sibling = parent - left).  ``axis_name``: rows
    are sharded over that mesh axis and the histogram crosses shards in one
    psum (the multi-target AllReduceHist)."""
    node0 = (1 << depth) - 1
    N = 1 << depth
    B = cuts_pad.shape[1]
    K = n_targets

    if last_level:
        return _finalize_leaves_multi(state, params, depth), None

    alive_lvl = lax.dynamic_slice_in_dim(state.alive, node0, N, axis=0)
    if subtract_on:
        half = N // 2
        left = build_level_hist_multi(bins, gpair, state.pos, node0=node0,
                                      n_nodes=half, n_bin=B, n_targets=K,
                                      stride=2)
        if axis_name is not None:
            left = lax.psum(left, axis_name)
        hist = combine_sibling_hists(left, hist_prev, alive_lvl)
    else:
        hist = build_level_hist_multi(bins, gpair, state.pos, node0=node0,
                                      n_nodes=N, n_bin=B, n_targets=K)
        if axis_name is not None:
            hist = lax.psum(hist, axis_name)

    st = _decide_body(state, hist, bins, cuts_pad, n_bins, feature_mask,
                      depth=depth, params=params, lossguide=lossguide)
    return st, hist


@jax.jit
def leaf_margin_delta_multi(pos, leaf_val):
    """(R_pad, K) margin update: every row adds its leaf's vector."""
    safe = jnp.clip(pos, 0, leaf_val.shape[0] - 1)
    return jnp.where((pos >= 0)[:, None], leaf_val[safe], 0.0)


class GrownMultiTree(NamedTuple):
    feat: "object"
    sbin: "object"
    thr: "object"
    dleft: "object"
    is_leaf: "object"
    leaf_val: "object"   # (max_nodes, K)
    gain: "object"
    base_weight: "object"  # (max_nodes, K)
    sum_hess: "object"
    totals: "object"


class MultiTargetTreeGrower:
    """Host driver for vector-leaf trees (one jitted level per depth).

    ``distributed=True``: every process holds a row shard; the level
    histogram crosses processes through ``collective.allreduce`` between
    build and decide (the rabit AllReduceHist role for the reference's
    MultiTargetHistBuilder, updater_quantile_hist.cc:156)."""

    def __init__(self, max_depth: int, params: SplitParams, n_targets: int,
                 *, subtract: bool = True, max_leaves: int = 0,
                 lossguide: bool = False, distributed: bool = False) -> None:
        self.max_depth = max_depth
        self.params = params
        self.n_targets = n_targets
        self.subtract = subtract
        self.max_leaves = max_leaves
        self.lossguide = lossguide
        self.distributed = distributed
        self.max_nodes = max_nodes_for_depth(max_depth)

    def grow(self, bins, gpair, valid, cuts_pad, n_bins,
             feature_masks=None) -> MultiTreeState:
        import numpy as np

        F = bins.shape[1]
        B = cuts_pad.shape[1]
        K = self.n_targets
        ones = jnp.ones((1, F), dtype=bool)
        state = init_multi_state(
            gpair, valid, max_nodes=self.max_nodes, n_targets=K,
            max_splits=(self.max_leaves - 1) if self.max_leaves > 0 else 0)
        if self.distributed:
            from .grow import sync_root_totals

            state = sync_root_totals(state)
        hist_prev = None
        for d in range(self.max_depth + 1):
            fm = ones if feature_masks is None else feature_masks(d, 1 << d)
            if d == self.max_depth:
                state, hist_prev = level_step_multi(
                    state, bins, gpair, cuts_pad, n_bins, fm, None,
                    depth=d, params=self.params, last_level=True,
                    n_targets=K, lossguide=self.lossguide)
                continue
            subtract = self.subtract and d > 0 and hist_prev is not None
            if self.distributed:
                from .. import collective

                node0, N = (1 << d) - 1, 1 << d
                n_build = (N // 2) if subtract else N
                h = build_level_hist_multi(
                    bins, gpair, state.pos, node0=node0, n_nodes=n_build,
                    n_bin=B, n_targets=K, stride=2 if subtract else 1)
                h = jnp.asarray(collective.allreduce(np.asarray(h)))
                if subtract:
                    alive_lvl = lax.dynamic_slice_in_dim(state.alive, node0, N)
                    hist = combine_sibling_hists(h, hist_prev, alive_lvl)
                else:
                    hist = h
                state = decide_level_multi(
                    state, hist, bins, cuts_pad, n_bins, fm, depth=d,
                    params=self.params, n_targets=K, lossguide=self.lossguide)
                hist_prev = hist
            else:
                state, hist_prev = level_step_multi(
                    state, bins, gpair, cuts_pad, n_bins, fm, hist_prev,
                    depth=d, params=self.params, last_level=False,
                    n_targets=K, subtract_on=subtract,
                    lossguide=self.lossguide)
        return state

    @staticmethod
    def to_host(state: MultiTreeState) -> GrownMultiTree:
        import numpy as np

        return GrownMultiTree(
            feat=np.asarray(state.feat),
            sbin=np.asarray(state.sbin),
            thr=np.asarray(state.thr),
            dleft=np.asarray(state.dleft),
            is_leaf=np.asarray(state.is_leaf),
            leaf_val=np.asarray(state.leaf_val),
            gain=np.asarray(state.gain),
            base_weight=np.asarray(state.base_weight),
            sum_hess=np.asarray(state.sum_hess),
            totals=np.asarray(state.totals),
        )
