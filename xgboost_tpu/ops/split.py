"""Split evaluation: gain scan over histogram bins.

TPU-native equivalent of the reference's split evaluators
(src/tree/gpu_hist/evaluate_splits.cu — forward/backward bin scans with
missing-value direction search; CPU src/tree/hist/evaluate_splits.h).
The CUDA code runs a block-parallel segmented scan per (node, feature); here
the whole (N, F, B) gain tensor is computed at once with a cumsum — a few
microseconds of VPU work — and reduced with argmax.

Gain formulae follow src/tree/param.h (CalcGain / CalcWeight / ThresholdL1 /
CalcGainGivenWeight): L1 soft-threshold via ``alpha``, L2 ``lambda``, optional
``max_delta_step`` weight clipping.  Missing-value handling matches
LossChangeMissing (evaluate_splits.cu): both default directions are scored and
the better one becomes the node's default.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

_EPS = 1e-6  # kRtEps (include/xgboost/base.h)


class SplitParams(NamedTuple):
    """Static split hyper-parameters (hashable for jit)."""

    eta: float
    gamma: float
    min_child_weight: float
    lambda_: float
    alpha: float
    max_delta_step: float
    # monotone_constraints: per-feature {-1,0,+1} (src/tree/constraints.cc);
    # None disables the constrained evaluation path entirely
    monotone: "object" = None
    # categorical split config (reference: src/tree/param.h max_cat_to_onehot)
    max_cat_to_onehot: int = 4


class BestSplit(NamedTuple):
    gain: jnp.ndarray  # (N,) loss_chg of best split (-inf if none valid)
    feature: jnp.ndarray  # (N,) int32
    bin: jnp.ndarray  # (N,) int32 — left = bins <= bin
    default_left: jnp.ndarray  # (N,) bool
    left_sum: jnp.ndarray  # (N, 2) (G, H) of left child
    right_sum: jnp.ndarray  # (N, 2)
    left_weight: jnp.ndarray  # (N,) clipped child weights (monotone bounds)
    right_weight: jnp.ndarray  # (N,)
    is_cat: jnp.ndarray  # (N,) bool — categorical split chosen
    cat_set: jnp.ndarray  # (N, B) bool — categories routed RIGHT (reference
    #                        semantics: common/categorical.h Decision)


class BestSplitMulti(NamedTuple):
    """Vector-leaf split decision (reference: multi_evaluate_splits.cu /
    HistMultiEvaluator): one (feature, bin) for all targets, per-target
    child statistics."""

    gain: jnp.ndarray  # (N,)
    feature: jnp.ndarray  # (N,) int32
    bin: jnp.ndarray  # (N,) int32
    default_left: jnp.ndarray  # (N,) bool
    left_sum: jnp.ndarray  # (N, K, 2)
    right_sum: jnp.ndarray  # (N, K, 2)
    left_weight: jnp.ndarray  # (N, K)
    right_weight: jnp.ndarray  # (N, K)


def _threshold_l1(g, alpha):
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - alpha, 0.0)


def calc_weight(G, H, p: SplitParams, lower=None, upper=None):
    """Raw leaf weight -ThresholdL1(G)/(H+lambda), clipped (param.h CalcWeight);
    optional [lower, upper] clamp implements monotone bounds propagation."""
    w = -_threshold_l1(G, p.alpha) / (H + p.lambda_)
    if p.max_delta_step > 0.0:
        w = jnp.clip(w, -p.max_delta_step, p.max_delta_step)
    if lower is not None:
        w = jnp.clip(w, lower, upper)
    return jnp.where(H <= 0.0, 0.0, w)


def gain_given_weight(G, H, w, p: SplitParams):
    """param.h CalcGainGivenWeight — used when weights are bound-clipped."""
    ret = -(2.0 * _threshold_l1(G, p.alpha) * w + (H + p.lambda_) * w * w)
    return jnp.where(H <= 0.0, 0.0, ret)


def calc_gain(G, H, p: SplitParams):
    """param.h CalcGain: ThresholdL1(G)^2/(H+lambda), or gain-given-weight when
    max_delta_step clips."""
    if p.max_delta_step == 0.0:
        return jnp.where(H <= 0.0, 0.0, _threshold_l1(G, p.alpha) ** 2 / (H + p.lambda_))
    w = calc_weight(G, H, p)
    # CalcGainGivenWeight: -(2 G w + (H + lambda) w^2), with L1 adjustment
    ret = -(2.0 * _threshold_l1(G, p.alpha) * w + (H + p.lambda_) * w * w)
    return jnp.where(H <= 0.0, 0.0, ret)


@functools.partial(jax.jit, static_argnames=("params",))
def evaluate_splits_multi(hist, totals, n_bins, params: SplitParams,
                          feature_mask=None) -> BestSplitMulti:
    """Best split per node for vector-leaf trees.

    hist   : (N, F, B, K, 2) f32 — per-target bin (G, H) sums
    totals : (N, K, 2) f32 — per-target node totals (incl. missing rows)

    Gain is the SUM of per-target gains for a shared (feature, bin) — the
    reference's multi-target objective (multi_evaluate_splits.cu accumulates
    per-target CalcGain under one split).  min_child_weight applies to the
    mean per-target hessian, matching the "average tree" reading used by the
    CPU HistMultiEvaluator.  Monotone/categorical are handled by the caller
    (unsupported for multi-target in round 2, like the reference's own
    multi_output_tree restrictions).
    """
    N, F, B, K, _ = hist.shape

    cum = jnp.cumsum(hist, axis=2)  # (N,F,B,K,2) left sums; missing -> right
    feat_sum = cum[:, :, -1]  # (N,F,K,2)
    miss = totals[:, None] - feat_sum  # (N,F,K,2)

    GL_r, HL_r = cum[..., 0], cum[..., 1]  # (N,F,B,K) missing -> right
    GL_l = GL_r + miss[:, :, None, :, 0]
    HL_l = HL_r + miss[:, :, None, :, 1]

    parent_gain = calc_gain(totals[..., 0], totals[..., 1], params).sum(-1)[
        :, None, None]  # (N,1,1)

    def side_gain(GL, HL):
        GR = totals[:, None, None, :, 0] - GL
        HR = totals[:, None, None, :, 1] - HL
        gain = (calc_gain(GL, HL, params) + calc_gain(GR, HR, params)).sum(-1) \
            - parent_gain  # (N,F,B)
        HLm, HRm = HL.mean(-1), HR.mean(-1)
        valid = ((HLm >= params.min_child_weight)
                 & (HRm >= params.min_child_weight)
                 & (HLm > 0.0) & (HRm > 0.0))
        return jnp.where(valid, gain, -jnp.inf), GR, HR

    gain_r, GR_r, HR_r = side_gain(GL_r, HL_r)
    gain_l, GR_l, HR_l = side_gain(GL_l, HL_l)

    bin_idx = jnp.arange(B, dtype=jnp.int32)
    bin_ok = bin_idx[None, :] < (n_bins[:, None] - 1)  # (F,B)
    top_ok = (bin_idx[None, :] == (n_bins[:, None] - 1)) & (
        jnp.abs(miss[..., 1]).sum(-1)[:, :, None] > _EPS)
    ok = bin_ok[None] | top_ok
    if feature_mask is not None:
        fm = feature_mask if feature_mask.ndim == 2 else feature_mask[None, :]
        ok = ok & fm[:, :, None]
    gain_r = jnp.where(ok, gain_r, -jnp.inf)
    gain_l = jnp.where(ok, gain_l, -jnp.inf)
    use_left = gain_l >= gain_r
    gain = jnp.where(use_left, gain_l, gain_r)

    flat = gain.reshape(N, F * B)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    best_f = (best // B).astype(jnp.int32)
    best_b = (best % B).astype(jnp.int32)

    def pick(arr):  # (N,F,B,K) -> (N,K) at the best (feature, bin)
        return jnp.take_along_axis(
            arr.reshape(N, F * B, K), best[:, None, None], axis=1)[:, 0]

    def pick2(arr):  # (N,F,B) -> (N,)
        return jnp.take_along_axis(arr.reshape(N, F * B), best[:, None], axis=1)[:, 0]

    dleft = pick2(use_left)
    GL = jnp.where(dleft[:, None], pick(GL_l), pick(GL_r))
    HL = jnp.where(dleft[:, None], pick(HL_l), pick(HL_r))
    GR = jnp.where(dleft[:, None], pick(GR_l), pick(GR_r))
    HR = jnp.where(dleft[:, None], pick(HR_l), pick(HR_r))

    return BestSplitMulti(
        gain=best_gain,
        feature=best_f,
        bin=best_b,
        default_left=dleft,
        left_sum=jnp.stack([GL, HL], axis=-1),
        right_sum=jnp.stack([GR, HR], axis=-1),
        left_weight=calc_weight(GL, HL, params),
        right_weight=calc_weight(GR, HR, params),
    )


def _native_split_ok(params: SplitParams) -> bool:
    """The native one-pass gain scan covers the numeric, unconstrained case
    (the ladder benchmarks); categorical and monotone keep the XLA path."""
    import os

    if os.environ.get("XTB_NO_NATIVE_SPLIT", ""):
        return False
    if jax.default_backend() != "cpu":
        return False
    if params.monotone is not None and any(c != 0 for c in params.monotone):
        return False
    from ..utils import native

    return native.ffi_usable()


def _evaluate_splits_native(hist, totals, n_bins, params: SplitParams,
                            feature_mask) -> BestSplit:
    """XLA FFI custom call into xtb_split_scan — one bin pass per (node,
    feature) instead of the XLA formulation's ~15 materialized (N,F,B)
    temporaries.  Same decisions (both missing directions scored,
    first-occurrence argmax in (feature, bin) order)."""
    import numpy as np

    N, F, B, _ = hist.shape
    fm = (jnp.ones((N, F), bool) if feature_mask is None
          else jnp.broadcast_to(
              feature_mask if feature_mask.ndim == 2 else feature_mask[None],
              (N, F)))
    shapes = (jax.ShapeDtypeStruct((N,), jnp.float32),
              jax.ShapeDtypeStruct((N,), jnp.int32),
              jax.ShapeDtypeStruct((N,), jnp.int32),
              jax.ShapeDtypeStruct((N,), jnp.uint8),
              jax.ShapeDtypeStruct((N,), jnp.float32),
              jax.ShapeDtypeStruct((N,), jnp.float32))
    from ..utils import native as _native

    _native.ensure_pool()
    call = jax.ffi.ffi_call("xtb_split", shapes)
    gain, feat, bin_, dleft, GL, HL = call(
        hist.astype(jnp.float32), totals.astype(jnp.float32),
        n_bins.astype(jnp.int32), fm.astype(jnp.uint8),
        lam=np.float32(params.lambda_), alpha=np.float32(params.alpha),
        mcw=np.float32(params.min_child_weight),
        mds=np.float32(params.max_delta_step))
    GR = totals[:, 0] - GL
    HR = totals[:, 1] - HL
    return BestSplit(
        gain=gain,
        feature=feat,
        bin=bin_,
        default_left=dleft.astype(bool),
        left_sum=jnp.stack([GL, HL], axis=1),
        right_sum=jnp.stack([GR, HR], axis=1),
        left_weight=calc_weight(GL, HL, params),
        right_weight=calc_weight(GR, HR, params),
        is_cat=jnp.zeros(N, bool),
        cat_set=jnp.zeros((N, B), bool),
    )


@functools.partial(jax.jit, static_argnames=("params",))
def evaluate_splits(
    hist, totals, n_bins, params: SplitParams, feature_mask=None, node_bounds=None,
    cat_mask=None,
) -> BestSplit:
    """Pick the best split per node.

    hist   : (N, F, B, 2) f32 — per-node per-feature bin (G, H) sums
    totals : (N, 2) f32 — node (G, H) including missing rows
    n_bins : (F,) int32 — valid bin count per feature (pads masked out)
    feature_mask : optional (F,) or (N, F) bool — column sampling / interaction
                   constraints (per-node allowed features)
    node_bounds  : optional (N, 2) f32 [lower, upper] monotone weight bounds
    """
    N, F, B, _ = hist.shape
    has_cat = cat_mask is not None
    if not has_cat and _native_split_ok(params):
        return _evaluate_splits_native(hist, totals, n_bins, params,
                                       feature_mask)

    if has_cat:
        # Categorical features (reference: evaluate_splits.cu one-hot pass +
        # sorted-partition pass, max_cat_to_onehot switch in param.h):
        #  - partition: permute bins by grad/hess ratio, then the ordinary
        #    prefix scan below IS the optimal-partition scan;
        #  - one-hot (few categories): left = everything-but-c, expressed by
        #    overriding the prefix sums with feat_sum - hist[c].
        onehot_f = cat_mask & (n_bins < params.max_cat_to_onehot)  # (F,)
        ratio = hist[..., 0] / (hist[..., 1] + _EPS)  # (N,F,B)
        ratio = jnp.where(hist[..., 1] > 0, ratio, jnp.inf)  # empty cats last
        bin_iota = jnp.arange(B, dtype=jnp.float32)
        sort_key = jnp.where(cat_mask[None, :, None], ratio, bin_iota[None, None, :])
        order = jnp.argsort(sort_key, axis=2)  # identity for numeric features
        inv_order = jnp.argsort(order, axis=2).astype(jnp.int32)
        hist_eval = jnp.take_along_axis(hist, order[..., None], axis=2)
    else:
        hist_eval = hist

    cum = jnp.cumsum(hist_eval, axis=2)  # (N,F,B,2): left sums, missing->right
    feat_sum = cum[:, :, -1, :]  # (N,F,2) — uses all bins incl. top
    miss = totals[:, None, :] - feat_sum  # (N,F,2) missing-value stats

    GL_r, HL_r = cum[..., 0], cum[..., 1]  # missing -> right
    if has_cat:
        oh = onehot_f[None, :, None]
        GL_r = jnp.where(oh, feat_sum[:, :, None, 0] - hist[..., 0], GL_r)
        HL_r = jnp.where(oh, feat_sum[:, :, None, 1] - hist[..., 1], HL_r)
    GL_l, HL_l = GL_r + miss[:, :, None, 0], HL_r + miss[:, :, None, 1]  # missing -> left

    monotone = params.monotone is not None and any(c != 0 for c in params.monotone)
    if monotone:
        lo = node_bounds[:, 0][:, None, None] if node_bounds is not None else -jnp.inf
        hi = node_bounds[:, 1][:, None, None] if node_bounds is not None else jnp.inf
        cvec = jnp.asarray(params.monotone, jnp.int32)[None, :, None]  # (1,F,1)
        w_parent = calc_weight(totals[:, 0], totals[:, 1], params,
                               lo if node_bounds is None else node_bounds[:, 0],
                               hi if node_bounds is None else node_bounds[:, 1])
        parent_gain = gain_given_weight(totals[:, 0], totals[:, 1], w_parent, params)[
            :, None, None
        ]
    else:
        parent_gain = calc_gain(totals[:, 0], totals[:, 1], params)[:, None, None]

    def side_gain(GL, HL):
        GR = totals[:, None, None, 0] - GL
        HR = totals[:, None, None, 1] - HL
        if monotone:
            # constrained evaluation (src/tree/constraints.cc / evaluate_splits.cu
            # LossChangeMissing with ValueConstraint): child weights clipped to
            # the node's bounds; monotone violation invalidates the split
            wL = calc_weight(GL, HL, params, lo, hi)
            wR = calc_weight(GR, HR, params, lo, hi)
            gain = (
                gain_given_weight(GL, HL, wL, params)
                + gain_given_weight(GR, HR, wR, params)
                - parent_gain
            )
            viol = ((cvec > 0) & (wL > wR)) | ((cvec < 0) & (wL < wR))
            gain = jnp.where(viol, -jnp.inf, gain)
        else:
            wL = wR = None
            gain = calc_gain(GL, HL, params) + calc_gain(GR, HR, params) - parent_gain
        valid = (
            (HL >= params.min_child_weight)
            & (HR >= params.min_child_weight)
            & (HL > 0.0)
            & (HR > 0.0)
        )
        return jnp.where(valid, gain, -jnp.inf), GR, HR, wL, wR

    gain_r, GR_r, HR_r, wL_r, wR_r = side_gain(GL_r, HL_r)
    gain_l, GR_l, HR_l, wL_l, wR_l = side_gain(GL_l, HL_l)

    # mask padded bins and the top bin (split there = empty right for dense features)
    bin_idx = jnp.arange(B, dtype=jnp.int32)
    bin_ok = bin_idx[None, :] < (n_bins[:, None] - 1)  # (F, B); allow [0, nb-2]
    # allow the top valid bin only when there ARE missing values to send right
    top_ok = (bin_idx[None, :] == (n_bins[:, None] - 1)) & (
        jnp.abs(miss[:, :, 1:2]) > _EPS
    ).reshape(N, F, 1)
    ok = bin_ok[None, :, :] | top_ok
    if has_cat:
        # one-hot: every non-empty category is a valid candidate
        ok = jnp.where(onehot_f[None, :, None],
                       (bin_idx[None, None, :] < n_bins[None, :, None]), ok)
    if feature_mask is not None:
        fm = feature_mask if feature_mask.ndim == 2 else feature_mask[None, :]
        ok = ok & fm[:, :, None]
    gain_r = jnp.where(ok, gain_r, -jnp.inf)
    gain_l = jnp.where(ok, gain_l, -jnp.inf)

    # prefer missing->left on ties? reference default dir comes from scan order;
    # pick strictly-better direction, defaulting left like DeviceSplitCandidate.
    use_left = gain_l >= gain_r
    gain = jnp.where(use_left, gain_l, gain_r)

    flat = gain.reshape(N, F * B)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    best_f = (best // B).astype(jnp.int32)
    best_b = (best % B).astype(jnp.int32)

    def pick(arr):  # (N,F,B) -> (N,) at best
        return jnp.take_along_axis(arr.reshape(N, F * B), best[:, None], axis=1)[:, 0]

    dleft = pick(use_left)
    GL = jnp.where(dleft, pick(GL_l), pick(GL_r))
    HL = jnp.where(dleft, pick(HL_l), pick(HL_r))
    GR = jnp.where(dleft, pick(GR_l), pick(GR_r))
    HR = jnp.where(dleft, pick(HR_l), pick(HR_r))

    if monotone:
        lw = jnp.where(dleft, pick(wL_l), pick(wL_r))
        rw = jnp.where(dleft, pick(wR_l), pick(wR_r))
    else:
        lw = calc_weight(GL, HL, params)
        rw = calc_weight(GR, HR, params)

    if has_cat:
        is_cat = cat_mask[best_f]  # (N,)
        chosen_oh = onehot_f[best_f]
        # categories routed RIGHT (common/categorical.h: in-set -> right):
        #  one-hot: the single chosen category; partition: the sorted suffix
        inv_at = jnp.take_along_axis(
            inv_order, best_f[:, None, None], axis=1
        )[:, 0, :]  # (N, B) rank of each bin in the sorted order
        bb = jnp.arange(B, dtype=jnp.int32)[None, :]
        in_range = bb < n_bins[best_f][:, None]
        set_oh = (bb == best_b[:, None])
        set_part = inv_at > best_b[:, None]
        cat_set = jnp.where(chosen_oh[:, None], set_oh, set_part) & in_range & is_cat[:, None]
    else:
        is_cat = jnp.zeros(N, bool)
        cat_set = jnp.zeros((N, B), bool)

    return BestSplit(
        gain=best_gain,
        feature=best_f,
        bin=best_b,
        default_left=dleft,
        left_sum=jnp.stack([GL, HL], axis=1),
        right_sum=jnp.stack([GR, HR], axis=1),
        left_weight=lw,
        right_weight=rw,
        is_cat=is_cat,
        cat_set=cat_set,
    )
