"""Depthwise hist tree growing — the ``tpu_hist`` updater core.

TPU-native re-design of the reference's GPU hist updater
(src/tree/updater_gpu_hist.cu:617 UpdateTree; Driver loop src/tree/driver.h:30).
The CUDA updater pops variable node batches from a priority queue and mutates
the tree on host; under XLA we need static shapes, so the tree grows strictly
level-by-level over a heap-indexed node array (node i -> children 2i+1, 2i+2).
Dead heap slots cost nothing: their node masks match no rows, so their
histograms are zero and they become weightless leaves.

A level is written once, in ``_level``: histogram (``level_histogram`` of
ops/histogram.py, which alone knows which kernel that is) -> decide
(``decide_level``: ops/split.py's scan, the gain threshold, the split budget,
the tree-array writes) -> route (``_update_positions``, the RowPartitioner
analogue, src/tree/gpu_hist/row_partitioner.cuh — here an elementwise ``pos``
rewrite, no physical partition).  It has two jitted entry points, because a
static and a traced ``node0`` are two programs: ``level_step`` (a program a
depth) and ``level_step_padded`` (one program a width, shared by the interior
depths that ``level_width`` pads to it); the compile cache is shared across
all trees and boosting rounds.
``HistTreeGrower.grow`` is the one depth-wise loop; parallel/grower.py
inherits it and wraps each program in ``shard_map``, with ``lax.psum`` on the
histogram (the reference's AllReduceHist,
src/tree/gpu_hist/histogram.cu:598-608): everything runs on device.  The
class-batched and the vector-leaf levels (grow_lockstep.py, grow_multi.py)
carry an axis of their own through every line and stay apart.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.histogram import (BinTiers, combine_sibling_hists,
                             hist_form, hist_is_row_pass, level_histogram,
                             node_sums, onehot_rows, transposed_page)
from ..ops.split import BestSplit, SplitParams, calc_weight, evaluate_splits
from ..telemetry import span
from ..telemetry.spans import count_in_round, wait_span

_EPS = 1e-6


class TreeState(NamedTuple):
    """Device-side tree under construction (heap layout, max_nodes slots)."""

    pos: jnp.ndarray  # (R_pad,) int32 — node id per row, -1 = padded/invalid
    alive: jnp.ndarray  # (max_nodes,) bool — candidate for expansion
    totals: jnp.ndarray  # (max_nodes, 2) f32 — node (G, H)
    feat: jnp.ndarray  # (max_nodes,) int32 — split feature, -1 for leaf
    sbin: jnp.ndarray  # (max_nodes,) int32 — split bin (left = bins <= sbin)
    thr: jnp.ndarray  # (max_nodes,) f32 — raw split condition cuts[f][sbin]
    dleft: jnp.ndarray  # (max_nodes,) bool — default direction for missing
    is_leaf: jnp.ndarray  # (max_nodes,) bool
    leaf_val: jnp.ndarray  # (max_nodes,) f32 — eta-scaled leaf weight
    gain: jnp.ndarray  # (max_nodes,) f32 — loss_chg of the split
    base_weight: jnp.ndarray  # (max_nodes,) f32 — raw node weight
    sum_hess: jnp.ndarray  # (max_nodes,) f32
    lower: jnp.ndarray  # (max_nodes,) f32 — monotone weight lower bound
    upper: jnp.ndarray  # (max_nodes,) f32 — monotone weight upper bound
    setcompat: jnp.ndarray  # (max_nodes, n_sets) bool — interaction sets alive
    splits_left: jnp.ndarray  # (1,) int32 — remaining split budget (max_leaves)
    is_cat: jnp.ndarray  # (max_nodes,) bool — categorical split
    cat_set: jnp.ndarray  # (max_nodes, B) bool — categories routed RIGHT


def max_nodes_for_depth(max_depth: int) -> int:
    return (1 << (max_depth + 1)) - 1


def make_set_matrix(interaction_sets, n_features: int):
    """(n_sets, F) bool membership matrix; unlisted features become singleton
    sets (reference semantics: unlisted features cannot interact with listed
    ones).  None -> a single all-True set (constraints disabled)."""
    import numpy as np

    if not interaction_sets:
        return np.ones((1, n_features), dtype=bool)
    listed = set()
    rows = []
    for grp in interaction_sets:
        row = np.zeros(n_features, dtype=bool)
        for f in grp:
            row[f] = True
            listed.add(int(f))
        rows.append(row)
    for f in range(n_features):
        if f not in listed:
            row = np.zeros(n_features, dtype=bool)
            row[f] = True
            rows.append(row)
    return np.stack(rows)


@functools.partial(
    jax.jit, static_argnames=("max_nodes", "axis_name", "n_sets", "max_splits",
                              "n_bin")
)
def init_tree_state(gpair, valid, *, max_nodes: int, axis_name: Optional[str] = None,
                    n_sets: int = 1, max_splits: int = 0, n_bin: int = 1):
    """Fresh state: all rows at the root; root totals (all)reduced.

    valid : (R_pad,) bool — False for padding rows.
    max_splits: total split budget (max_leaves - 1); 0 = unlimited.
    """
    R = gpair.shape[0]
    pos = jnp.where(valid, 0, -1).astype(jnp.int32)
    root = node_sums(gpair, pos, node0=0, n_nodes=1)  # (1, 2)
    if axis_name is not None:
        root = lax.psum(root, axis_name)
    mn = max_nodes
    totals = jnp.zeros((mn, 2), jnp.float32).at[0].set(root[0])
    budget = max_splits if max_splits > 0 else jnp.iinfo(jnp.int32).max
    return TreeState(
        pos=pos,
        alive=jnp.zeros(mn, bool).at[0].set(True),
        totals=totals,
        feat=jnp.full(mn, -1, jnp.int32),
        sbin=jnp.zeros(mn, jnp.int32),
        thr=jnp.zeros(mn, jnp.float32),
        dleft=jnp.ones(mn, bool),
        is_leaf=jnp.zeros(mn, bool),
        leaf_val=jnp.zeros(mn, jnp.float32),
        gain=jnp.zeros(mn, jnp.float32),
        base_weight=jnp.zeros(mn, jnp.float32),
        sum_hess=jnp.zeros(mn, jnp.float32),
        lower=jnp.full(mn, -jnp.inf, jnp.float32),
        upper=jnp.full(mn, jnp.inf, jnp.float32),
        setcompat=jnp.ones((mn, n_sets), bool),
        splits_left=jnp.full((1,), budget, jnp.int32),
        is_cat=jnp.zeros(mn, bool),
        cat_set=jnp.zeros((mn, n_bin), bool),
    )


def sync_root_totals(state):
    """Multi-process root GlobalSum (updater_gpu_hist.cu:581): the local root
    totals computed by init_*_state cross processes once.  Works for both the
    scalar TreeState ((mn, 2) totals) and MultiTreeState ((mn, K, 2))."""
    import numpy as np

    from .. import collective

    root = collective.allreduce(np.asarray(state.totals[:1]))
    return state._replace(totals=state.totals.at[0].set(jnp.asarray(root[0])))


def _record_level(st: TreeState, best, idx, can_split, new_leaf, w, thr_lvl,
                  totals_lvl, compat_lvl, member, new_budget, lower_lvl,
                  upper_lvl, params: SplitParams):
    """Apply one level's split decisions to the tree arrays."""
    st = st._replace(
        feat=st.feat.at[idx].set(jnp.where(can_split, best.feature, -1)),
        sbin=st.sbin.at[idx].set(jnp.where(can_split, best.bin, 0)),
        thr=st.thr.at[idx].set(jnp.where(can_split, thr_lvl, 0.0)),
        dleft=st.dleft.at[idx].set(best.default_left),
        is_leaf=st.is_leaf.at[idx].set(new_leaf),
        leaf_val=st.leaf_val.at[idx].set(jnp.where(new_leaf, params.eta * w, 0.0)),
        gain=st.gain.at[idx].set(jnp.where(can_split, best.gain, 0.0)),
        base_weight=st.base_weight.at[idx].set(w),
        sum_hess=st.sum_hess.at[idx].set(totals_lvl[:, 1]),
        is_cat=st.is_cat.at[idx].set(can_split & best.is_cat),
        cat_set=st.cat_set.at[idx].set(best.cat_set & can_split[:, None]),
    )
    left_ids = 2 * idx + 1
    right_ids = 2 * idx + 2
    st = st._replace(
        alive=st.alive.at[left_ids].set(can_split).at[right_ids].set(can_split),
        totals=st.totals.at[left_ids].set(best.left_sum).at[right_ids].set(best.right_sum),
        splits_left=jnp.full((1,), new_budget, jnp.int32),
    )
    child_compat = compat_lvl & member
    st = st._replace(
        setcompat=st.setcompat.at[left_ids].set(child_compat).at[right_ids].set(child_compat)
    )
    if params.monotone is not None and any(c != 0 for c in params.monotone):
        # bounds propagation: mid = (wL + wR)/2 splits the feasible interval
        # (reference: constraints.cc ValueConstraint::SetChild)
        cvec = jnp.asarray(params.monotone, jnp.int32)
        c_at = cvec[jnp.clip(best.feature, 0, len(params.monotone) - 1)]
        mid = 0.5 * (best.left_weight + best.right_weight)
        l_lo = jnp.where(c_at < 0, mid, lower_lvl)
        l_hi = jnp.where(c_at > 0, mid, upper_lvl)
        r_lo = jnp.where(c_at > 0, mid, lower_lvl)
        r_hi = jnp.where(c_at < 0, mid, upper_lvl)
        st = st._replace(
            lower=st.lower.at[left_ids].set(l_lo).at[right_ids].set(r_lo),
            upper=st.upper.at[left_ids].set(l_hi).at[right_ids].set(r_hi),
        )
    return st


def _update_positions(bins, pos, best, can_split, node0: int, N: int, B: int,
                      has_cat: bool):
    """Route rows of splitting nodes to their children (RowPartitioner
    analogue) — per-row elementwise, safe to run per page shard.

    A row at level offset ``lc = pos - node0`` in ``[0, N)`` whose node can
    split looks at ``binval = bins[r, feature[lc]]``: left if ``binval <=
    bin[lc]`` (or, for a categorical split, if ``binval`` is not in
    ``cat_set[lc]``), by ``default_left[lc]`` if ``binval >= B`` (missing);
    ``pos' = 2*pos + 1 + (0|1)``.  Every other row keeps ``pos``.

    Two forms of the same integer logic, bitwise equal on every input
    (tests/test_route.py), chosen where the histogram's is: a level step
    whose histogram is the dense one-hot matmul routes densely too, with no
    per-row gather (on the chip the five gathers cost 0.24 to 0.57 s a call
    at 10.5M rows, the dense pass 1.1 to 2.4 ms: PERF.md, PR 27); beside the
    CPU's row-pass histogram a gather is the cheap form, and so it is for a
    page too wide for a node's entry to fit one int32."""
    dense = not hist_is_row_pass() and _packed_bits(bins.shape[1], B) <= 31
    form = _update_positions_dense if dense else _update_positions_gather
    return form(bins, pos, best, can_split, node0, N, B, has_cat)


def _packed_bits(F: int, B: int) -> int:
    """Bits of a node's packed entry (``_update_positions_dense``): three
    flags, ``bin + 1`` in ``[0, B]``, the feature."""
    return 3 + int(B).bit_length() + max(F - 1, 1).bit_length()


def _update_positions_gather(bins, pos, best, can_split, node0, N: int,
                             B: int, has_cat: bool):
    """One gather a row out of each node table and one out of the page."""
    local = pos - node0
    in_lvl = (local >= 0) & (local < N)
    lc = jnp.clip(local, 0, N - 1)
    can_r = can_split[lc]
    fr = best.feature[lc]
    sb = best.bin[lc]
    dl = best.default_left[lc]
    binval = jnp.take_along_axis(
        bins, jnp.clip(fr, 0, bins.shape[1] - 1)[:, None].astype(jnp.int32), axis=1
    )[:, 0].astype(jnp.int32)
    goleft_num = binval <= sb
    if has_cat:
        # categorical: in right-set -> right (common/categorical.h Decision)
        flat = best.cat_set.reshape(-1)
        member = flat[lc * B + jnp.clip(binval, 0, B - 1)]
        goleft_split = jnp.where(best.is_cat[lc], ~member, goleft_num)
    else:
        goleft_split = goleft_num
    goleft = jnp.where(binval >= B, dl, goleft_split)  # sentinel B = missing
    child = 2 * pos + 1 + jnp.where(goleft, 0, 1)
    return jnp.where(in_lvl & can_r, child, pos)


def _update_positions_dense(bins, pos, best, can_split, node0, N: int,
                            B: int, has_cat: bool):
    """No gather with a row-sized output: what a row needs of its node is
    packed into one int32 a node on the node side and brought to the row by
    a select over the N nodes; the split feature's bin by a select over the
    F columns the histogram pass reads anyway.  Both are reduces that XLA
    fuses with their compare, so neither the (N, R) nor the (R, F) int32
    operand exists in memory.  Work a level: R*(N + F) selects."""
    F = bins.shape[1]
    i32 = jnp.int32
    # bit 0 can_split, 1 default_left, 2 is_cat, then bin + 1 in [0, B] (a
    # bin below 0 or above B - 1 routes every present value one way, as
    # ``binval <= bin`` does), then the feature
    bin_bits = int(B).bit_length()
    assert _packed_bits(F, B) <= 31, (F, B)
    packed = (can_split.astype(i32)
              | (best.default_left.astype(i32) << 1)
              | ((jnp.clip(best.bin, -1, B - 1).astype(i32) + 1) << 3)
              | (jnp.clip(best.feature, 0, F - 1).astype(i32) << (3 + bin_bits)))
    if has_cat:
        packed = packed | (best.is_cat.astype(i32) << 2)
    local = pos - node0
    # rows above or below the level, and padded rows, match no node: 0
    row = jnp.sum(jnp.where(jnp.arange(N, dtype=i32)[:, None] == local[None, :],
                            packed[:, None], 0), axis=0)
    can_r = (row & 1) == 1
    dl = (row & 2) == 2
    sb = ((row >> 3) & ((1 << bin_bits) - 1)) - 1
    fr = row >> (3 + bin_bits)
    binval = jnp.sum(jnp.where(jnp.arange(F, dtype=i32)[None, :] == fr[:, None],
                               bins.astype(i32), 0), axis=1)
    goleft = binval <= sb
    if has_cat:
        # the membership lookup keeps its gather: no cell has categories
        lc = jnp.clip(local, 0, N - 1)
        member = best.cat_set.reshape(-1)[lc * B + jnp.clip(binval, 0, B - 1)]
        goleft = jnp.where((row & 4) == 4, ~member, goleft)
    goleft = jnp.where(binval >= B, dl, goleft)  # sentinel B = missing
    child = 2 * pos + 1 + jnp.where(goleft, 0, 1)
    return jnp.where(can_r, child, pos)


def decide_level(state: TreeState, hist_of, cuts_pad, n_bins, feature_mask,
                 set_matrix, cat_mask, node0, N: int, *, params: SplitParams,
                 last_level: bool, lossguide: bool, has_cat: bool):
    """A level less its rows: the level's slices of the tree arrays, then
    either the leaf level's writes or histogram -> best splits -> who may
    take them -> the tree arrays with that written in.

    ``hist_of`` maps the level's (N,) alive mask to ``(hist, hist_eval)``:
    the level's sums as the next level subtracts from them, and as the
    (N, F, B, 2) float32 the split scan reads.  ``_level`` builds them there,
    between the slices and the scan; the growers whose rows come a page or a
    process at a time (tree/stream.py) have summed them before, and route
    the rows themselves.  Returns ``(state, best, can_split, hist)``, the
    last three None on the last level."""
    B = cuts_pad.shape[1]
    with jax.named_scope("split"):
        idx = node0 + jnp.arange(N, dtype=jnp.int32)
        totals_lvl = lax.dynamic_slice_in_dim(state.totals, node0, N, axis=0)
        alive_lvl = lax.dynamic_slice_in_dim(state.alive, node0, N, axis=0)
        lower_lvl = lax.dynamic_slice_in_dim(state.lower, node0, N, axis=0)
        upper_lvl = lax.dynamic_slice_in_dim(state.upper, node0, N, axis=0)
        w = calc_weight(totals_lvl[:, 0], totals_lvl[:, 1], params,
                        lower_lvl, upper_lvl)

    if last_level:
        # no hist needed: every surviving node becomes a leaf
        with jax.named_scope("record"):
            return state._replace(
                is_leaf=state.is_leaf.at[idx].set(alive_lvl),
                leaf_val=state.leaf_val.at[idx].set(
                    jnp.where(alive_lvl, params.eta * w, 0.0)
                ),
                base_weight=state.base_weight.at[idx].set(w),
                sum_hess=state.sum_hess.at[idx].set(totals_lvl[:, 1]),
            ), None, None, None

    hist, hist_eval = hist_of(alive_lvl)

    with jax.named_scope("split"):
        # interaction constraints: allowed feature set per node = union of
        # the constraint sets still compatible with the node's path
        # (reference: src/tree/constraints.cc FeatureInteractionConstraint)
        compat_lvl = lax.dynamic_slice_in_dim(state.setcompat, node0, N, axis=0)
        allowed = jnp.einsum("ns,sf->nf", compat_lvl.astype(jnp.float32),
                             set_matrix.astype(jnp.float32)) > 0.0  # (N, F)
        fm = feature_mask if feature_mask.ndim == 2 else feature_mask[None, :]
        fmask = allowed & fm

        node_bounds = jnp.stack([lower_lvl, upper_lvl], axis=1)
        best = evaluate_splits(hist_eval, totals_lvl, n_bins, params, fmask,
                               node_bounds,
                               cat_mask=cat_mask if has_cat else None)

        gamma_eps = max(params.gamma, _EPS)
        can_split = alive_lvl & (best.gain > gamma_eps)

        # split budget (max_leaves): expand best-first under lossguide,
        # node-order under depthwise (reference: src/tree/driver.h
        # grow-policy queue)
        budget = state.splits_left[0]
        prio = best.gain if lossguide else -idx.astype(jnp.float32)
        prio = jnp.where(can_split, prio, -jnp.inf)
        order = jnp.argsort(-prio)
        ranks = jnp.argsort(order).astype(jnp.int32)
        can_split = can_split & (ranks < budget)
        new_budget = budget - jnp.sum(can_split).astype(jnp.int32)

        new_leaf = alive_lvl & ~can_split

        thr_lvl = cuts_pad[best.feature, jnp.minimum(best.bin, B - 1)]
        member = set_matrix.T[jnp.clip(best.feature, 0, set_matrix.shape[1] - 1)]  # (N, n_sets)
    with jax.named_scope("record"):
        st = _record_level(state, best, idx, can_split, new_leaf, w, thr_lvl,
                           totals_lvl, compat_lvl, member, new_budget,
                           lower_lvl, upper_lvl, params)
    return st, best, can_split, hist


def _level(state: TreeState, bins, gpair, cuts_pad, n_bins, feature_mask,
           set_matrix, cat_mask, hist_prev, rho, node0, N: int, *,
           params: SplitParams, last_level: bool, axis_name: Optional[str],
           lossguide: bool, has_cat: bool, subtract: bool, quantised: bool,
           tiers: Optional[BinTiers] = None, bins_t: Optional[tuple] = None):
    """One level, the only place it is written: histogram -> decide -> route
    over the ``N`` heap slots from ``node0``, a Python int (``level_step``: a
    program a depth) or a traced scalar (``level_step_padded``: one program
    for every interior depth of a width).

    Mirrors one driver iteration of the reference
    (updater_gpu_hist.cu:626-646: PartitionAndBuildHist + ReduceHist +
    EvaluateSplits + ApplySplit), with the node batch = the whole level.

    Returns ``(state, hist)`` — ``hist`` (N, F, B, C) feeds the next level's
    subtraction trick (updater_gpu_hist.cu:309 SubtractHist): with
    ``subtract=True`` and ``hist_prev`` = the parent level's histogram, only
    left children (even level offsets) are built by matmul and each right
    sibling is derived as ``parent - left`` — halving both the hist FLOPs and
    (multi-chip) the psum payload.  ``hist`` is None on the last level.
    ``tiers`` (ops/histogram.py ``bin_tiers``) shorten the one-hot inside
    ``level_histogram`` and nothing else: what comes back is the (N, F, B, C)
    histogram in column order.  ``bins_t`` (``transposed_page``: the grower's
    own copy) is what the one-pass kernel reads where ``level_histogram``
    picks it; None, or under a mesh, is the XLA form.
    """
    B = cuts_pad.shape[1]
    sharded = axis_name is not None

    def hist_of(alive_lvl):
        # quantised: gpair is the (R, C, 3) int8 limb array: integer builds
        # and psums are exact/order-invariant, so hist bits are topology-free
        # (the reference's GradientQuantiser contract, quantiser.cuh:52)
        with jax.named_scope("hist"):
            if subtract:
                half = N // 2
                # left children sit at even offsets 2j (heap id node0 + 2j);
                # parent j of the previous level maps to offsets (2j, 2j+1)
                left = level_histogram(bins, gpair, state.pos, node0,
                                       n_nodes=half, n_bin=B, stride=2,
                                       quantised=quantised, tiers=tiers,
                                       bins_t=bins_t, sharded=sharded)
                if axis_name is not None:
                    left = lax.psum(left, axis_name)
                # a parent level handed over at this level's width has its
                # real rows first, and they are N/2 at most
                hist = combine_sibling_hists(left, hist_prev[:half], alive_lvl)
            else:
                hist = level_histogram(bins, gpair, state.pos, node0,
                                       n_nodes=N, n_bin=B, quantised=quantised,
                                       tiers=tiers, bins_t=bins_t,
                                       sharded=sharded)
                if axis_name is not None:
                    # the distributed cost (SURVEY §3.1)
                    hist = lax.psum(hist, axis_name)
            if not quantised:
                return hist, hist
            from ..ops.quantise import dequantise

            return hist, dequantise(hist, rho)  # the ONE rounding step

    st, best, can_split, hist = decide_level(
        state, hist_of, cuts_pad, n_bins, feature_mask, set_matrix, cat_mask,
        node0, N, params=params, last_level=last_level, lossguide=lossguide,
        has_cat=has_cat)
    if not last_level:
        with jax.named_scope("route"):
            st = st._replace(
                pos=_update_positions(bins, st.pos, best, can_split, node0, N,
                                      B, has_cat))
    return st, hist


@functools.partial(
    jax.jit,
    static_argnames=("depth", "params", "last_level", "axis_name",
                     "lossguide", "has_cat", "subtract", "quantised"),
)
def level_step(
    state: TreeState,
    bins,
    gpair,
    cuts_pad,
    n_bins,
    feature_mask,
    set_matrix,
    cat_mask,
    hist_prev=None,
    rho=None,
    *,
    depth: int,
    params: SplitParams,
    last_level: bool,
    axis_name: Optional[str] = None,
    lossguide: bool = False,
    has_cat: bool = False,
    subtract: bool = False,
    quantised: bool = False,
    tiers: Optional[BinTiers] = None,
    bins_t: Optional[tuple] = None,
):
    """Expand every alive node at ``depth`` (``_level``): a program a depth,
    ``node0`` and the width ``2**depth`` its constants."""
    return _level(state, bins, gpair, cuts_pad, n_bins, feature_mask,
                  set_matrix, cat_mask, hist_prev, rho, (1 << depth) - 1,
                  1 << depth, params=params, last_level=last_level,
                  axis_name=axis_name, lossguide=lossguide, has_cat=has_cat,
                  subtract=subtract, quantised=quantised, tiers=tiers,
                  bins_t=bins_t)


@functools.partial(
    jax.jit,
    static_argnames=("width", "params", "axis_name", "lossguide", "has_cat",
                     "subtract", "quantised"),
)
def level_step_padded(
    state: TreeState,
    bins,
    gpair,
    cuts_pad,
    n_bins,
    feature_mask,
    set_matrix,
    cat_mask,
    hist_prev,
    node0,
    rho=None,
    *,
    width: int,
    params: SplitParams,
    axis_name: Optional[str] = None,
    lossguide: bool = False,
    has_cat: bool = False,
    subtract: bool = True,
    quantised: bool = False,
    tiers: Optional[BinTiers] = None,
    bins_t: Optional[tuple] = None,
):
    """``_level`` with the node dimension PADDED to a fixed ``width`` and
    a TRACED ``node0`` — ONE compiled program serves every interior depth
    that is dispatched at that width (VERDICT r3 #4: the per-depth compile
    wall).

    ``width`` >= 2**depth is ``level_width``'s to choose, and the reasons
    are there.  What the padding costs on the chip: a level builds
    ``width // 2`` left children whatever its depth (ops/histogram.py: the
    one-pass kernel over ``bins_t`` at 32 slots, the one-hot matmul at
    HIGHEST beyond), and that is nearly all of a level's time.  On
    the CPU the row-pass kernels add only where a row's node matches, and
    the padding costs the wider output block alone.

    Correctness of the padding (garbage level offsets j >= 2**depth):
    - their heap slots overlay only DEEPER levels' ids, whose real writes
      happen at later steps, strictly after every garbage write;
    - within one step, left/right child scatter indices are all distinct
      (odd/even disjoint), so garbage and real writes never collide;
    - garbage rows match no ``pos`` (row positions only ever hold ids of
      levels <= current), so their histograms, and hence gains, are zero and
      ``alive`` is False — they can never split or consume ``max_leaves``
      budget (their priority is -inf, which cannot outrank any real
      candidate's finite priority).

    ``hist_prev``/returned ``hist`` use the padded level-offset layout
    (width, F, B, C); row j = heap node ``node0 + j``.
    """
    return _level(state, bins, gpair, cuts_pad, n_bins, feature_mask,
                  set_matrix, cat_mask, hist_prev, rho,
                  jnp.asarray(node0, jnp.int32), width, params=params,
                  last_level=False, axis_name=axis_name, lossguide=lossguide,
                  has_cat=has_cat, subtract=subtract, quantised=quantised,
                  tiers=tiers, bins_t=bins_t)


@jax.jit
def leaf_margin_delta(pos, leaf_val):
    """Per-row margin update from the finished tree — the prediction-cache
    fast path (reference: TreeUpdater::UpdatePredictionCache,
    include/xgboost/tree_updater.h:92): every row sits on its leaf already."""
    with jax.named_scope("margin"):
        safe = jnp.clip(pos, 0, leaf_val.shape[0] - 1)
        return jnp.where(pos >= 0, leaf_val[safe], 0.0)


class GrownTree(NamedTuple):
    """Host copy of a finished tree (heap layout)."""

    is_cat: "object"
    cat_set: "object"
    feat: "object"
    sbin: "object"
    thr: "object"
    dleft: "object"
    is_leaf: "object"
    leaf_val: "object"
    gain: "object"
    base_weight: "object"
    sum_hess: "object"
    totals: "object"


# The narrowest width a shared interior program is padded to: 32 slots = 16
# built left children = 32 output columns of the histogram's matmul, and 96
# rows of the one-pass kernel's three-term operand: what fits its one MXU
# tile (ops/histogram.py hist_form).  On the chip a whole level at 10.5M x 28
# takes 0.0514 s at the root and 0.0770 s at 32 slots on the kernel (0.1769
# and 0.2006 s in the XLA form, where the cost is flat up to 32 slots);
# beyond, the XLA form's matmul is paid for: 0.213 s at 64 slots and 0.3756 s
# at 128, 89% of the three-pass bfloat16 peak (PERF.md §5, §7).
_WIDTH_FLOOR = 32


def level_width(d: int, max_depth: int) -> int:
    """Slots the shared program of interior depth ``d`` is padded to; the
    only place that is decided.  The smallest of 32, 128, 512, ... that holds
    the level's ``2**d`` nodes, and never more than the widest interior
    level, ``2**(max_depth-1)``: up to depth 6 one width, as it always was.

    Steps of x4 and not x2: every tier is one more program with a scan over
    all the rows in it, and at 10.5M rows such a program compiles cold for
    about five minutes (ROADMAP S12)."""
    width = _WIDTH_FLOOR
    while width < (1 << d):
        width *= 4
    return min(1 << (max_depth - 1), width)


def default_padded_levels(max_depth: int) -> bool:
    """Platform rule for sharing padded interior level programs across
    depths (one a width tier of ``level_width``) in place of a program a
    depth: on accelerators the padding is nearly free up to the narrowest
    tier's width (``_WIDTH_FLOOR`` has the readings) and killing the
    per-depth compile wall matters.  On CPU the rule depends on the
    histogram impl: the native/scatter row-pass kernels add only for rows
    whose node matches, so a padded node dimension costs just the wider
    (memset) output block and the shared program wins there too; only the
    forced matmul impl still pays the full padded operand width at every
    depth (r5: the bench compile_est 8.8s -> ~4s came from extending this
    to the CPU default)."""
    if jax.default_backend() != "cpu" or max_depth <= 5:
        return True
    # native/scatter row-pass kernels: padding costs only the padded hist
    # output blocks (memset + accumulate traffic, 2**(md-1)*F*B*2 floats
    # per level) and the scan over dead slots is short-circuited in the
    # native kernel — a clear win at the bench depth 6, but at depth 8 the
    # 128-wide buffers measurably outweigh the saved compiles, so deep CPU
    # trees keep per-depth programs
    return hist_is_row_pass() and max_depth <= 6


class HistTreeGrower:
    """Host driver looping jitted level steps (reference: GPUHistMaker::Update,
    src/tree/updater_gpu_hist.cu:703)."""

    # whether the level programs run under a mesh (parallel/grower.py)
    sharded = False

    def __init__(
        self,
        max_depth: int,
        params: SplitParams,
        *,
        interaction_sets=None,
        max_leaves: int = 0,
        lossguide: bool = False,
        subtract: bool = True,
        padded_levels: Optional[bool] = None,
        quantised: bool = False,
    ) -> None:
        self.max_depth = max_depth
        self.params = params
        self.interaction_sets = interaction_sets
        self.max_leaves = max_leaves
        self.lossguide = lossguide
        self.subtract = subtract
        # fixed-point limb histograms: bitwise-identical trees on EVERY
        # topology (chips x processes) — the GradientQuantiser contract
        # (src/tree/gpu_hist/quantiser.cuh); see ops/quantise.py
        self.quantised = quantised
        # shared compiled programs for the interior depths (padded node
        # dim + traced node0, one a width of level_width) instead of one
        # per depth; None = the platform's rule (default_padded_levels has
        # the reasons)
        if padded_levels is None:
            padded_levels = default_padded_levels(max_depth)
        self.padded_levels = padded_levels
        self.max_nodes = max_nodes_for_depth(max_depth)
        # (page, tiers, transposed_page of them): the copy the one-pass
        # kernel reads, kept while the same page comes back
        self._page_t = None

    def _transposed(self, bins, tiers: Optional[BinTiers]) -> tuple:
        """The page transposed, a tier at a time (``transposed_page``): made
        at a page's first tree and kept in the grower's own state, not in
        the page (one more copy of it on the device: 588 MB at 10.5M x 28,
        1.83 GB at 946,997 x 968)."""
        kept = self._page_t
        if kept is None or kept[0] is not bins or kept[1] is not tiers:
            self._page_t = kept = (bins, tiers, transposed_page(bins, tiers))
        return kept[2]

    def _built(self, d: int, width: Optional[int]) -> int:
        """Nodes whose histogram level ``d`` builds from the rows, dispatched
        at ``width`` slots (None: at its own ``2**d``); none at the leaf
        level."""
        if d == self.max_depth:
            return 0
        slots = width or (1 << d)
        return slots // 2 if self.subtract and d > 0 else slots

    def _init_state(self, gpair, valid, setmat, cuts_pad,
                    has_cat: bool) -> TreeState:
        return init_tree_state(
            gpair, valid, max_nodes=self.max_nodes, n_sets=setmat.shape[0],
            n_bin=cuts_pad.shape[1],
            max_splits=(self.max_leaves - 1) if self.max_leaves > 0 else 0,
        )

    def _run_level(self, d: int, width: Optional[int], state, page, fm,
                   setmat, cm, hist_prev, rho, has_cat: bool,
                   tiers: Optional[BinTiers] = None,
                   bins_t: Optional[tuple] = None):
        """Dispatch depth ``d``'s program: ``(state, hist)``.  With a
        ``width``, the shared padded interior program of that width with a
        traced node0: root, leaf finalize and one program a tier of
        ``level_width`` (one up to depth 6, two up to depth 8), however
        deep the tree.  With None, a program a depth.  ``bins_t``: the
        transposed page, for a level that the one-pass kernel builds."""
        md = self.max_depth
        common = dict(params=self.params, lossguide=self.lossguide,
                      has_cat=has_cat, quantised=self.quantised, tiers=tiers)
        if bins_t is not None:  # None: the program as it always lowered
            common["bins_t"] = bins_t
        if width is not None:
            return level_step_padded(
                state, *page, fm, setmat, cm, hist_prev, (1 << d) - 1, rho,
                width=width, subtract=self.subtract, **common)
        return level_step(
            state, *page, fm, setmat, cm, hist_prev, rho, depth=d,
            last_level=(d == md), subtract=(self.subtract and 0 < d < md),
            **common)

    def grow(self, bins, gpair, valid, cuts_pad, n_bins, feature_masks=None,
             cat_mask=None, tiers: Optional[BinTiers] = None,
             resident: bool = False) -> TreeState:
        """feature_masks: None, or callable (depth, n_nodes) -> (1|N, F) bool mask
        (the ColumnSampler hook: bytree/bylevel/bynode, src/common/random.h).
        cat_mask: optional (F,) bool marking categorical features.
        tiers: the page's ``bin_tiers`` (``EllpackPage.tiers``), for the
        float32 one-hot on one chip; None is one tier of ``B`` bins.
        resident: ``bins`` is the same page tree after tree (core.py: not
        ``approx``, which bins anew every round), so a transposed copy of it
        pays: the levels that ``hist_form`` gives the one-pass kernel read
        it; without, every level keeps the XLA form."""
        F = bins.shape[1]
        with span("grow.setup"):  # the tree's state and masks, before a level
            ones = jnp.ones((1, F), dtype=bool)
            setmat = jnp.asarray(make_set_matrix(self.interaction_sets, F))
            has_cat = cat_mask is not None
            cm = jnp.asarray(cat_mask) if has_cat else jnp.zeros(F, bool)
            state = self._init_state(gpair, valid, setmat, cuts_pad, has_cat)
            rho = None
            if self.quantised:
                from ..ops.quantise import prepare_quantised

                gpair, rho, state = prepare_quantised(gpair, valid, state)
        md = self.max_depth
        page = (bins, gpair, cuts_pad, n_bins)
        tall = onehot_rows(tiers, cuts_pad.shape[1], F)
        hist = None
        onepass = 0
        for d in range(md + 1):
            # the root and the leaf level have programs of their own; the
            # levels between are 2**d slots wide, or padded to the width of
            # their tier
            width = (level_width(d, md)
                     if self.padded_levels and 0 < d < md else None)
            # one span per level: the compiled program fuses build_hist +
            # eval_split + the position rewrite, so the bracket necessarily
            # covers all three — the name keeps the reference phase vocabulary
            # greppable in traces (bestfirst.py's pass has a span of its own);
            # width = the slots the level was dispatched at, onehot_rows
            # the height of its chunks' one-hot operand, hist_form how its
            # histogram is built (ops/histogram.py hist_form; "none" at the
            # leaf level, which builds none).  The level's inputs
            # are made inside it (eager programs of their own, a millisecond
            # a tree): its column mask and its parent's histogram
            built = self._built(d, width)
            form = "none" if not built else "xla" if not resident else \
                hist_form(built, channels=gpair.shape[1],
                          quantised=self.quantised, sharded=self.sharded)
            onepass += form == "onepass"
            with span("grow.build_hist+eval_split", depth=d,
                      width=width or (1 << d), onehot_rows=tall,
                      hist_form=form):
                fm = (ones if feature_masks is None
                      else feature_masks(d, 1 << d))
                if width is not None:
                    fm = self._pad_mask(fm, width)
                    if hist.shape[0] != width:
                        # the parent level's histogram (the root's, or a
                        # narrower tier's), handed over at this level's
                        # width: its real rows first, zero rows after
                        hist = jnp.zeros(
                            (width,) + hist.shape[1:],
                            hist.dtype).at[:hist.shape[0]].set(hist)
                state, hist = self._run_level(
                    d, width, state, page, fm, setmat, cm,
                    None if d == md else hist, rho, has_cat, tiers,
                    self._transposed(bins, tiers) if form == "onepass"
                    else None)
        count_in_round(**{"hist.onepass_levels": onepass})
        return state

    @staticmethod
    def _pad_mask(fm, W: int):
        """Pad a (N, F) per-node feature mask to the fixed (W, F) level width
        (False rows can never split); (1, F) masks broadcast unchanged."""
        fm = jnp.asarray(fm)
        if fm.ndim == 2 and 1 < fm.shape[0] < W:
            fm = jnp.concatenate(
                [fm, jnp.zeros((W - fm.shape[0], fm.shape[1]), bool)], axis=0)
        return fm

    @staticmethod
    def to_host(state: TreeState) -> GrownTree:
        import numpy as np

        # the one place a round with no evals blocks on the device: what is
        # left of the round's host time is the loop's own
        with wait_span("grow.wait_device"):
            jax.block_until_ready(state)
        with wait_span("grow.to_host",
                       copies=len(GrownTree._fields)) as copying:
            tree = GrownTree(
                is_cat=np.asarray(state.is_cat),
                cat_set=np.asarray(state.cat_set),
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
            # how many splits the tree made and how many of them send their
            # absent rows left: on this span and summed on the round's
            split = (tree.feat >= 0) & ~tree.is_leaf
            counts = {"splits": int(split.sum()),
                      "splits.default_left": int((tree.dleft & split).sum())}
            copying.args.update(counts)
            count_in_round(**counts)
            return tree
