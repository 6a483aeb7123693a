"""Global best-first (lossguide) tree growing.

TPU-native equivalent of the reference's Driver priority queue
(src/tree/driver.h:30) + lossguide updater behavior: expand the single
highest-gain leaf anywhere in the tree, repeat until the ``max_leaves``
budget or no positive gain remains.  The tree is the serial driver's, node
ids in pop order, children ``(n, n+1)``, depth bounded only by ``max_depth``
(0 = unbounded); how it is reached is not serial.

*Evaluate ahead, commit in order.*  A chunk of the histogram's one-hot
matmul costs the same whether it builds one node or thirty-two (PERF.md §5),
and a pass has a fixed cost besides, so a pass (``level_step_bestfirst``,
one jitted program) takes the ``pairs`` nodes the driver is likeliest to pop
next among those whose children are not known yet (a node the tree does not
hold yet among them: it is popped no sooner than its ancestors, so its rank
is the least gain on its way down from the tree), routes their rows, builds
the smaller child of each pair from the rows (``level_histogram``, the
sibling as parent minus child from the histogram kept for every unsplit
node) and scans both children for their best splits (``evaluate_splits``).
*A pass costs the rows of its nodes*: on one chip the rows of the built
children are written as a list after the route, and the histogram scans the
list, 2,048 gathered rows a chunk, where it holds at most ``_LIST_SHARE`` of
the page (most passes of a deep tree hold a few per cent of it, and a pass on
a finished tree none), and the page itself where it holds more (the root's
pass always): chosen on the device from the list's length, both loops in the
one program (ops/histogram.py ``build_histogram_listed``).  Then the serial
driver is replayed on the device over what is now known: pop the open leaf of
highest gain; if its children were evaluated, commit the split (the children
take the next two ids) and go on; stop at the first popped leaf whose
children are not.  The best open leaf is always the first of the ``pairs``, so
a pass commits at least one split, and a node's candidate is a function of
its own histogram alone, so a pair that was evaluated and never popped costs
its built child's rows in one pass and nothing else: its rows sit below
their leaf and take the leaf's value.  A tree needs at least its depth in
passes; how many follows its shape (the queue stalls wherever a child just
made is the next to be popped).  A tree that spends its whole budget is given
the same number of passes whatever its shape (``_SPARE``); those beyond its
own hold no node and, where a pass scans a list, cost its fixed part.

While it grows the tree lives in SLOTS in evaluation order (a pass writes
its children as one contiguous block, the built child first); ``_finish``
puts nodes and rows into pop order.  The host reads four integers a pass
(done, splits, slots in use, rows scanned) and decides nothing but whether
the tree needs another; it sends the next pass before it reads the last.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..models.tree import RegTree
from ..ops.histogram import (BinTiers, combine_sibling_hists,
                             hist_is_row_pass, level_histogram, node_sums,
                             row_list, row_list_fits, rows_scanned)
from ..ops.split import (BestSplit, SplitParams, calc_weight, cat_columns,
                         evaluate_splits)
from ..telemetry import span
from ..telemetry.spans import count_in_round, wait_span
from .grow import _update_positions, make_set_matrix

_EPS = 1e-6
# Pairs of children a pass evaluates.  The tree does not depend on it
# (tests/test_bestfirst.py); the passes a tree takes and what a pass costs
# do.  A 255-leaf tree on HIGGS (depth 12-14, so 13-15 passes at the least)
# takes 36-38 passes at 8 pairs, 22-23 at 16, 17-18 at 24, 15-16 at 32 and
# 14-15 at 48 and at 64 (CPU, 1M rows; 21-24 at 16 on the chip at 10.5M);
# a pass over 10.5M x 28 costs 0.2019 s at 16 built nodes (32 output columns
# of the matmul), 0.213 s at 32 (64 columns) and 0.3756 s at 64 (PERF.md §5):
# the product is least at 32.
_PAIRS = 32
# How far below the tree a node's rank in the queue is followed exactly.
_WAIT = 8
# A tree that spends its budget runs ``_least_passes * (1 + _SPARE)`` passes
# whatever its shape: 18 at 255 leaves and 32 pairs, where the trees of HIGGS
# need 15 to 18 (mean 15.7; PERF.md §6).  The passes beyond a tree's own do
# no work that the tree needs: they are there so that a round takes the same
# time on every tree and seed, which the benchmark's admission of a cell asks
# (a spread under 0.5% over seeds, where three data-dependent trees a window
# spread 3%); each scans an empty list (2.8 ms on the chip; a whole page
# where no list is scanned, 12% of the rate before PR 33), and since a pass
# costs the rows of its nodes a round no longer takes the same time on every
# tree whatever their number.  A tree that stops short of its budget is given
# none.  Take this out (the constant, ``_least_passes``,
# ``BestFirstGrower.passes`` and the second half of the loop's condition)
# once the benchmark's window can hold a cell whose work follows its data.
_SPARE = 0.375
# The largest share of the page's rows for which a pass scans the list of its
# built children's rows and not the page (ops/histogram.py
# ``build_histogram_listed``).  On the chip at 10.5M x 28 and 64 columns
# (scripts/probe_rowlist.py --only shipped; PERF.md §6, PR 33) the page
# scanned straight costs 201.0 ms, 19.14 ns a row; a listed row, gathered with
# its pair, 52.3-52.7 ns at any share from 0.1 to 0.5 (55.8 at 0.02), and the
# list itself 9.85 ms where it is written for 1.3 where it is not: the two
# meet at 0.35 of the rows (9.85 + 0.35 x 10.5M x 52.4 ns = 202 ms).
_LIST_SHARE = 0.35


def _least_passes(max_leaves: int, pairs: int) -> int:
    """The passes no tree that spends its budget can do without: the root's,
    then as many pairs a pass as there are open leaves, ``pairs`` at most,
    every one of them committed."""
    passes, splits = 1, 0
    while splits < max_leaves - 1:
        splits += min(pairs, splits + 1)
        passes += 1
    return passes


class BFState(NamedTuple):
    """The tree under construction, by slot (module docstring).  A node is
    *committed* once the replay has given it its id (``fid >= 0``), *split*
    once its own split is committed, *expanded* once its children have slots
    (``left >= 0``); an open leaf is committed and not split."""

    pos: jnp.ndarray        # (R_pad,) int32 — slot per row, -1 = padding
    hist: jnp.ndarray       # (S, F, B, 2) f32 — of every node not yet split
    parent: jnp.ndarray     # (S,) int32 slot
    left: jnp.ndarray       # (S,) int32 slot of the left child, -1 before
    right: jnp.ndarray      # (S,) int32
    fid: jnp.ndarray        # (S,) int32 — id in pop order, -1 before
    split: jnp.ndarray      # (S,) bool
    depth: jnp.ndarray      # (S,) int32
    totals: jnp.ndarray     # (S, 2) f32
    lower: jnp.ndarray      # (S,) f32 monotone bounds
    upper: jnp.ndarray      # (S,) f32
    setcompat: jnp.ndarray  # (S, n_sets) bool
    path: jnp.ndarray       # (S,) uint32 — the way down from the root, hashed
    # the node's best split, known as soon as its histogram is
    cand_gain: jnp.ndarray  # (S,) f32, -inf where there is none
    cand_feat: jnp.ndarray  # (S,) int32
    cand_bin: jnp.ndarray   # (S,) int32
    cand_dleft: jnp.ndarray  # (S,) bool
    cand_lsum: jnp.ndarray  # (S, 2)
    cand_rsum: jnp.ndarray  # (S, 2)
    cand_lw: jnp.ndarray    # (S,) f32 clipped child weights
    cand_rw: jnp.ndarray    # (S,) f32
    cand_is_cat: jnp.ndarray  # (S,) bool
    cand_cat_set: jnp.ndarray  # (S, B) bool
    n_alloc: jnp.ndarray    # () int32 — slots in use (0: the root is to come)
    n_splits: jnp.ndarray   # () int32 — splits committed
    done: jnp.ndarray       # () bool — budget spent or no gain left
    told: jnp.ndarray       # (4,) int32 — done, n_splits, n_alloc, rows the
    #                         pass scanned: the host's one read a pass


class Picked(NamedTuple):
    """What a pass chose to evaluate: between its two halves."""

    sel: jnp.ndarray         # (k,) int32 slots of the parents, best first
    ok: jnp.ndarray          # (k,) bool
    build_left: jnp.ndarray  # (k,) bool — the left child is the built one
    root: jnp.ndarray        # () bool — this pass builds the root
    scanned: jnp.ndarray     # () int32 — rows its histogram visited


class BFTree(NamedTuple):
    """A finished tree in pop order (``n_slots`` long, ``n_nodes`` used) and
    every row's leaf."""

    pos: jnp.ndarray
    left: jnp.ndarray
    right: jnp.ndarray
    parent: jnp.ndarray
    feat: jnp.ndarray
    sbin: jnp.ndarray
    dleft: jnp.ndarray
    gain: jnp.ndarray
    totals: jnp.ndarray
    lower: jnp.ndarray
    upper: jnp.ndarray
    is_cat: jnp.ndarray
    cat_set: jnp.ndarray
    n_nodes: int


def _init_state(pos, root_totals, *, S: int, F: int, B: int, n_sets: int):
    i32, f32 = jnp.int32, jnp.float32
    return BFState(
        pos=pos,
        hist=jnp.zeros((S, F, B, 2), f32),
        parent=jnp.full(S, -1, i32),
        left=jnp.full(S, -1, i32),
        right=jnp.full(S, -1, i32),
        fid=jnp.full(S, -1, i32).at[0].set(0),
        split=jnp.zeros(S, bool),
        depth=jnp.zeros(S, i32),
        totals=jnp.zeros((S, 2), f32).at[0].set(root_totals),
        lower=jnp.full(S, -jnp.inf, f32),
        upper=jnp.full(S, jnp.inf, f32),
        setcompat=jnp.ones((S, n_sets), bool),
        path=jnp.zeros(S, jnp.uint32),
        cand_gain=jnp.full(S, -jnp.inf, f32),
        cand_feat=jnp.zeros(S, i32),
        cand_bin=jnp.zeros(S, i32),
        cand_dleft=jnp.ones(S, bool),
        cand_lsum=jnp.zeros((S, 2), f32),
        cand_rsum=jnp.zeros((S, 2), f32),
        cand_lw=jnp.zeros(S, f32),
        cand_rw=jnp.zeros(S, f32),
        cand_is_cat=jnp.zeros(S, bool),
        cand_cat_set=jnp.zeros((S, B), bool),
        n_alloc=jnp.zeros((), i32),
        n_splits=jnp.zeros((), i32),
        done=jnp.zeros((), bool),
        told=jnp.zeros(4, i32),
    )


def _expand(state: BFState, bins, gpair, *, pairs: int, max_leaves: int,
            gamma_eps: float, has_cat: tuple, list_rows: Optional[int],
            tiers: Optional[BinTiers] = None):
    """First half of a pass: choose the parents, route their rows, build the
    pass's histograms from the rows.  Returns ``(state, picked, built)`` with
    ``built`` (pairs, F, B, 2): of the built child of each pair, or of the
    root in the first pass (slot 0 is the built child of pair 0 there).
    ``list_rows``: the most rows for which the built children's rows are
    scanned as a list (``_LIST_SHARE``); None: the page, every pass.
    ``tiers``: the page's ``bin_tiers``, for the chunk's one-hot."""
    k, B = pairs, state.cand_cat_set.shape[1]
    i32 = jnp.int32
    with jax.named_scope("queue"):
        j = jnp.arange(k, dtype=i32)
        root = state.n_alloc == 0
        S = state.fid.shape[0]
        held = state.fid >= 0
        up = jnp.clip(state.parent, 0, None)
        # when the driver can pop a node: by its gain if the tree holds it,
        # else no sooner than its parent (exact for waits up to _WAIT deep;
        # the order only decides what is worth evaluating, never the tree)
        rank = lax.fori_loop(
            0, _WAIT, lambda _, r: jnp.where(
                held, state.cand_gain, jnp.minimum(state.cand_gain, r[up])),
            state.cand_gain)
        unknown = ((jnp.arange(S, dtype=i32) < state.n_alloc) & ~state.split
                   & (state.left < 0) & (rank > gamma_eps))
        top, sel = lax.top_k(jnp.where(unknown, rank, -jnp.inf), k)
        sel = sel.astype(i32)
        # never more pairs than splits are left to commit; and where the
        # evaluated splits that wait for their turn fill their room, the
        # best open leaf alone, whose split does not wait
        waiting = (state.n_alloc - 1) // 2 - state.n_splits
        n_sel = jnp.minimum(
            jnp.minimum(jnp.sum(top > -jnp.inf).astype(i32),
                        max_leaves - 1 - state.n_splits),
            jnp.maximum(max_leaves - waiting, 1))
        ok = j < n_sel
        # the smaller child (by its hessian sum) is built, its sibling
        # derived: what the subtraction loses is measured against the parent
        build_left = state.cand_lsum[sel, 1] <= state.cand_rsum[sel, 1]
    with jax.named_scope("route"):
        # a row's place among the chosen parents (and whether its pair is
        # built right), -1 for every other row: one select over the k
        # parents; then the level step's own route over those k "nodes"
        key = jnp.where(ok, 2 * j + (~build_left).astype(i32) + 1, 0)
        code = jnp.sum(jnp.where(sel[:, None] == state.pos[None, :],
                                 key[:, None], 0), axis=0) - 1
        jr, flip = code >> 1, code & 1
        chosen = BestSplit(
            gain=top, feature=state.cand_feat[sel], bin=state.cand_bin[sel],
            default_left=state.cand_dleft[sel], left_sum=None, right_sum=None,
            left_weight=None, right_weight=None,
            is_cat=state.cand_is_cat[sel], cat_set=state.cand_cat_set[sel])
        routed = _update_positions(bins, jr, chosen, ok, 0, k, B, has_cat)
        go_right = routed - (2 * jr + 1)
        # the built child takes the even slot of its pair
        pos = jnp.where(jr >= 0, state.n_alloc + 2 * jr + (go_right ^ flip),
                        state.pos)
    with jax.named_scope("hist"):
        rows, scanned = None, jnp.asarray(pos.shape[0], i32)
        if list_rows is not None:
            # the built children's rows: the even slots of the block this
            # pass writes (slot 0, every valid row, in the root's pass)
            rows = row_list(pos, state.n_alloc, n_nodes=k, stride=2,
                            most=list_rows)
            scanned = rows_scanned(rows, pos.shape[0])
        built = level_histogram(bins, gpair, pos, state.n_alloc, n_nodes=k,
                                n_bin=B, stride=2, rows=rows, tiers=tiers)
    return (state._replace(pos=pos),
            Picked(sel=sel, ok=ok, build_left=build_left, root=root,
                   scanned=scanned), built)


def _settle(state: BFState, picked: Picked, built, n_bins, root_mask,
            pair_masks, set_matrix, *, pairs: int, max_leaves: int,
            max_depth: int, gamma_eps: float, params: SplitParams,
            has_cat: tuple, monotone: bool):
    """Second half of a pass: the siblings by subtraction, every child's
    best split, the block of 2*pairs slots written, the driver replayed."""
    k = pairs
    i32 = jnp.int32
    sel, ok, bl, root, scanned = picked
    S = state.fid.shape[0]
    a = state.n_alloc

    def pair(x):  # (k, ...) of the parents -> (2k, ...) of their children
        return jnp.repeat(x, 2, axis=0)

    with jax.named_scope("split"):
        c = jnp.arange(2 * k, dtype=i32)
        first = root & (c == 0)  # the root as a child of nothing
        # a child is its parent's left one where its slot is the built one
        # (even) and the left child was built, or neither
        is_left = (c % 2 == 0) == pair(bl)
        valid = jnp.where(root, first, pair(ok))
    with jax.named_scope("hist"):
        block = combine_sibling_hists(built, state.hist[sel], valid)
    with jax.named_scope("split"):
        def side(lv, rv):
            pick = is_left.reshape((-1,) + (1,) * (lv.ndim - 1))
            return jnp.where(pick, pair(lv), pair(rv))

        totals = side(state.cand_lsum[sel], state.cand_rsum[sel])
        depth = pair(state.depth[sel]) + 1
        lower, upper = pair(state.lower[sel]), pair(state.upper[sel])
        f = state.cand_feat[sel]
        if monotone:
            # bounds propagation (constraints.cc ValueConstraint::SetChild)
            cvec = jnp.asarray(params.monotone, i32)
            c_at = pair(cvec[jnp.clip(f, 0, len(params.monotone) - 1)])
            mid = pair(0.5 * (state.cand_lw[sel] + state.cand_rw[sel]))
            lower, upper = (
                jnp.where(jnp.where(is_left, c_at < 0, c_at > 0), mid, lower),
                jnp.where(jnp.where(is_left, c_at > 0, c_at < 0), mid, upper))
        # interaction constraints: children keep only sets containing f
        # (constraints.cc FeatureInteractionConstraint path restriction)
        member = set_matrix.T[jnp.clip(f, 0, set_matrix.shape[1] - 1)]
        compat = pair(state.setcompat[sel] & member)
        # a child's column draw is keyed by its parent's way down from the
        # root and its side, so the tree does not depend on which pass
        # evaluated it
        draw = pair_masks[pair(state.path[sel]) % pair_masks.shape[0],
                          jnp.where(is_left, 0, 1)]
        path = 2 * pair(state.path[sel]) + jnp.where(is_left, 1, 2).astype(
            jnp.uint32)
        fr = first.reshape(-1, 1)
        totals = jnp.where(fr, state.totals[:1], totals)
        depth = jnp.where(first, 0, depth)
        path = jnp.where(first, 0, path)
        lower = jnp.where(first, -jnp.inf, lower)
        upper = jnp.where(first, jnp.inf, upper)
        compat = jnp.where(fr, True, compat)
        draw = jnp.where(fr, root_mask, draw)
        allowed = jnp.einsum("ns,sf->nf", compat.astype(jnp.float32),
                             set_matrix.astype(jnp.float32)) > 0.0
        best = evaluate_splits(block, totals, n_bins, params, allowed & draw,
                               jnp.stack([lower, upper], axis=1),
                               cat_cols=has_cat)
        gain = jnp.where(valid, best.gain, -jnp.inf)
        if max_depth > 0:
            gain = jnp.where(depth < max_depth, gain, -jnp.inf)
    with jax.named_scope("record"):
        def put(arr, blk):
            return lax.dynamic_update_slice_in_dim(arr, blk.astype(arr.dtype),
                                                   a, axis=0)

        # parents that were not chosen write to the last slot, which no
        # block reaches
        tgt = jnp.where(ok, sel, S - 1)
        built_slot = a + 2 * jnp.arange(k, dtype=i32)
        st = state._replace(
            hist=put(state.hist, block),
            parent=put(state.parent, jnp.where(first, -1, pair(sel))),
            left=put(state.left, jnp.full(2 * k, -1, i32))
            .at[tgt].set(jnp.where(bl, built_slot, built_slot + 1)),
            right=put(state.right, jnp.full(2 * k, -1, i32))
            .at[tgt].set(jnp.where(bl, built_slot + 1, built_slot)),
            fid=put(state.fid, jnp.where(first, 0, -1)),
            split=put(state.split, jnp.zeros(2 * k, bool)),
            depth=put(state.depth, depth),
            totals=put(state.totals, totals),
            lower=put(state.lower, lower),
            upper=put(state.upper, upper),
            setcompat=put(state.setcompat, compat),
            path=put(state.path, path),
            cand_gain=put(state.cand_gain, gain),
            cand_feat=put(state.cand_feat, best.feature),
            cand_bin=put(state.cand_bin, best.bin),
            cand_dleft=put(state.cand_dleft, best.default_left),
            cand_lsum=put(state.cand_lsum, best.left_sum),
            cand_rsum=put(state.cand_rsum, best.right_sum),
            cand_lw=put(state.cand_lw, best.left_weight),
            cand_rw=put(state.cand_rw, best.right_weight),
            cand_is_cat=put(state.cand_is_cat, best.is_cat),
            cand_cat_set=put(state.cand_cat_set, best.cat_set),
            n_alloc=jnp.where(root, 1, a + 2 * jnp.sum(ok).astype(i32)),
        )
    with jax.named_scope("queue"):
        st = _replay(st, max_leaves=max_leaves, gamma_eps=gamma_eps)
        # the host's one read: what the replay tells, and what the pass scanned
        return st._replace(told=jnp.append(st.told, scanned))


def _replay(st: BFState, *, max_leaves: int, gamma_eps: float) -> BFState:
    """The serial driver (driver.h pop/push) over what is known: commits, in
    pop order, every split whose children have been evaluated, and stops at
    the first popped leaf whose children have not, or where the driver
    itself stops (``done``)."""
    i32 = jnp.int32
    far = jnp.iinfo(jnp.int32).max

    def step(carry):
        fid, split, n, _, _ = carry
        is_open = (fid >= 0) & ~split
        gain = jnp.where(is_open, st.cand_gain, -jnp.inf)
        top = jnp.max(gain)
        # of equal gains the lowest id, as the driver's argmax over ids
        nid = jnp.argmin(jnp.where(is_open & (gain == top), fid, far))
        done = (top <= gamma_eps) | (n >= max_leaves - 1)
        commit = ~done & (st.left[nid] >= 0)
        kid = jnp.where(commit, 2 * n + 1, -1)
        return (fid.at[jnp.where(commit, st.left[nid], nid)]
                .set(jnp.where(commit, kid, fid[nid]))
                .at[jnp.where(commit, st.right[nid], nid)]
                .set(jnp.where(commit, kid + 1, fid[nid])),
                split.at[nid].set(split[nid] | commit),
                n + commit.astype(i32), ~commit, done)

    fid, split, n, _, done = lax.while_loop(
        lambda carry: ~carry[3], step,
        (st.fid, st.split, st.n_splits, jnp.zeros((), bool),
         jnp.zeros((), bool)))
    return st._replace(fid=fid, split=split, n_splits=n, done=done,
                       told=jnp.stack([done.astype(i32), n, st.n_alloc]))


_EXPAND_STATIC = ("pairs", "max_leaves", "gamma_eps", "has_cat", "list_rows")
_SETTLE_STATIC = ("pairs", "max_leaves", "gamma_eps", "has_cat", "max_depth",
                  "params", "monotone")
_STATIC = _SETTLE_STATIC + ("list_rows",)  # of the whole pass


@functools.partial(jax.jit, static_argnames=_STATIC)
def level_step_bestfirst(state: BFState, bins, gpair, n_bins, root_mask,
                         pair_masks, set_matrix, *, pairs: int,
                         max_leaves: int, max_depth: int, gamma_eps: float,
                         params: SplitParams, has_cat: tuple, monotone: bool,
                         list_rows: Optional[int] = None,
                         tiers: Optional[BinTiers] = None):
    """One pass (module docstring), the whole of it one program: a tree is
    this program run until ``state.done``, the root's pass its first run."""
    state, picked, built = _expand(
        state, bins, gpair, pairs=pairs, max_leaves=max_leaves,
        gamma_eps=gamma_eps, has_cat=has_cat, list_rows=list_rows,
        tiers=tiers)
    return _settle(state, picked, built, n_bins, root_mask, pair_masks,
                   set_matrix, pairs=pairs, max_leaves=max_leaves,
                   max_depth=max_depth, gamma_eps=gamma_eps, params=params,
                   has_cat=has_cat, monotone=monotone)


# the two halves apart, for rows that live in several processes: the built
# histograms cross them through the host between the halves
_expand_alone = jax.jit(_expand, static_argnames=_EXPAND_STATIC)
_settle_alone = jax.jit(_settle, static_argnames=_SETTLE_STATIC)


def _lookup(table, pos):
    """``table[pos]`` for every row (0 where ``pos`` is negative): where the
    histogram is the dense matmul a select over the table, no row-sized
    gather (tree/grow.py ``_update_positions`` has the readings)."""
    if hist_is_row_pass():
        return jnp.where(pos >= 0, table[jnp.clip(pos, 0, None)], 0)
    slots = jnp.arange(table.shape[0], dtype=pos.dtype)
    return jnp.sum(jnp.where(slots[:, None] == pos[None, :],
                             table[:, None], 0), axis=0)


@functools.partial(jax.jit, static_argnames=("n_slots",))
def _finish(st: BFState, *, n_slots: int) -> BFTree:
    """Slots to pop order.  A row below a leaf whose evaluated split was
    never committed goes back to that leaf."""
    with jax.named_scope("record"):
        S = st.fid.shape[0]
        own = st.fid >= 0
        # the nearest ancestor the tree holds, by pointer doubling
        anc = lax.while_loop(
            lambda a: ~jnp.all(own[a]), lambda a: a[a],
            jnp.where(own, jnp.arange(S, dtype=jnp.int32),
                      jnp.clip(st.parent, 0, None)))
        node_of = st.fid[anc]
        # slot of every id; ids past the tree's end read the last slot
        slot = jnp.full(n_slots, S - 1, jnp.int32).at[
            jnp.where(own, st.fid, n_slots)].set(
                jnp.arange(S, dtype=jnp.int32), mode="drop")
        inner = st.split[slot]

        def child(side):
            return jnp.where(inner, st.fid[jnp.clip(side[slot], 0, None)], -1)

        parent = jnp.where(slot == 0, -1,
                           st.fid[jnp.clip(st.parent[slot], 0, None)])
    with jax.named_scope("route"):
        pos = jnp.where(st.pos >= 0, _lookup(node_of, st.pos), -1)
    return BFTree(
        pos=pos, left=child(st.left), right=child(st.right), parent=parent,
        feat=jnp.where(inner, st.cand_feat[slot], -1),
        sbin=jnp.where(inner, st.cand_bin[slot], 0),
        dleft=jnp.where(inner, st.cand_dleft[slot], True),
        gain=jnp.where(inner, st.cand_gain[slot], 0.0),
        totals=st.totals[slot], lower=st.lower[slot], upper=st.upper[slot],
        is_cat=inner & st.cand_is_cat[slot],
        cat_set=st.cand_cat_set[slot] & inner[:, None],
        n_nodes=2 * st.n_splits + 1)


class BestFirstGrower:
    """Lossguide driver: passes of ``level_step_bestfirst`` until the
    replayed queue (driver.h pop/push) is done."""

    def __init__(self, max_depth: int, params: SplitParams, *,
                 max_leaves: int, interaction_sets=None,
                 distributed: bool = False, mesh=None) -> None:
        assert max_leaves > 1
        self.max_depth = max_depth  # 0 = unbounded
        self.params = params
        self.max_leaves = max_leaves
        self.interaction_sets = interaction_sets
        self.n_slots = 2 * max_leaves  # any L-leaf binary tree: 2L-1 nodes
        self.pairs = min(_PAIRS, max_leaves - 1)
        # what a tree that spends its budget runs at the least (_SPARE)
        self.passes = math.ceil(
            _least_passes(max_leaves, self.pairs) * (1 + _SPARE))
        # while it grows: the root, two slots a committed split, two an
        # evaluated split that waits (max_leaves of them and a pass's more,
        # _expand), a pass's block beyond the last slot in use, and one
        # slot that nothing reaches
        self._grow_slots = 4 * max_leaves + 4 * self.pairs
        # distributed=True: row shards live in other PROCESSES — a pass's
        # built histograms go through the host collective (the
        # AllReduceHist exchange) between its two halves, after which every
        # rank's replay pops the same nodes.  mesh: rows sharded over
        # in-process devices — inputs are placed row-sharded and GSPMD
        # inserts the psum inside the hist matmul itself (driver.h queue
        # semantics, global across shards, either way).
        self.distributed = distributed
        self.mesh = mesh

    def _masks(self, feature_masks, F: int):
        """(root's mask (1, F), the pairs' draws (P, 2, F)).  Column
        sampling: a fresh bylevel/bynode draw a pair (the reference's
        ColumnSampler draws as nodes are created), a pair taking the draw
        that its parent's way down from the root hashes to; the bytree mask
        is shared through the closure.  No sampling: one row of ones."""
        if feature_masks is None:
            return jnp.ones((1, F), bool), jnp.ones((1, 2, F), bool)
        root = jnp.asarray(feature_masks(0, 1))
        draws = np.stack([
            np.broadcast_to(np.asarray(feature_masks(0, 2)), (2, F))
            for _ in range(2 * self.max_leaves - 1)])
        return root, jnp.asarray(draws)

    def grow(self, bins, gpair, valid, cuts_pad, n_bins, feature_masks=None,
             cat_mask=None, tiers: Optional[BinTiers] = None) -> BFTree:
        """``tiers``: the page's ``bin_tiers``, for one chip's one program;
        the halves that rows in several processes run apart build one."""
        assert tiers is None or not (self.distributed
                                     or self.mesh is not None)
        F = bins.shape[1]
        B = cuts_pad.shape[1]
        has_cat = cat_columns(cat_mask)
        with span("grow.setup"):  # the tree's state and masks, before a pass
            setmat = jnp.asarray(make_set_matrix(self.interaction_sets, F))
            root_mask, pair_masks = self._masks(feature_masks, F)

            if self.mesh is not None:
                from ..parallel import shard_rows

                bins, gpair, valid = shard_rows(self.mesh, bins, gpair, valid)
            pos = jnp.where(valid, 0, -1).astype(jnp.int32)
            root = node_sums(gpair, pos, node0=0, n_nodes=1)[0]
            if self.distributed:
                from .. import collective  # the loop's passes use it too

                root = jnp.asarray(collective.allreduce(np.asarray(root)))
            state = _init_state(pos, root, S=self._grow_slots, F=F, B=B,
                                n_sets=setmat.shape[0])
        rows, width = int(bins.shape[0]), 2 * self.pairs
        # a pass scans the rows of the children it builds where they are few
        # (_LIST_SHARE); under a mesh a trip count that differs by shard
        # cannot be written without shard_map, and the row-pass kernels of
        # the CPU backend cost little a row: both scan the page
        listed = (self.mesh is None and not hist_is_row_pass()
                  and row_list_fits(rows, self.pairs))
        static = dict(
            pairs=self.pairs, max_leaves=self.max_leaves,
            max_depth=self.max_depth,
            gamma_eps=max(float(self.params.gamma), _EPS),
            params=self.params, has_cat=has_cat,
            monotone=(self.params.monotone is not None
                      and any(c != 0 for c in self.params.monotone)),
            list_rows=int(_LIST_SHARE * rows) if listed else None)

        def run(state):
            if not self.distributed:
                return level_step_bestfirst(
                    state, bins, gpair, n_bins, root_mask, pair_masks,
                    setmat, tiers=tiers, **static)
            state, picked, built = _expand_alone(
                state, bins, gpair,
                **{name: static[name] for name in _EXPAND_STATIC})
            built = jnp.asarray(collective.allreduce(np.asarray(built)))
            return _settle_alone(
                state, picked, built, n_bins, root_mask, pair_masks, setmat,
                **{name: static[name] for name in _SETTLE_STATIC})

        sent = passes = n_alloc = n_splits = 0
        told, scanned = collections.deque(), []
        done = False
        while not done or (n_splits == self.max_leaves - 1
                           and passes < self.passes):
            # one span a pass (the program fuses route, histogram, split scan
            # and replay; width = the child slots it evaluates), and in it
            # the one read that says whether the tree needs another; a tree
            # that spent its budget early runs to self.passes (_SPARE).  While
            # that schedule lasts the device is kept one pass ahead of the
            # read, so that it does not wait for the host between two passes
            # (a tree that stops short of its budget runs that one pass more)
            with span("grow.bestfirst_pass", rows=rows, width=width) as sp:
                while sent < max(passes + 1, min(passes + 2, self.passes)):
                    state = run(state)
                    told.append(state.told)
                    sent += 1
                with wait_span("grow.wait_device"):
                    done, splits_now, alloc_now, scanned_now = (
                        int(v) for v in np.asarray(told.popleft()))
                sp.args.update(pairs=(alloc_now - max(n_alloc, 1)) // 2,
                               committed=splits_now - n_splits,
                               scanned=scanned_now)
            passes, n_alloc, n_splits = passes + 1, alloc_now, splits_now
            scanned.append(scanned_now)
        if told:  # the pass sent ahead of a tree that stopped short
            with wait_span("grow.wait_device"):
                scanned += [int(np.asarray(unread)[3]) for unread in told]
        count_in_round(**{
            "bestfirst.passes": sent,
            "bestfirst.pairs_evaluated": (n_alloc - 1) // 2,
            "bestfirst.pairs_committed": n_splits,
            "bestfirst.hist_rows": sum(scanned),
            "bestfirst.listed_passes": sum(n < rows for n in scanned)})
        with span("grow.finish"):  # the dispatch of _finish, not its run
            return _finish(state, n_slots=self.n_slots)._replace(
                n_nodes=2 * n_splits + 1)

    def to_regtree(self, tree: BFTree, cuts_pad) -> "tuple[RegTree, np.ndarray]":
        """(RegTree in pop order, leaf_val array for the margin update)."""
        n = int(tree.n_nodes)
        fields = ("left", "right", "parent", "feat", "sbin", "dleft", "gain",
                  "totals", "lower", "upper", "is_cat", "cat_set")
        # every pass has been waited for; what is left is _finish
        with wait_span("grow.wait_device"):
            jax.block_until_ready(tree)
        with wait_span("grow.to_host", copies=len(fields) + 1):
            (left, right, parent, feat, sbin, dleft, gain, totals, lower,
             upper, is_cat, cat_set) = (
                np.asarray(getattr(tree, name))[:n] for name in fields)
            cuts_np = np.asarray(cuts_pad)
        # host only, but for calc_weight's trip to the device and back
        with span("tree.to_regtree"):
            B = cuts_np.shape[1]

            p = self.params
            w = np.asarray(calc_weight(jnp.asarray(totals[:, 0]),
                                       jnp.asarray(totals[:, 1]), p,
                                       jnp.asarray(lower), jnp.asarray(upper)))
            leaf_mask = left == -1
            thr = np.where(leaf_mask, 0.0,
                           cuts_np[np.clip(feat, 0, None),
                                   np.minimum(sbin, B - 1)]).astype(np.float32)
            leaf_val_full = np.zeros(self.n_slots, np.float32)
            leaf_val_full[:n] = np.where(leaf_mask, p.eta * w, 0.0)

            cats = {}
            for i in np.nonzero(~leaf_mask)[0]:
                if is_cat[i]:
                    cats[int(i)] = np.nonzero(cat_set[i])[0].astype(np.int32)
            regtree = RegTree(
                left_children=left.astype(np.int32),
                right_children=right.astype(np.int32),
                parents=parent.astype(np.int32),
                split_indices=np.where(leaf_mask, 0, feat).astype(np.int32),
                split_conditions=np.where(leaf_mask, p.eta * w, thr).astype(np.float32),
                default_left=dleft.astype(bool),
                base_weights=w.astype(np.float32),
                loss_changes=np.where(leaf_mask, 0.0, gain).astype(np.float32),
                sum_hessian=totals[:, 1].astype(np.float32),
                split_bins=np.where(leaf_mask, 0, sbin).astype(np.int32),
                split_type=is_cat.astype(np.int32),
                categories=cats or {},
            )
            return regtree, jnp.asarray(leaf_val_full)
