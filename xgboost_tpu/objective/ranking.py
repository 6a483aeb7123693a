"""Learning-to-rank objectives: LambdaMART (reference:
src/objective/lambdarank_obj.cc / .cu, 675+ LoC).

The reference samples ``lambdarank_num_pair_per_sample`` pairs per document
within each query group (pair_method="mean") or uses top-k pairs (the
default).  Here groups are padded to a (G, S) doc grid (S = the longest
group) so ranks, pairs and lambda accumulation are fixed-shape vectorized
ops; the per-group IDCG and rank discounts follow LambdaMARTCalcDeltaNDCG.
The layout, and what of it the labels fix (the gains on the grid, the
ideal DCG), is built once in ``set_group_info``; a round computes only what
the margin moves.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from ..telemetry.spans import span
from . import ObjFunction, register_objective

# pair cells (group x top-k x slot) of one lax.map block of the top-k
# gradient: ~2^22 keeps a block's intermediates near 100 MB
_PAIR_CELLS_A_BLOCK = 1 << 22


def _group_slots(group_ptr: np.ndarray):
    """(group of each row, its place inside the group), rows in CSR order."""
    group_ptr = np.asarray(group_ptr, np.int64)
    sizes = np.diff(group_ptr)
    gid = np.repeat(np.arange(len(sizes), dtype=np.int64), sizes)
    pos = np.arange(int(group_ptr[-1]), dtype=np.int64) - group_ptr[:-1][gid]
    return gid, pos


def make_group_layout(group_ptr: np.ndarray):
    """Host: CSR group_ptr -> padded (G, S) row-index matrix + mask + the
    inverse map row -> flat (g*S + s) slot (rows appear exactly once, so the
    padded-grid gradients come back to row order with a GATHER, no scatter —
    TPU scatter-adds are serialized)."""
    sizes = np.diff(group_ptr)
    G = len(sizes)
    S = int(sizes.max()) if G else 1
    gid, pos = _group_slots(group_ptr)
    inv = (gid * S + pos).astype(np.int32)
    idx = np.zeros(G * S, dtype=np.int32)
    idx[inv] = np.arange(len(inv), dtype=np.int32)
    mask = np.zeros(G * S, dtype=bool)
    mask[inv] = True
    return idx.reshape(G, S), mask.reshape(G, S), inv


class TopkLayout(NamedTuple):
    """What the top-k gradient reads besides the margin, all fixed by the
    groups and the labels: groups in ``n_blocks`` blocks of ``gb`` (the last
    block filled up with empty groups), each group ``S`` slots wide."""
    start: np.ndarray  # (n_blocks, gb) int32: a group's first row
    count: np.ndarray  # (n_blocks, gb) int32: its documents
    gain: np.ndarray   # (n_blocks, gb, S) float32: 2^label - 1 on the grid
    idcg: np.ndarray   # (n_blocks, gb) float32: ideal DCG of those gains
    slot: np.ndarray   # (R,) int32: row -> flat slot of the grid


def make_topk_layout(group_ptr: np.ndarray, labels: np.ndarray, k: int,
                     exp_gain: bool = True) -> TopkLayout:
    """Host, once a training matrix: the grid of ``_lambda_gradients_topk``.
    No loop over groups: every array is a scatter or a segment sum over the
    rows.  The gains are made here, in float64, with the ideal DCG that
    is made of them.  ``exp_gain=False`` (the objectives that
    weigh every pair alike) puts the labels themselves on the grid: only
    their order is read."""
    group_ptr = np.asarray(group_ptr, np.int64)
    sizes = np.diff(group_ptr)
    G, R = len(sizes), int(group_ptr[-1])
    S = max(int(sizes.max()), 1)
    n_blocks = -(-G // max(1, _PAIR_CELLS_A_BLOCK // (min(k, S) * S)))
    gb = -(-G // n_blocks)  # the fewest empty groups in the last block
    Gp = n_blocks * gb
    gid, pos = _group_slots(group_ptr)
    slot = gid * S + pos
    y = np.asarray(labels, np.float64)[:R]
    gain = np.exp2(y) - 1.0 if exp_gain else y
    grid = np.zeros(Gp * S, np.float32)
    grid[slot] = gain
    # ideal DCG: a group's gains in descending order against the discounts
    ideal = np.lexsort((-gain, gid))
    idcg = np.bincount(gid, weights=gain[ideal] / np.log2(2.0 + pos),
                       minlength=Gp)
    start = np.zeros(Gp, np.int32)
    start[:G] = group_ptr[:-1]
    count = np.zeros(Gp, np.int32)
    count[:G] = sizes
    shape = (n_blocks, gb)
    return TopkLayout(start.reshape(shape), count.reshape(shape),
                      grid.reshape(shape + (S,)),
                      np.maximum(idcg, 1e-10).astype(np.float32).reshape(shape),
                      slot.astype(np.int32))


class _LambdaRankBase(ObjFunction):
    def __init__(self, params):
        super().__init__(params)
        # reference defaults (src/common/ranking_utils.h LambdaRankParam):
        # pair_method=topk, num_pair = 32 (topk) / 1 (mean),
        # normalization=true, score_normalization=true
        self.pair_method = str(params.get("lambdarank_pair_method", "topk"))
        if self.pair_method not in ("topk", "mean"):
            raise ValueError(
                f"lambdarank_pair_method must be 'topk' or 'mean', got "
                f"{self.pair_method!r}")
        np_default = 32 if self.pair_method == "topk" else 1
        self.num_pair = int(params.get("lambdarank_num_pair_per_sample",
                                       np_default))
        self.group_norm = str(params.get("lambdarank_normalization",
                                         "1")).lower() in ("1", "true")
        if str(params.get("lambdarank_unbiased", "0")).lower() in ("1",
                                                                   "true"):
            # position-bias EM debiasing (lambdarank_obj.h t_plus/t_minus)
            # is not implemented; silently ignoring it would train a
            # different model than the user asked for
            raise NotImplementedError(
                "lambdarank_unbiased=True (position-bias debiasing) is not "
                "supported yet")
        self.score_norm = str(params.get("lambdarank_score_normalization",
                                         "1")).lower() in ("1", "true")
        # set by the learner via set_group_info
        self._group_ptr = self._gptr = self._topk = self._topk_labels = None

    def set_group_info(self, group_ptr: np.ndarray, labels=None) -> None:
        """The groups of the training matrix and, where the caller has them
        (the learner does), its labels: everything of a round's gradient
        that the margin does not move is built here, once."""
        self._group_ptr = np.asarray(group_ptr, np.int64)
        self._gptr = self._topk = self._topk_labels = None
        if self.pair_method == "mean":
            idx, mask, inv = make_group_layout(self._group_ptr)
            self._gidx = jnp.asarray(idx)
            self._gmask = jnp.asarray(mask)
            self._ginv = jnp.asarray(inv)
        elif _native_lambdarank_ok():
            self._gptr = jnp.asarray(self._group_ptr.astype(np.int32))
        elif labels is not None:
            self._bind_labels(labels)

    def _bind_labels(self, labels) -> None:
        """The top-k grid of these labels; the span's arguments are the
        layout's counters (docs/observability.md)."""
        with span("objective.group_layout") as sp:
            layout = make_topk_layout(self._group_ptr, np.asarray(labels),
                                      self.num_pair, self._use_ndcg_weight())
            self._topk = jax.tree.map(jnp.asarray, layout)
            self._topk_labels = labels
            slots = int(layout.gain.size)
            sp.args.update({
                "rank.groups": len(self._group_ptr) - 1,
                "rank.docs": int(self._group_ptr[-1]), "rank.slots": slots,
                "rank.pair_cells": slots * min(self.num_pair,
                                               layout.gain.shape[-1])})

    def default_metric(self):
        return "ndcg"

    def _use_ndcg_weight(self) -> bool:
        return True

    def get_gradient(self, preds, labels, weights, iteration: int = 0):
        if self._group_ptr is None:
            raise ValueError(f"{self.name} requires group/qid information")
        pred = preds[:, 0] if preds.ndim == 2 else preds
        flags = dict(k=self.num_pair, ndcg_weight=self._use_ndcg_weight(),
                     score_norm=self.score_norm, group_norm=self.group_norm)
        if self.pair_method == "topk":
            if self._gptr is not None:
                grad, hess = _lambda_gradients_topk_native(
                    pred, labels.astype(jnp.float32), self._gptr, **flags)
            else:
                if self._topk_labels is not labels:
                    self._bind_labels(labels)
                grad, hess = _lambda_gradients_topk(pred, self._topk, **flags)
        else:
            key = jax.random.PRNGKey(iteration)
            grad, hess = _lambda_gradients(
                pred,
                labels.astype(jnp.float32),
                self._gidx,
                self._gmask,
                self._ginv,
                key,
                self.num_pair,
                self._use_ndcg_weight(),
                group_norm=self.group_norm,
            )
        if weights is not None:
            # per-query weights broadcast over docs (reference: ltr weights are per group)
            grad = grad * weights if weights.shape == grad.shape else grad
            hess = hess * weights if weights.shape == hess.shape else hess
        return jnp.stack([grad, hess], axis=-1)[:, None, :].astype(jnp.float32)


def _native_lambdarank_ok() -> bool:
    """The native CSR-group top-k pair pass is the CPU backend's path — the
    padded (G, k, S) pair tensors below cost hundreds of MB of masked
    intermediates per round that the sequential kernel never materializes
    (~4x at MSLR shapes).  Same per-host agreement story as the other
    kernels (utils/native.py)."""
    if jax.default_backend() != "cpu":
        return False
    from ..utils import native

    return native.ffi_usable()


@functools.partial(jax.jit, static_argnames=("k", "ndcg_weight", "score_norm",
                                             "group_norm"))
def _lambda_gradients_topk_native(pred, y, gptr, *, k: int,
                                  ndcg_weight: bool, score_norm: bool,
                                  group_norm: bool):
    """FFI custom call into xtb_lambdarank_topk_impl — semantics mirror
    _lambda_gradients_topk (same sort order incl. stable ties, pair set,
    LambdaGrad weights, group normalization); gradients agree to f32
    tolerance (tests/test_native_parity.py pins it)."""
    from ..utils import native

    native.ensure_pool()
    R = pred.shape[0]
    shapes = (jax.ShapeDtypeStruct((R,), jnp.float32),
              jax.ShapeDtypeStruct((R,), jnp.float32))
    call = jax.ffi.ffi_call("xtb_lambdarank", shapes)
    return call(pred.astype(jnp.float32), y.astype(jnp.float32),
                gptr.astype(jnp.int32), k=np.int32(k),
                ndcg_weight=np.int32(ndcg_weight),
                score_norm=np.int32(score_norm),
                group_norm=np.int32(group_norm))


@functools.partial(jax.jit, static_argnames=("k", "ndcg_weight", "score_norm",
                                             "group_norm"))
def _lambda_gradients_topk(pred, layout: TopkLayout, *, k: int,
                           ndcg_weight: bool, score_norm: bool,
                           group_norm: bool):
    """Top-k LambdaMART gradients, the reference's DEFAULT pair method
    (lambdarank_obj.h MakePairs truncation branch): each of the top-k docs
    on the CURRENT model ranking pairs with every doc ranked below it, so
    the gradient concentrates exactly where ndcg@k moves.  Per-pair weights
    follow LambdaGrad (lambdarank_obj.h:91): |delta ndcg| / idcg, optional
    division by (|score diff| + 0.01) (lambdarank_score_normalization),
    hessian doubled; per-group log2(1+sum_lambda)/sum_lambda rescale
    (lambdarank_normalization, lambdarank_obj.cc:227).

    One program, every device operation under scope ``gradient``.  A round
    brings only the margin: a group's scores are one contiguous slice of it
    (rows are in group order), the gains on the grid and the ideal DCG come
    with ``layout``.  One stable sort carries the gains and the slots along
    with the scores, a second one on the slots brings the pair back to grid
    order: no ``take_along_axis``, and the only per-element gather is the
    grid's way back to row order.

    Memory: pairs form a (gb, k, S) tensor; groups are processed in blocks
    via lax.map so MSLR-scale G never materializes G*k*S at once.
    """
    with jax.named_scope("gradient"):
        return _topk_grid(pred, layout, k, ndcg_weight, score_norm, group_norm)


def _topk_grid(pred, layout, k, ndcg_weight, score_norm, group_norm):
    R = pred.shape[0]
    n_blocks, gb, S = layout.gain.shape
    kk = min(k, S)
    # a slice of S rows from any group's first row stays inside the array
    pred_ext = jnp.concatenate([pred.astype(jnp.float32),
                                jnp.zeros(S, jnp.float32)])
    irange = jnp.arange(kk, dtype=jnp.int32)
    jrange = jnp.arange(S, dtype=jnp.int32)
    # rank discounts by sorted position: rank = pos + 1 -> 1/log2(1 + rank);
    # constants of the program, made as the layout's ideal DCG is made
    disc_j = jnp.asarray((1.0 / np.log2(2.0 + np.arange(S))).astype(np.float32))
    disc_i = disc_j[:kk]

    def block(args):
        start, count, gain, idcg = args  # (gb,), (gb,), (gb, S), (gb,)
        s = jax.vmap(lambda at: jax.lax.dynamic_slice(pred_ext, (at,), (S,))
                     )(start)
        mask = jrange[None, :] < count[:, None]
        # stable and descending; padding sorts last, so the mask is its own
        # sorted form
        key_srt, gain_srt, order = jax.lax.sort(
            (jnp.where(mask, -s, jnp.inf), gain,
             jnp.broadcast_to(jrange, (gb, S))),
            dimension=1, is_stable=True, num_keys=1)
        s_srt = -key_srt

        si = s_srt[:, :kk][:, :, None]           # (gb, k, 1)
        sj = s_srt[:, None, :]                   # (gb, 1, S)
        gi = gain_srt[:, :kk][:, :, None]        # a gain orders as its label
        gj = gain_srt[:, None, :]
        valid = (mask[:, :kk][:, :, None] & mask[:, None, :]
                 & (jrange[None, None, :] > irange[None, :, None])
                 & (gi != gj))
        high_is_i = gi > gj
        s_high = jnp.where(high_is_i, si, sj)
        s_low = jnp.where(high_is_i, sj, si)
        sig = jax.nn.sigmoid(s_high - s_low)

        if ndcg_weight:
            delta = jnp.abs((gi - gj)
                            * (disc_i[None, :, None] - disc_j[None, None, :])
                            ) / idcg[:, None, None]
        else:
            delta = jnp.ones_like(sig)
        if score_norm:
            # LambdaGrad norm_by_diff: skip when all scores equal (first
            # iteration) — best == worst per group
            best = jnp.max(jnp.where(mask, s, -jnp.inf), axis=1)
            worst = jnp.min(jnp.where(mask, s, jnp.inf), axis=1)
            spread = (best != worst)[:, None, None]
            delta = jnp.where(spread,
                              delta / (jnp.abs(s_high - s_low) + 0.01),
                              delta)

        lam = jnp.where(valid, (sig - 1.0) * delta, 0.0)  # high doc's grad
        hss = jnp.where(valid,
                        jnp.maximum(sig * (1.0 - sig), 1e-16) * delta * 2.0,
                        0.0)
        # endpoint accumulation in sorted coordinates
        sgn_i = jnp.where(high_is_i, 1.0, -1.0)
        grad_i = jnp.sum(lam * sgn_i, axis=2)                 # (gb, k)
        grad_j = jnp.sum(lam * (-sgn_i), axis=1)              # (gb, S)
        grad_srt = grad_j.at[:, :kk].add(grad_i)
        hess_srt = jnp.sum(hss, axis=1).at[:, :kk].add(jnp.sum(hss, axis=2))

        if group_norm:
            # sum_lambda accumulates -2 * (high-doc gradient) per pair
            sum_lambda = jnp.sum(-2.0 * lam, axis=(1, 2))
            norm = jnp.where(sum_lambda > 0.0,
                             jnp.log2(1.0 + sum_lambda)
                             / jnp.maximum(sum_lambda, 1e-16), 1.0)
            grad_srt = grad_srt * norm[:, None]
            hess_srt = hess_srt * norm[:, None]

        _, grad_blk, hess_blk = jax.lax.sort(
            (order, grad_srt, hess_srt), dimension=1, num_keys=1)
        return grad_blk, hess_blk

    grad_g, hess_g = jax.lax.map(
        block, (layout.start, layout.count, layout.gain, layout.idcg))
    # rows back from the grid: each owns exactly one slot, so a gather; the
    # padded tail of the margin (R - len(slot) rows) stays zero
    tail = (0, R - layout.slot.shape[0])
    return (jnp.pad(grad_g.reshape(-1)[layout.slot], tail),
            jnp.pad(hess_g.reshape(-1)[layout.slot], tail))


@functools.partial(jax.jit, static_argnames=("num_pair", "ndcg_weight",
                                             "group_norm"))
def _lambda_gradients(pred, y, gidx, gmask, ginv, key, num_pair: int,
                      ndcg_weight: bool, group_norm: bool = True):
    R = pred.shape[0]
    G, S = gidx.shape
    s = pred[gidx]  # (G, S)
    rel = y[gidx] * gmask
    s = jnp.where(gmask, s, -jnp.inf)

    # rank of each doc by current score, descending (1-based)
    order = jnp.argsort(-s, axis=1)
    arange = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (G, S))
    inv = jnp.argsort(order, axis=1)  # inverse permutation
    ranks = jnp.take_along_axis(arange, inv, axis=1) + 1  # (G, S) 1-based

    gain = (2.0 ** rel - 1.0) * gmask
    disc = 1.0 / jnp.log2(1.0 + ranks.astype(jnp.float32))
    ideal = jnp.sort(gain, axis=1)[:, ::-1]
    idisc = 1.0 / jnp.log2(2.0 + jnp.arange(S, dtype=jnp.float32))
    idcg = jnp.maximum(jnp.sum(ideal * idisc[None, :], axis=1), 1e-10)  # (G,)

    grad_g = jnp.zeros((G, S), jnp.float32)
    hess_g = jnp.zeros((G, S), jnp.float32)
    sizes = jnp.sum(gmask, axis=1).astype(jnp.int32)  # (G,)

    for p in range(num_pair):
        key, sub = jax.random.split(key)
        # uniform partner within group (resample j==i harmless: zero lambda)
        j = jax.random.randint(sub, (G, S), 0, jnp.maximum(S, 1)) % jnp.maximum(
            sizes[:, None], 1
        )
        s_j = jnp.take_along_axis(s, j, axis=1)
        rel_j = jnp.take_along_axis(rel, j, axis=1)
        rank_j = jnp.take_along_axis(ranks, j, axis=1)
        better = rel > rel_j  # this doc is the positive of the pair
        worse = rel < rel_j
        sig = jax.nn.sigmoid(-(s - s_j))  # for better pairs
        sig_w = jax.nn.sigmoid(-(s_j - s))
        if ndcg_weight:
            dg = jnp.abs(
                (2.0 ** rel - 2.0 ** rel_j)
                * (1.0 / jnp.log2(1.0 + ranks.astype(jnp.float32))
                   - 1.0 / jnp.log2(1.0 + rank_j.astype(jnp.float32)))
            ) / idcg[:, None]
        else:
            dg = jnp.ones((G, S), jnp.float32)
        lam_b = -sig * dg
        lam_w = sig_w * dg
        # hessian doubled like the reference LambdaGrad (lambdarank_obj.h)
        h_b = jnp.maximum(sig * (1 - sig) * dg, 1e-16) * 2.0
        h_w = jnp.maximum(sig_w * (1 - sig_w) * dg, 1e-16) * 2.0
        grad_g = grad_g + jnp.where(better & gmask, lam_b, 0.0) + jnp.where(
            worse & gmask, lam_w, 0.0
        )
        hess_g = hess_g + jnp.where((better | worse) & gmask, jnp.where(better, h_b, h_w), 0.0)

    if group_norm:
        # mean-method normalization: 1 / n_pairs (lambdarank_obj.cc:230)
        grad_g = grad_g / float(num_pair)
        hess_g = hess_g / float(num_pair)
    # rows back from the padded grid via the precomputed inverse map — a pure
    # gather (each row owns exactly one (g, s) slot); no scatter on TPU.
    # ginv covers the real rows; the padded tail (R_pad - R_real) stays zero.
    grad = jnp.pad(grad_g.reshape(-1)[ginv], (0, R - ginv.shape[0]))
    hess = jnp.pad(hess_g.reshape(-1)[ginv], (0, R - ginv.shape[0]))
    return grad, hess


@register_objective("rank:ndcg")
class LambdaRankNDCG(_LambdaRankBase):
    pass


@register_objective("rank:pairwise")
class LambdaRankPairwise(_LambdaRankBase):
    def _use_ndcg_weight(self):
        return False

    def default_metric(self):
        return "map"


@register_objective("rank:map")
class LambdaRankMAP(_LambdaRankBase):
    def _use_ndcg_weight(self):
        return False

    def default_metric(self):
        return "map"
