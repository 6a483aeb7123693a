"""Learning-to-rank objectives: LambdaMART (reference:
src/objective/lambdarank_obj.cc / .cu, 675+ LoC).

The reference samples ``lambdarank_num_pair_per_sample`` pairs per document
within each query group (pair_method="mean", the default) or uses top-k pairs.
Here groups are padded to a (G, S) doc tensor (S = max group size rounded up)
so ranks, pair sampling, and lambda accumulation are fixed-shape vectorized
ops; the per-group IDCG and rank discounts follow LambdaMARTCalcDeltaNDCG.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import ObjFunction, register_objective


def make_group_layout(group_ptr: np.ndarray):
    """Host: CSR group_ptr -> padded (G, S) row-index matrix + mask + the
    inverse map row -> flat (g*S + s) slot (rows appear exactly once, so the
    padded-grid gradients come back to row order with a GATHER, no scatter —
    TPU scatter-adds are serialized)."""
    sizes = np.diff(group_ptr)
    G = len(sizes)
    S = int(sizes.max()) if G else 1
    idx = np.zeros((G, S), dtype=np.int32)
    mask = np.zeros((G, S), dtype=bool)
    inv = np.zeros(int(group_ptr[-1]), dtype=np.int32)
    for g in range(G):
        n = sizes[g]
        rows = np.arange(group_ptr[g], group_ptr[g + 1])
        idx[g, :n] = rows
        mask[g, :n] = True
        inv[rows] = g * S + np.arange(n)
    return idx, mask, inv


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
        self._layout = None  # set by learner via set_group_info

    def set_group_info(self, group_ptr: np.ndarray) -> None:
        idx, mask, inv = make_group_layout(group_ptr)
        self._gidx = jnp.asarray(idx)
        self._gmask = jnp.asarray(mask)
        self._ginv = jnp.asarray(inv)
        self._gptr = jnp.asarray(np.asarray(group_ptr, np.int32))

    def default_metric(self):
        return "ndcg"

    def _use_ndcg_weight(self) -> bool:
        return True

    def get_gradient(self, preds, labels, weights, iteration: int = 0):
        if self._layout is None and not hasattr(self, "_gidx"):
            raise ValueError(f"{self.name} requires group/qid information")
        pred = preds[:, 0] if preds.ndim == 2 else preds
        if self.pair_method == "topk":
            if _native_lambdarank_ok():
                grad, hess = _lambda_gradients_topk_native(
                    pred, labels.astype(jnp.float32), self._gptr,
                    k=self.num_pair, ndcg_weight=self._use_ndcg_weight(),
                    score_norm=self.score_norm,
                    group_norm=self.group_norm)
            else:
                grad, hess = _lambda_gradients_topk(
                    pred, labels.astype(jnp.float32), self._gidx,
                    self._gmask, self._ginv, k=self.num_pair,
                    ndcg_weight=self._use_ndcg_weight(),
                    score_norm=self.score_norm, group_norm=self.group_norm)
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


import functools


def _native_lambdarank_ok() -> bool:
    """CPU gate for the native CSR-group top-k pair pass — the padded
    (G, k, S) pair tensors below cost hundreds of MB of masked
    intermediates per round that the sequential kernel never materializes
    (~4x at MSLR shapes).  Same per-host agreement story as the other
    kernels (utils/native.py)."""
    import os

    if os.environ.get("XTB_NO_NATIVE_LAMBDARANK", ""):
        return False
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
    import numpy as np

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
def _lambda_gradients_topk(pred, y, gidx, gmask, ginv, *, k: int,
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

    Memory: pairs form a (g_block, k, S) tensor; groups are processed in
    blocks via lax.map so MSLR-scale G never materializes G*k*S at once.
    """
    R = pred.shape[0]
    G, S = gidx.shape
    kk = min(k, S)
    # block size: ~2^22 pair cells per block keeps peak memory ~100MB
    gb = max(1, min(G, (1 << 22) // max(kk * S, 1)))
    n_blocks = (G + gb - 1) // gb
    Gp = n_blocks * gb
    pad_g = Gp - G

    s_all = jnp.where(gmask, pred[gidx], -jnp.inf)
    rel_all = y[gidx] * gmask
    if pad_g:
        s_all = jnp.concatenate(
            [s_all, jnp.full((pad_g, S), -jnp.inf, s_all.dtype)])
        rel_all = jnp.concatenate([rel_all, jnp.zeros((pad_g, S))])
        mask_all = jnp.concatenate([gmask, jnp.zeros((pad_g, S), bool)])
    else:
        mask_all = gmask

    irange = jnp.arange(kk, dtype=jnp.int32)
    jrange = jnp.arange(S, dtype=jnp.int32)
    # rank discounts by sorted position: rank = pos + 1 -> 1/log2(1 + rank)
    disc_i = 1.0 / jnp.log2(2.0 + irange.astype(jnp.float32))
    disc_j = 1.0 / jnp.log2(2.0 + jrange.astype(jnp.float32))

    def block(args):
        s, rel, mask = args  # (gb, S)
        order = jnp.argsort(-s, axis=1)  # stable; -inf padding sorts last
        inv_order = jnp.argsort(order, axis=1)
        s_srt = jnp.take_along_axis(s, order, axis=1)
        rel_srt = jnp.take_along_axis(rel, order, axis=1)
        m_srt = jnp.take_along_axis(mask, order, axis=1)
        cnt = jnp.sum(mask, axis=1).astype(jnp.int32)  # (gb,)

        gain_srt = (2.0 ** rel_srt - 1.0) * m_srt
        ideal = jnp.sort(gain_srt, axis=1)[:, ::-1]
        idcg = jnp.maximum(jnp.sum(ideal * disc_j[None, :], axis=1), 1e-10)

        si = s_srt[:, :kk][:, :, None]           # (gb, k, 1)
        sj = s_srt[:, None, :]                   # (gb, 1, S)
        reli = rel_srt[:, :kk][:, :, None]
        relj = rel_srt[:, None, :]
        valid = (m_srt[:, :kk][:, :, None] & m_srt[:, None, :]
                 & (jrange[None, None, :] > irange[None, :, None])
                 & (reli != relj))
        high_is_i = reli > relj
        s_high = jnp.where(high_is_i, si, sj)
        s_low = jnp.where(high_is_i, sj, si)
        sig = jax.nn.sigmoid(s_high - s_low)

        if ndcg_weight:
            gi = gain_srt[:, :kk][:, :, None]
            gj = gain_srt[:, None, :]
            delta = jnp.abs((gi - gj)
                            * (disc_i[None, :, None] - disc_j[None, None, :])
                            ) / idcg[:, None, None]
        else:
            delta = jnp.ones_like(sig)
        if score_norm:
            # LambdaGrad norm_by_diff: skip when all scores equal (first
            # iteration) — best == worst per group
            best = s_srt[:, 0]
            worst = jnp.take_along_axis(
                s_srt, jnp.maximum(cnt - 1, 0)[:, None], axis=1)[:, 0]
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

        grad_blk = jnp.take_along_axis(grad_srt, inv_order, axis=1)
        hess_blk = jnp.take_along_axis(hess_srt, inv_order, axis=1)
        return grad_blk, hess_blk

    s_b = s_all.reshape(n_blocks, gb, S)
    rel_b = rel_all.reshape(n_blocks, gb, S)
    m_b = mask_all.reshape(n_blocks, gb, S)
    grad_g, hess_g = jax.lax.map(block, (s_b, rel_b, m_b))
    grad_g = grad_g.reshape(Gp, S)[:G].astype(jnp.float32)
    hess_g = hess_g.reshape(Gp, S)[:G].astype(jnp.float32)
    grad = jnp.pad(grad_g.reshape(-1)[ginv], (0, R - ginv.shape[0]))
    hess = jnp.pad(hess_g.reshape(-1)[ginv], (0, R - ginv.shape[0]))
    return grad, hess


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
