"""The plain reference of top-k LambdaMART boosting (``rank:ndcg`` with
``lambdarank_pair_method=topk``), in numpy and float64, a loop over groups.

It imports nothing of the program; the tree walk, the exact histograms of a
followed tree, the sketch's and the binning's checks and the gaps are
``benchmarks/reference.py``'s.  The gradient pair is written from the
published description (dmlc/xgboost ``doc/tutorials/learning_to_rank.rst``,
``lambdarank_obj.h``): a group's documents in a stable descending sort of
their scores; for each ``i`` among the top ``k`` and each ``j`` ranked below
it with another label,

    delta = |(2^l_i - 2^l_j) (1/log2(2+i) - 1/log2(2+j))| / IDCG,
            divided by |s_hi - s_lo| + 0.01 unless the group's scores are
            all equal
    p = sigmoid(s_hi - s_lo)      (hi: the document with the higher label)
    lambda = (p - 1) delta        to the higher-labelled document, -lambda
                                  to the lower
    h = 2 max(p (1 - p), 1e-16) delta   to both

and the group's pair rescaled by ``log2(1 + S) / S`` with ``S = -2 sum of
lambda``.

**The margin a ranker ranks on.**  The configuration trains in float32: a
document's margin is the float32 sum, in tree order, of float32 leaf values.
Two documents whose exact sums differ by less than a float32 rounding can
tie or swap there, and a swap changes the pair set, which is no fault of the
program's.  So the gradient is computed from that float32 sum (numpy's own
float32 addition); the same gradient from the float64 walk is read beside it
and logged as ``*_rank64``: the size of the hazard.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks import reference

TAIL_KEPT = 64  # the planted fault: a group's documents past this, left out


def lambdarank_gpair(score: np.ndarray, y: np.ndarray, group_ptr: np.ndarray,
                     k: int = 32, normalize: bool = True,
                     keep: int = None):
    """(g, h) in float64.  ``normalize=False`` and ``keep`` (only a group's
    first ``keep`` documents take part) are the planted faults."""
    score = np.asarray(score, np.float64)
    g, h = np.zeros(len(score)), np.zeros(len(score))
    for lo, hi in zip(group_ptr[:-1], group_ptr[1:]):
        if keep is not None:
            hi = min(hi, lo + keep)
        n = hi - lo
        if n < 2:
            continue
        s, lab = score[lo:hi], y[lo:hi]
        order = np.argsort(-s, kind="stable")
        s, lab = s[order], lab[order]
        top = min(k, n)
        i, j = np.arange(top)[:, None], np.arange(n)[None, :]
        valid = (j > i) & (lab[:top, None] != lab[None, :])
        if not valid.any():
            continue
        gain = np.exp2(lab)
        disc = 1.0 / np.log2(2.0 + np.arange(n))
        idcg = float(np.sum(np.sort(gain - 1.0)[::-1] * disc))
        i_high = lab[:top, None] > lab[None, :]
        diff = np.where(i_high, 1.0, -1.0) * (s[:top, None] - s[None, :])
        delta = np.abs((gain[:top, None] - gain[None, :])
                       * (disc[:top, None] - disc[None, :])) / idcg
        if s[0] != s[-1]:
            delta = delta / (np.abs(diff) + 0.01)
        p = 1.0 / (1.0 + np.exp(-diff))
        lam = np.where(valid, (p - 1.0) * delta, 0.0)
        hes = np.where(valid, 2.0 * np.maximum(p * (1.0 - p), 1e-16) * delta,
                       0.0)
        to_i = np.where(i_high, lam, -lam)
        gs = (-to_i).sum(axis=0)
        gs[:top] += to_i.sum(axis=1)
        hs = hes.sum(axis=0)
        hs[:top] += hes.sum(axis=1)
        total = -2.0 * lam.sum()
        if normalize and total > 0.0:
            scale = np.log2(1.0 + total) / total
            gs, hs = gs * scale, hs * scale
        g[lo + order] = gs
        h[lo + order] = hs
    return g, h


def ndcg_at(score: np.ndarray, y: np.ndarray, group_ptr: np.ndarray,
           at: int = 10) -> float:
    """Mean NDCG@``at`` over the groups that have a relevant document."""
    disc = 1.0 / np.log2(2.0 + np.arange(at))
    got = []
    for lo, hi in zip(group_ptr[:-1], group_ptr[1:]):
        gain = np.exp2(y[lo:hi]) - 1.0
        ideal = np.sort(gain)[::-1][:at]
        if ideal[:1].sum() <= 0:
            continue
        mine = gain[np.argsort(-score[lo:hi], kind="stable")][:at]
        got.append(np.sum(mine * disc[:len(mine)])
                   / np.sum(ideal * disc[:len(ideal)]))
    return float(np.mean(got)) if got else float("nan")


def compare_ranking(X, y, group_ptr, model: dict, cut_ptrs, cut_values,
                    page_bins, sample_idx, sample_bins, sample_margin, *,
                    max_bin: int, max_depth: int, eta: float, lam: float,
                    mcw: float, base_margin: float, k: int, follow: int,
                    split_tree: int, continuous: np.ndarray,
                    lower_precision: bool = False, faults: bool = False,
                    log=lambda s: None) -> Dict[str, float]:
    """Every number the comparison reads, under ``reference.py``'s names and
    by its measures; ``continuous`` lists the columns the sketch is judged
    on (a column of twelve distinct values has no quantiles to miss).  With
    ``lower_precision`` also, under ``*_low``, what the same trees would
    read with the gradient pair in bfloat16: the control.  With ``faults``
    what the sums would read from a gradient whose groups end after
    ``TAIL_KEPT`` documents (``*_tail``), without the group normalisation
    (``*_nonorm``), and from the margin of one round before (``*_stale``;
    for the margin, the last tree's update left out), what the split scan
    would read with the root's cut moved (``split_gap_moved``), and what the
    sketch would read with every second cut left out
    (``bin_mass_gap_half``): the faults, planted in the reference's place."""
    trees = reference.model_trees(model)
    out: Dict[str, float] = {}
    ptrs_c = np.concatenate([[0], np.cumsum(np.diff(cut_ptrs)[continuous])])
    vals_c = np.concatenate([cut_values[cut_ptrs[f]:cut_ptrs[f + 1]]
                             for f in continuous])
    Xc = np.ascontiguousarray(X[:, continuous])
    out["bin_mass_gap"] = reference.bin_mass_gap(Xc, ptrs_c, vals_c, max_bin)
    Xs = X[sample_idx]
    out["bin_mismatch"] = float(np.mean(
        reference.bin_rows(Xs, cut_ptrs, cut_values) != sample_bins))
    if faults:
        out["bin_mass_gap_half"] = reference.bin_mass_gap(
            Xc, *reference.every_second_cut(ptrs_c, vals_c), max_bin)
    del Xc
    log(f"sketch ({len(continuous)} continuous columns) and binning (all "
        f"{X.shape[1]}; fewest bins {int(np.diff(cut_ptrs).min())}): "
        f"bin_mass_gap {out['bin_mass_gap']:.3e}, bin_mismatch "
        f"{out['bin_mismatch']:.3e} on {Xs.size} sampled values")

    y64 = y.astype(np.float64)
    margin32 = np.full(len(X), base_margin, np.float32)
    margin64 = margin32.astype(np.float64)
    walker = reference.Walker(X)
    names = ("hess_gap", "grad_gap", "leaf_gap")
    out.update({key: 0.0 for key in names})
    check = reference.SplitCheck(walker, page_bins, cut_ptrs, cut_values, lam,
                                 mcw, max_depth)
    g = h = None
    for t, tree in enumerate(trees[:max(follow, split_tree + 1)]):
        stale = (g, h)
        g, h = lambdarank_gpair(margin32, y64, group_ptr, k)
        leaf = walker.leaves(tree)
        if t < follow:
            G, H, A = reference.node_sums(tree, leaf, g, h)
            gaps = reference.sums_gaps(tree, G, H, A, lam, eta)
            for key, v in gaps.items():
                out[key] = max(out[key], v)
            log(f"tree {t}: hess_gap {gaps['hess_gap']:.3e}, grad_gap "
                f"{gaps['grad_gap']:.3e}, leaf_gap {gaps['leaf_gap']:.3e} "
                f"over {tree.n_nodes} nodes")

            def read(pair) -> dict:
                Gx, Hx, _ = reference.node_sums(tree, leaf, *pair)
                return reference.sums_gaps(tree, G, H, A, lam, eta, G_got=Gx,
                                           H_got=Hx)

            def keep(tag: str, got: dict, worst) -> None:
                for key in names[:2]:
                    out[key + tag] = worst(out.get(key + tag, got[key]),
                                           got[key])

            off = int(np.sum(margin32 != margin64.astype(np.float32)))
            got = read(lambdarank_gpair(margin64, y64, group_ptr, k))
            keep("_rank64", got, max)
            log(f"tree {t} ranked on the float64 walk ({off} margins differ "
                f"from the float32 sum): hess_gap {got['hess_gap']:.3e}, "
                f"grad_gap {got['grad_gap']:.3e}")
            if lower_precision:
                got = read((reference.to_bfloat16(g), reference.to_bfloat16(h)))
                keep("_low", got, max)
                log(f"tree {t} with the pair in bfloat16: hess_gap "
                    f"{got['hess_gap']:.3e}, grad_gap {got['grad_gap']:.3e}")
            if faults:  # a fault's reading is its smallest over the trees
                keep("_tail", read(lambdarank_gpair(
                    margin32, y64, group_ptr, k, keep=TAIL_KEPT)), min)
                keep("_nonorm", read(lambdarank_gpair(
                    margin32, y64, group_ptr, k, normalize=False)), min)
                if t > 0:
                    keep("_stale", read(stale), min)
        if t == split_tree:
            lowp = ((reference.to_bfloat16(g), reference.to_bfloat16(h))
                    if lower_precision else (None, None))
            got = check.run(tree, g, h, *lowp)
            judged, widest = got.pop("nodes_judged"), got.pop("widest_gap")
            out.update(got)
            log(f"tree {t}: split_gap {got['split_gap']:.3e} of the gain on "
                f"offer over {judged} nodes (widest gap of one node "
                f"{widest:.3e})"
                + (f"; splits chosen in bfloat16: {got['split_gap_low']:.3e}"
                   if lower_precision else ""))
        margin32 += tree.cond[leaf]
        margin64 += tree.cond[leaf].astype(np.float64)

    ref_margin = reference.walk(trees, Xs, base_margin)
    scale = np.maximum(np.abs(ref_margin), np.median(np.abs(ref_margin)))
    out["margin_gap"] = float(np.max(
        np.abs(sample_margin.astype(np.float64) - ref_margin) / scale))
    if faults:
        short = reference.walk(trees[:-1], Xs, base_margin)
        out["margin_gap_stale"] = float(np.max(np.abs(short - ref_margin)
                                               / scale))
    log(f"margin after {len(trees)} trees on {len(Xs)} sampled rows: "
        f"margin_gap {out['margin_gap']:.3e}")
    return out
