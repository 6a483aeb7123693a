"""The plain reference of hist gradient boosting, in numpy and float64.

It imports nothing of the program.  What the timed call produced (the
trees of the saved JSON model, the cut points, the binned page, the margin)
comes in as plain arrays and is held against what this file computes from
the raw rows and labels alone:

  * ``bin_mass_gap``   the sketch: the fullest bin's share of all rows, from
                       an exact sort of each column, over the ideal 1/max_bin;
  * ``bin_mismatch``   binning: sampled rows whose bin is not the count of
                       cuts <= the value;
  * ``hess_gap`` and ``grad_gap``  gradient, level histogram and routing:
                       each node's hessian and gradient sums of the first
                       trees against exact float64 sums over the rows that the
                       raw thresholds send there, the margin before each tree
                       being this file's own walk of the trees before it;
  * ``leaf_gap``       a leaf's value against eta times its node's weight;
  * ``split_gap``      the split scan: the share of the gain on offer that
                       the chosen splits give away against the best cuts;
  * ``margin_gap``     margin update and leaf routing over every round: the
                       margin the booster ended with against a float64 walk
                       of all its trees.

``lower_precision`` computes the same sums with the gradient pair rounded
to bfloat16, the nearest precision below the float32 the configurations
state: it is the control that the limits are set against.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

MCW_ROOM = 1e-3  # a split counts as a candidate only clear of min_child_weight
# a float32 gain is a difference of terms G^2/H whose sums are good to some
# 5e-6 of the root's (hess_gap, grad_gap), ten times that and more of the
# difference: two cuts whose exact gains lie within this share of the best
# are a tie, and choosing either gives nothing away
GAIN_TIE = 1e-4
MOVED_BINS = 16  # the planted fault: the root's cut, this many bins aside


# --------------------------------------------------------------- the model
class Tree:
    """One tree of the saved JSON model as plain arrays (creation order:
    a child's id is above its parent's)."""

    def __init__(self, tree: dict) -> None:
        self.left = np.asarray(tree["left_children"], np.int64)
        self.right = np.asarray(tree["right_children"], np.int64)
        self.feat = np.asarray(tree["split_indices"], np.int64)
        self.cond = np.asarray(tree["split_conditions"], np.float32)
        self.weight = np.asarray(tree["base_weights"], np.float64)
        self.hess = np.asarray(tree["sum_hessian"], np.float64)
        self.inner = self.left != -1
        self.depth = np.zeros(len(self.left), np.int64)
        self.parent = np.zeros(len(self.left), np.int64)
        for n in np.flatnonzero(self.inner):
            for child in (self.left[n], self.right[n]):
                self.depth[child] = self.depth[n] + 1
                self.parent[child] = n

    @property
    def n_nodes(self) -> int:
        return len(self.left)

    def finite(self) -> bool:
        return bool(np.isfinite(self.cond).all()
                    and np.isfinite(self.weight).all()
                    and np.isfinite(self.hess).all())


def model_trees(model: dict) -> List[Tree]:
    return [Tree(t) for t in
            model["learner"]["gradient_booster"]["model"]["trees"]]


class Walker:
    """Sends all rows of ``X`` down a tree, a level at a time.  Every
    row-long temporary is a buffer made once and written in place: at 10.5M
    rows a fresh 84 MB array a level costs more in first-touched pages than
    the arithmetic does."""

    def __init__(self, X: np.ndarray) -> None:
        R, F = X.shape
        self.flat = X.reshape(-1)
        self.base = np.arange(R, dtype=np.int64) * F
        self.idx = np.empty(R, np.int64)
        self.kid = np.empty(R, np.int64)
        self.other = np.empty(R, np.int64)
        self.v = np.empty(R, np.float32)
        self.c = np.empty(R, np.float32)
        self.mask = np.empty(R, bool)

    def step(self, tree: Tree, node: np.ndarray) -> None:
        """``node`` (int64, in place) one level down: ``value <
        split_condition`` goes left; the data has no missing values."""
        np.take(tree.feat, node, out=self.idx, mode="clip")
        self.idx += self.base
        np.take(self.flat, self.idx, out=self.v, mode="clip")
        np.take(tree.cond, node, out=self.c, mode="clip")
        np.take(tree.left, node, out=self.kid, mode="clip")
        np.take(tree.right, node, out=self.other, mode="clip")
        np.less(self.v, self.c, out=self.mask)
        np.copyto(self.other, self.kid, where=self.mask)  # the child taken
        np.greater_equal(self.kid, 0, out=self.mask)  # -1: a leaf stays
        np.copyto(node, self.other, where=self.mask)

    def leaves(self, tree: Tree) -> np.ndarray:
        node = np.zeros(len(self.base), np.int64)
        for _ in range(int(tree.depth.max())):
            self.step(tree, node)
        return node


def walk(trees: List[Tree], X: np.ndarray, base_margin: float) -> np.ndarray:
    """Float64 margin of ``X`` under ``trees`` (leaf values as the model
    holds them: they are the program's answers, judged by ``leaf_gap``)."""
    walker = Walker(X)
    margin = np.full(len(X), base_margin, np.float64)
    for t in trees:
        margin += t.cond[walker.leaves(t)]
    return margin


# ------------------------------------------------------------ the objective
def logistic_gpair(margin: np.ndarray, y: np.ndarray, g=None, h=None):
    """g = p - y, h = max(p (1 - p), 1e-16), p the sigmoid of the margin;
    written into ``g`` and ``h`` where given."""
    g = np.empty_like(margin) if g is None else g
    h = np.empty_like(margin) if h is None else h
    np.negative(margin, out=g)
    np.exp(g, out=g)
    g += 1.0
    np.reciprocal(g, out=g)  # p
    np.subtract(1.0, g, out=h)
    h *= g
    np.maximum(h, 1e-16, out=h)
    g -= y
    return g, h


def to_bfloat16(v: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), back in float64."""
    bits = v.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def auc(score: np.ndarray, y: np.ndarray) -> float:
    order = np.argsort(score, kind="stable")
    ranks = np.empty(len(score), np.float64)
    ranks[order] = np.arange(1, len(score) + 1)
    pos = y > 0.5
    n1, n0 = int(pos.sum()), int((~pos).sum())
    if n1 == 0 or n0 == 0:
        return float("nan")
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


# ---------------------------------------------------- sketch and binning
def bin_mass_gap(X: np.ndarray, cut_ptrs: np.ndarray, cut_values: np.ndarray,
                 max_bin: int) -> float:
    """The fullest bin's share of the rows times ``max_bin``, less 1: nought
    for exact quantiles of untied values, 1 for a sketch of half the bins."""
    R = len(X)

    def fullest(f: int) -> float:
        col = np.sort(X[:, f])
        cuts = cut_values[cut_ptrs[f]:cut_ptrs[f + 1]]
        edges = np.searchsorted(col, cuts[:-1], side="left")
        mass = np.diff(np.concatenate([[0], edges, [R]]))
        return float(mass.max()) / R

    # a sort releases the interpreter lock: a few threads, a column each
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        worst = max(pool.map(fullest, range(X.shape[1])))
    return worst * max_bin - 1.0


def every_second_cut(cut_ptrs: np.ndarray, cut_values: np.ndarray):
    """The cuts of a sketch of half the bins: every second one of each
    feature, its last (the open upper bound) always."""
    kept = [np.r_[np.arange(cut_ptrs[f] + 1, cut_ptrs[f + 1] - 1, 2),
                  cut_ptrs[f + 1] - 1] for f in range(len(cut_ptrs) - 1)]
    ptrs = np.concatenate([[0], np.cumsum([len(k) for k in kept])])
    return ptrs, cut_values[np.concatenate(kept)]


def bin_rows(X: np.ndarray, cut_ptrs: np.ndarray,
             cut_values: np.ndarray) -> np.ndarray:
    """bin = count of cuts <= value, values past the last cut in the top bin."""
    out = np.empty(X.shape, np.int64)
    for f in range(X.shape[1]):
        cuts = cut_values[cut_ptrs[f]:cut_ptrs[f + 1]]
        out[:, f] = np.minimum(np.searchsorted(cuts, X[:, f], side="right"),
                               len(cuts) - 1)
    return out


# ------------------------------------------------- node sums of one tree
def node_sums(tree: Tree, leaf: np.ndarray, g: np.ndarray, h: np.ndarray):
    """(G, H, A) of every node, A the sum of |g|: exact sums at the leaves,
    children added up."""
    sums = [np.bincount(leaf, weights=v, minlength=tree.n_nodes)
            for v in (g, h, np.abs(g))]
    for n in range(tree.n_nodes - 1, -1, -1):
        if tree.inner[n]:
            for s in sums:
                s[n] = s[tree.left[n]] + s[tree.right[n]]
    return sums


def sums_gaps(tree: Tree, G, H, A, lam: float, eta: float,
              G_got=None, H_got=None) -> dict:
    """Worst node of a tree against the exact sums.  A node's sums come out
    of a histogram that is a prefix of its parent's or parent minus sibling,
    the parent's likewise, up to the root: what a float32 sum can keep of a
    deep node is set by the magnitudes that went into the root's.  So each
    gap is measured against the root's H and the root's sum of |g|.  What is
    judged defaults to what the tree records (G from its weight:
    w = -G / (H + lambda)); a control passes sums of its own."""
    if H_got is None:
        H_got = tree.hess
        G_got = -tree.weight * (tree.hess + lam)
    hess_gap = np.abs(H_got - H) / H[0]
    grad_gap = np.abs(G_got - G) / A[0]
    lf = ~tree.inner
    w = np.abs(tree.weight)
    leaf_gap = (np.abs(tree.cond[lf].astype(np.float64) - eta * tree.weight[lf])
                / (eta * np.maximum(w[lf], np.median(w))))
    return {"hess_gap": float(hess_gap.max()),
            "grad_gap": float(grad_gap.max()),
            "leaf_gap": float(leaf_gap.max())}


# ------------------------------------------- level histograms of one tree
def level_hists(bins_fr: np.ndarray, node: np.ndarray, wanted: np.ndarray,
                g: np.ndarray, h: np.ndarray, n_bin: int) -> np.ndarray:
    """Exact histograms ``(len(wanted), F, n_bin, 2)`` of the nodes
    ``wanted`` over the rows that sit on them.  ``bins_fr`` is (F, R)."""
    slot = np.full(int(node.max()) + 2, -1, np.int64)
    slot[wanted] = np.arange(len(wanted))
    base = slot[node]
    everything = bool((base >= 0).all())
    if everything:
        rows, gr, hr = slice(None), g, h
    else:
        rows = np.flatnonzero(base >= 0)
        base, gr, hr = base[rows], g[rows], h[rows]
    base *= n_bin
    out = np.zeros((len(wanted), bins_fr.shape[0], n_bin, 2))
    flat = np.empty(len(base), np.int64)
    for f in range(bins_fr.shape[0]):
        np.add(base, bins_fr[f][rows], out=flat)
        n = len(wanted) * n_bin
        out[:, f, :, 0] = np.bincount(flat, weights=gr, minlength=n
                                      ).reshape(len(wanted), n_bin)
        out[:, f, :, 1] = np.bincount(flat, weights=hr, minlength=n
                                      ).reshape(len(wanted), n_bin)
    return out


def split_gains(hist: np.ndarray, n_bins: np.ndarray, lam: float,
                mcw: float) -> tuple:
    """Gain of every (feature, bin) split of one node's histogram
    ``(F, B, 2)`` and whether it is a candidate clear of min_child_weight."""
    GL = np.cumsum(hist[:, :, 0], axis=1)
    HL = np.cumsum(hist[:, :, 1], axis=1)
    G, H = GL[0, -1], HL[0, -1]
    GR, HR = G - GL, H - HL
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam) - G ** 2 / (H + lam)
    b = np.arange(hist.shape[1])[None, :]
    ok = ((b < (n_bins[:, None] - 1)) & (HL >= mcw * (1 + MCW_ROOM))
          & (HR >= mcw * (1 + MCW_ROOM)))
    return np.where(np.isfinite(gain), gain, -np.inf), ok


class SplitCheck:
    """Walks one tree level by level over all rows, building the exact
    histogram of every node above the last level (the smaller child of
    each sibling pair from its rows, its sibling as parent minus child) and
    reading how far the chosen split's gain lies below the best."""

    def __init__(self, walker, bins_fr, cut_ptrs, cut_values, lam, mcw,
                 max_depth):
        self.walker, self.bins = walker, bins_fr
        self.ptrs, self.cuts = cut_ptrs, cut_values
        self.n_bins = np.diff(cut_ptrs).astype(np.int64)
        self.n_bin = int(self.n_bins.max())
        self.lam, self.mcw, self.max_depth = lam, mcw, max_depth

    def chosen_bin(self, tree: Tree, n: int) -> int:
        f = int(tree.feat[n])
        seg = self.cuts[self.ptrs[f]:self.ptrs[f + 1]]
        b = int(np.searchsorted(seg, tree.cond[n], side="left"))
        return b if b < len(seg) and seg[b] == tree.cond[n] else -1

    def run(self, tree: Tree, g, h, g_low=None, h_low=None) -> dict:
        """``split_gap`` of the tree as it stands: the share of the gain on
        offer, summed over its nodes, that the chosen splits gave away
        beyond a tie (``GAIN_TIE`` of the node's best; a node's own widest
        gap swings with float32 ties in small nodes, and is printed beside
        it).  With ``g_low, h_low`` (the pair in a lower
        precision) also ``split_gap_low``: the same share for the splits
        that the lower-precision histograms put first."""
        node = np.zeros(len(self.walker.base), np.int64)
        hists = {0: None}
        worst, n_judged, moved = 0.0, 0, None  # worst: before the tie room
        lost = lost_low = avail = 0.0
        for d in range(self.max_depth):
            here = np.flatnonzero(tree.depth == d)
            if not len(here):
                break
            count = np.bincount(node, minlength=tree.n_nodes)
            build, derive = [], []
            for n in here:
                if d == 0:
                    build.append(n)
                    continue
                parent = int(tree.parent[n])
                sib = int(tree.left[parent] + tree.right[parent] - n)
                if (count[n], n) <= (count[sib], sib):
                    build.append(n)
                else:
                    derive.append((n, parent, sib))
            build = np.asarray(build, np.int64)
            exact = level_hists(self.bins, node, build, g, h, self.n_bin)
            new = {int(n): exact[i] for i, n in enumerate(build)}
            low = None
            if g_low is not None:
                lo = level_hists(self.bins, node, build, g_low, h_low,
                                 self.n_bin)
                low = {int(n): lo[i] for i, n in enumerate(build)}
            for n, parent, sib in derive:
                new[int(n)] = hists[parent][0] - new[sib]
                if low is not None:
                    low[int(n)] = hists[parent][1] - low[sib]
            for n in here:
                n = int(n)
                gain, ok = split_gains(new[n], self.n_bins, self.lam, self.mcw)
                best = float(np.max(np.where(ok, gain, -np.inf)))
                if tree.inner[n]:
                    b = self.chosen_bin(tree, n)
                    got = gain[int(tree.feat[n]), b] if b >= 0 else -np.inf
                    if best > 0:
                        gap = (best - got) / best
                    else:  # nothing on offer: any cut that exists will do
                        gap = 0.0 if got >= best else 1.0
                elif best > 1e-3:
                    gap = 1.0  # a leaf above the last level that could split
                else:
                    gap = 0.0
                if n == 0 and tree.inner[0] and b >= 0:
                    near = [gain[int(tree.feat[0]), k]
                            for k in (b - MOVED_BINS, b + MOVED_BINS)
                            if 0 <= k < self.n_bins[int(tree.feat[0])] - 1]
                    moved = best * (1 - GAIN_TIE) - max(near)
                worst = max(worst, min(max(gap, 0.0), 1.0))
                gap = min(max(gap - GAIN_TIE, 0.0), 1.0)
                n_judged += 1
                avail += max(best, 0.0)
                lost += gap * max(best, 0.0)
                if low is not None and best > 0:
                    lgain, lok = split_gains(low[n], self.n_bins, self.lam,
                                             self.mcw)
                    pick = np.unravel_index(
                        np.argmax(np.where(lok & ok, lgain, -np.inf)),
                        lgain.shape)
                    lost_low += max(best * (1 - GAIN_TIE) - gain[pick], 0.0)
            hists = {n: (new[n], None if low is None else low[n])
                     for n in new}
            self.walker.step(tree, node)
        out = {"split_gap": lost / avail if avail > 0 else 1.0,
               "widest_gap": worst, "nodes_judged": n_judged}
        if g_low is not None:
            out["split_gap_low"] = lost_low / avail if avail > 0 else 1.0
        if moved is not None and avail > 0:
            out["split_gap_moved"] = moved / avail
        return out


# ---------------------------------------------------------- the comparison
def compare_training(X, y, model: dict, cut_ptrs, cut_values, page_bins,
                     sample_idx, sample_bins, sample_margin, *, max_bin: int,
                     max_depth: int, eta: float, lam: float, mcw: float,
                     base_margin: float, follow: int, split_tree: int,
                     lower_precision: bool = False, faults: bool = False,
                     log=lambda s: None) -> Dict[str, float]:
    """Every number the comparison reads.  ``page_bins`` is the binned page
    as (F, R) uint8, ``sample_*`` the program's bins and final margin on the
    rows ``sample_idx``.  ``follow`` trees are followed for the node sums,
    tree ``split_tree`` for the split scan.  With ``lower_precision`` the
    result also holds, under ``*_low``, what the same trees would read with
    the gradient pair in bfloat16: the control.  With ``faults`` it holds
    what the sums would read over half of the rows, doubled (``*_half``),
    and from the margin of one round before (``*_stale``; for the margin,
    the last tree's update left out), what the split scan would read with
    the root's cut moved by ``MOVED_BINS`` bins (``split_gap_moved``), and
    what the sketch would read with every second cut left out
    (``bin_mass_gap_half``): the faults, planted in the reference's place."""
    trees = model_trees(model)
    out: Dict[str, float] = {}
    out["bin_mass_gap"] = bin_mass_gap(X, cut_ptrs, cut_values, max_bin)
    Xs = X[sample_idx]
    out["bin_mismatch"] = float(np.mean(
        bin_rows(Xs, cut_ptrs, cut_values) != sample_bins))
    if faults:
        out["bin_mass_gap_half"] = bin_mass_gap(
            X, *every_second_cut(cut_ptrs, cut_values), max_bin)
    log(f"sketch and binning: bin_mass_gap {out['bin_mass_gap']:.3e}, "
        f"bin_mismatch {out['bin_mismatch']:.3e} on {Xs.size} sampled values")

    y64 = y.astype(np.float64)
    margin = np.full(len(X), base_margin, np.float64)
    walker = Walker(X)
    g, h = np.empty(len(X)), np.empty(len(X))
    names = ("hess_gap", "grad_gap", "leaf_gap")
    out.update({k: 0.0 for k in names})
    if lower_precision:
        out.update({k + "_low": 0.0 for k in names[:2]})
    check = SplitCheck(walker, page_bins, cut_ptrs, cut_values, lam, mcw, max_depth)
    for t, tree in enumerate(trees[:max(follow, split_tree + 1)]):
        if faults:
            stale = (g.copy(), h.copy())
        logistic_gpair(margin, y64, g, h)
        leaf = walker.leaves(tree)
        if t < follow:
            G, H, A = node_sums(tree, leaf, g, h)
            gaps = sums_gaps(tree, G, H, A, lam, eta)
            for k, v in gaps.items():
                out[k] = max(out[k], v)
            log(f"tree {t}: hess_gap {gaps['hess_gap']:.3e}, grad_gap "
                f"{gaps['grad_gap']:.3e}, leaf_gap {gaps['leaf_gap']:.3e} "
                f"over {tree.n_nodes} nodes")
            if lower_precision:
                G16, H16, _ = node_sums(tree, leaf, to_bfloat16(g),
                                        to_bfloat16(h))
                low = sums_gaps(tree, G, H, A, lam, eta, G_got=G16, H_got=H16)
                for k in names[:2]:
                    out[k + "_low"] = max(out[k + "_low"], low[k])
                log(f"tree {t} with the pair in bfloat16: hess_gap "
                    f"{low['hess_gap']:.3e}, grad_gap {low['grad_gap']:.3e}")
            if faults:
                Gh, Hh, _ = node_sums(tree, leaf[::2], 2 * g[::2], 2 * h[::2])
                got = sums_gaps(tree, G, H, A, lam, eta, G_got=Gh, H_got=Hh)
                if t > 0:
                    Gs, Hs, _ = node_sums(tree, leaf, *stale)
                    old = sums_gaps(tree, G, H, A, lam, eta, G_got=Gs, H_got=Hs)
                for k in names[:2]:
                    out[k + "_half"] = min(out.get(k + "_half", np.inf), got[k])
                    if t > 0:
                        out[k + "_stale"] = min(out.get(k + "_stale", np.inf),
                                                old[k])
        if t == split_tree:
            lowp = ((to_bfloat16(g), to_bfloat16(h))
                    if lower_precision else (None, None))
            got = check.run(tree, g, h, *lowp)
            judged, widest = got.pop("nodes_judged"), got.pop("widest_gap")
            out.update(got)
            log(f"tree {t}: split_gap {got['split_gap']:.3e} of the gain on "
                f"offer over {judged} nodes (widest gap of one node "
                f"{widest:.3e})"
                + (f"; splits chosen in bfloat16: {got['split_gap_low']:.3e}"
                   if lower_precision else ""))
        margin += tree.cond[leaf]

    ref_margin = walk(trees, Xs, base_margin)
    scale = np.maximum(np.abs(ref_margin), np.median(np.abs(ref_margin)))
    out["margin_gap"] = float(np.max(
        np.abs(sample_margin.astype(np.float64) - ref_margin) / scale))
    if faults:
        short = walk(trees[:-1], Xs, base_margin)
        out["margin_gap_stale"] = float(np.max(np.abs(short - ref_margin)
                                               / scale))
    log(f"margin after {len(trees)} trees on {len(Xs)} sampled rows: "
        f"margin_gap {out['margin_gap']:.3e}")
    return out
