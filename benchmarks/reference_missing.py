"""The plain reference of hist gradient boosting on rows with absent
entries, in numpy and float64: sparsity-aware split finding (Chen &
Guestrin 2016, section 3.4; ``src/tree/hist/evaluate_splits.h``).

It imports nothing of the program (``benchmarks/reference.py``'s helpers
only) and reads what ``reference.py`` reads, by the same measures, with the
absent entries where they belong:

  * a node's totals ``(G, H)`` are over **all** its rows; a feature's
    histogram ``(g_b, h_b)`` is over the rows that have a value, and what is
    left, ``(G_m, H_m) = (G, H) - sum_b (g_b, h_b)``, is the absent rows';
  * every cut ``b`` offers two candidates, ``(G_L, H_L) = sum_{b' <= b}``
    (absent rows right) and the same plus ``(G_m, H_m)`` (absent rows left);
    the last bin's cut with the absent rows right splits present from absent;
    ``gain = G_L^2/(H_L+lambda) + G_R^2/(H_R+lambda) - G^2/(H+lambda)`` where
    both children hold ``min_child_weight``;
  * a row goes left if its value is under the cut, by the node's
    ``default_left`` if it is NaN;
  * the gradient is the logistic pair, a positive's times ``scale_pos_weight``;
  * a NaN bins to the sentinel (the padded bin width), a value to the count
    of cuts at or under it.

Histograms are built from the present entries alone, through a column-wise
index of them (``PresentIndex``): a fifth of the cells on the data this was
written for.  Beside ``reference.py``'s seven numbers it reads

  * ``default_gap``  over the splits of the followed trees, at the program's
                     own feature and cut: the share of the gain of the better
                     direction that the chosen direction gives away beyond a
                     tie.

With ``faults`` it also reads what the measures would read of a program with
a fault in it, each planted in the reference's place (``compare_training``).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

from benchmarks import reference
from benchmarks.reference import GAIN_TIE, MCW_ROOM, MOVED_BINS


class Tree(reference.Tree):
    """``reference.Tree`` with the direction each split gives its absent rows."""

    def __init__(self, tree: dict) -> None:
        super().__init__(tree)
        self.dleft = np.asarray(tree["default_left"]).astype(bool)


def model_trees(model: dict) -> List[Tree]:
    return [Tree(t) for t in
            model["learner"]["gradient_booster"]["model"]["trees"]]


class Walker(reference.Walker):
    """``reference.Walker`` on rows with NaN: an absent value follows the
    node's ``default_left`` (``dleft``: the tree's own, or a fault's)."""

    def __init__(self, X: np.ndarray) -> None:
        super().__init__(X)
        self.absent = np.empty(len(X), bool)
        self.way = np.empty(len(X), bool)

    def step(self, tree: Tree, node: np.ndarray, dleft=None) -> None:
        np.take(tree.feat, node, out=self.idx, mode="clip")
        self.idx += self.base
        np.take(self.flat, self.idx, out=self.v, mode="clip")
        np.take(tree.cond, node, out=self.c, mode="clip")
        np.take(tree.left, node, out=self.kid, mode="clip")
        np.take(tree.right, node, out=self.other, mode="clip")
        np.less(self.v, self.c, out=self.mask)  # NaN compares false
        np.isnan(self.v, out=self.absent)
        np.take(tree.dleft if dleft is None else dleft, node, out=self.way,
                mode="clip")
        np.copyto(self.mask, self.way, where=self.absent)
        np.copyto(self.other, self.kid, where=self.mask)  # the child taken
        np.greater_equal(self.kid, 0, out=self.mask)  # -1: a leaf stays
        np.copyto(node, self.other, where=self.mask)

    def leaves(self, tree: Tree, dleft=None) -> np.ndarray:
        node = np.zeros(len(self.base), np.int64)
        for _ in range(int(tree.depth.max())):
            self.step(tree, node, dleft)
        return node


def walk(trees: List[Tree], X: np.ndarray, base_margin: float,
         dleft=None) -> np.ndarray:
    """Float64 margin of ``X`` under ``trees``; ``dleft`` (a bool) sends
    every absent value one way whatever the trees say: a fault."""
    walker = Walker(X)
    margin = np.full(len(X), base_margin, np.float64)
    for t in trees:
        way = None if dleft is None else np.full(t.n_nodes, dleft)
        margin += t.cond[walker.leaves(t, way)]
    return margin


def weighted_gpair(margin, y, spw: float, g=None, h=None):
    """The logistic pair with a positive's ``g`` and ``h`` times ``spw``."""
    g, h = reference.logistic_gpair(margin, y, g, h)
    w = np.where(y == 1.0, spw, 1.0)
    g *= w
    h *= w
    return g, h


# ---------------------------------------------------- sketch and binning
def bin_mass_gap(X, cut_ptrs, cut_values, max_bin: int, columns) -> float:
    """``reference.bin_mass_gap`` over the present values of ``columns``:
    the fullest bin's share of a column's values times ``max_bin``, less 1."""
    worst = 0.0
    for f in columns:
        col = X[:, f]
        col = np.sort(col[~np.isnan(col)])
        if not len(col):
            continue
        cuts = cut_values[cut_ptrs[f]:cut_ptrs[f + 1]]
        edges = np.searchsorted(col, cuts[:-1], side="left")
        mass = np.diff(np.concatenate([[0], edges, [len(col)]]))
        worst = max(worst, float(mass.max()) / len(col))
    return worst * max_bin - 1.0


def bin_rows(X, cut_ptrs, cut_values, sentinel: int,
             nan_to: int = None) -> np.ndarray:
    """``reference.bin_rows`` with a NaN in the bin ``sentinel`` (``nan_to``:
    where a fault puts it), as (F, rows) int16: a column at a time over the
    transposed rows (968 strided columns cost more than the search), a few
    threads, a column each (the search releases the interpreter lock)."""
    Xt = np.ascontiguousarray(X.T)
    out = np.empty(Xt.shape, np.int16)

    def one(f: int) -> None:
        cuts = cut_values[cut_ptrs[f]:cut_ptrs[f + 1]]
        found = np.searchsorted(cuts, Xt[f], side="right")
        np.minimum(found, len(cuts) - 1, out=found)  # a NaN sorts past every cut
        found[np.isnan(Xt[f])] = sentinel if nan_to is None else nan_to
        out[f] = found

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(one, range(len(Xt))))
    return out


class PresentIndex:
    """The present entries of a binned page, column by column: the rows that
    have a value and the bin of each.  ``page_fr`` is the page as (F, R),
    ``sentinel`` the symbol of an absent entry."""

    def __init__(self, page_fr: np.ndarray, sentinel: int) -> None:
        self.rows, self.bins = [], []
        for col in page_fr:
            at = np.flatnonzero(col != sentinel)
            self.rows.append(at.astype(np.int32))
            self.bins.append(col[at].astype(np.uint8))
        self.entries = sum(len(r) for r in self.rows)

    def level_hists(self, node, wanted, g, h, n_bin: int) -> np.ndarray:
        """Exact histograms ``(len(wanted), F, n_bin, 2)`` of the nodes
        ``wanted`` over the present entries of the rows that sit on them."""
        slot = np.full(max(int(node.max()), int(np.max(wanted))) + 2, -1,
                       np.int64)
        slot[wanted] = np.arange(len(wanted))
        base = slot[node]
        everything = bool((base >= 0).all())
        base *= n_bin
        n = len(wanted) * n_bin
        out = np.zeros((len(wanted), len(self.rows), n_bin, 2))
        for f, (rows, bins) in enumerate(zip(self.rows, self.bins)):
            flat = base[rows]
            gr, hr = g[rows], h[rows]
            if not everything:
                keep = flat >= 0
                flat, gr, hr, bins = flat[keep], gr[keep], hr[keep], bins[keep]
            flat = flat + bins
            out[:, f, :, 0] = np.bincount(flat, weights=gr, minlength=n
                                          ).reshape(len(wanted), n_bin)
            out[:, f, :, 1] = np.bincount(flat, weights=hr, minlength=n
                                          ).reshape(len(wanted), n_bin)
        return out


# ------------------------------------------------------------ the split scan
def gain_of(GL, HL, G, H, lam: float):
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = (GL ** 2 / (HL + lam) + (G - GL) ** 2 / (H - HL + lam)
                - G ** 2 / (H + lam))
    return np.where(np.isfinite(gain), gain, -np.inf)


def split_gains(hist, G: float, H: float, n_bins, lam: float, mcw: float):
    """Gain ``(2, F, B)`` of every split of one node: direction (0: absent
    rows right, 1: left), feature, cut; and whether it is a candidate clear
    of ``min_child_weight``.  ``hist`` is (F, B, 2) over the present
    entries, ``(G, H)`` the node's totals over all its rows."""
    cum = np.cumsum(hist, axis=1)
    miss = np.array([G, H]) - cum[:, -1, :]  # (F, 2)
    GL = np.stack([cum[:, :, 0], cum[:, :, 0] + miss[:, None, 0]])
    HL = np.stack([cum[:, :, 1], cum[:, :, 1] + miss[:, None, 1]])
    gain = gain_of(GL, HL, G, H, lam)
    b = np.arange(hist.shape[1])[None, None, :]
    least = mcw * (1 + MCW_ROOM)
    ok = (b < n_bins[None, :, None]) & (HL >= least) & (H - HL >= least)
    return gain, ok


def scan_without_absent_totals(hist, n_bins, lam: float, mcw: float):
    """The (direction, feature, cut) that a scan puts first which takes a
    node's totals from the feature's own histogram: the absent rows are in
    no total, no direction gains anything, and the tie goes left (a fault)."""
    cum = np.cumsum(hist, axis=1)
    G, H = cum[:, -1:, 0], cum[:, -1:, 1]
    gain = gain_of(cum[:, :, 0], cum[:, :, 1], G, H, lam)
    b = np.arange(hist.shape[1])[None, :]
    least = mcw * (1 + MCW_ROOM)
    ok = ((b < n_bins[:, None] - 1) & (cum[:, :, 1] >= least)
          & (H - cum[:, :, 1] >= least))
    f, cut = np.unravel_index(np.argmax(np.where(ok, gain, -np.inf)),
                              gain.shape)
    return 1, f, cut


class SplitCheck:
    """``reference.SplitCheck`` with both directions on offer: walks one
    tree level by level over all rows, builds every node's histogram from
    the present entries of its rows and its totals from all of them, and
    reads how far the chosen split's gain lies below the best."""

    def __init__(self, walker: Walker, index: PresentIndex, cut_ptrs,
                 cut_values, lam: float, mcw: float, max_depth: int) -> None:
        self.walker, self.index = walker, index
        self.ptrs, self.cuts = cut_ptrs, cut_values
        self.n_bins = np.diff(cut_ptrs).astype(np.int64)
        self.n_bin = int(self.n_bins.max())
        self.lam, self.mcw, self.max_depth = lam, mcw, max_depth

    def chosen_bin(self, tree: Tree, n: int) -> int:
        f = int(tree.feat[n])
        seg = self.cuts[self.ptrs[f]:self.ptrs[f + 1]]
        b = int(np.searchsorted(seg, tree.cond[n], side="left"))
        return b if b < len(seg) and seg[b] == tree.cond[n] else -1

    def run(self, tree: Tree, g, h, g_low=None, h_low=None,
            faults: bool = False) -> dict:
        """``split_gap``: the share of the gain on offer, summed over the
        tree's nodes, that the chosen (feature, cut, direction) gave away
        beyond a tie.  With ``g_low, h_low`` also ``split_gap_low`` (the
        splits that lower-precision sums put first); with ``faults`` what a
        scan would give away that offers one direction only
        (``split_gap_right``, ``split_gap_left``), that leaves the absent
        rows out of a node's totals (``split_gap_nototal``), or that moved
        the root's cut by ``MOVED_BINS`` bins (``split_gap_moved``)."""
        node = np.zeros(len(self.walker.base), np.int64)
        worst, n_judged, n_same, moved = 0.0, 0, 0, None
        lost = avail = 0.0
        also = {k: 0.0 for k in (("low",) if g_low is not None else ())
                + (("right", "left", "nototal") if faults else ())}
        for d in range(self.max_depth):
            here = np.flatnonzero(tree.depth == d)
            if not len(here):
                break
            sums = [np.bincount(node, weights=v, minlength=tree.n_nodes)
                    for v in (g, h)]
            exact = self.index.level_hists(node, here, g, h, self.n_bin)
            low = None
            if g_low is not None:
                low = self.index.level_hists(node, here, g_low, h_low,
                                             self.n_bin)
                sums_low = [np.bincount(node, weights=v,
                                        minlength=tree.n_nodes)
                            for v in (g_low, h_low)]
            for i, n in enumerate(int(n) for n in here):
                gain, ok = split_gains(exact[i], sums[0][n], sums[1][n],
                                       self.n_bins, self.lam, self.mcw)
                offered = np.where(ok, gain, -np.inf)
                best = float(offered.max())
                b = -1
                if tree.inner[n]:
                    b = self.chosen_bin(tree, n)
                    got = (gain[int(tree.dleft[n]), int(tree.feat[n]), b]
                           if b >= 0 else -np.inf)
                    # the reference's own first choice, or another name for
                    # it: a cut or a direction that parts the rows alike
                    n_same += bool(got >= best - 1e-9 * abs(best))
                    if best > 0:
                        gap = (best - got) / best
                    else:  # nothing on offer: any cut that exists will do
                        gap = 0.0 if got >= best else 1.0
                elif best > 1e-3:
                    gap = 1.0  # a leaf above the last level that could split
                else:
                    gap = 0.0
                if n == 0 and b >= 0:
                    f0 = int(tree.feat[0])
                    near = [gain[int(tree.dleft[0]), f0, k]
                            for k in (b - MOVED_BINS, b + MOVED_BINS)
                            if 0 <= k < self.n_bins[f0] - 1]
                    if near:
                        moved = best * (1 - GAIN_TIE) - max(near)
                worst = max(worst, min(max(gap, 0.0), 1.0))
                gap = min(max(gap - GAIN_TIE, 0.0), 1.0)
                n_judged += 1
                avail += max(best, 0.0)
                lost += gap * max(best, 0.0)
                if best <= 0:
                    continue
                room = best * (1 - GAIN_TIE)
                picks = {}
                if low is not None:
                    lgain, lok = split_gains(low[i], sums_low[0][n],
                                             sums_low[1][n], self.n_bins,
                                             self.lam, self.mcw)
                    picks["low"] = np.unravel_index(np.argmax(np.where(
                        lok & ok, lgain, -np.inf)), lgain.shape)
                if faults:
                    for name, way in (("right", 0), ("left", 1)):
                        f, cut = np.unravel_index(np.argmax(offered[way]),
                                                  offered[way].shape)
                        picks[name] = (way, f, cut)
                    picks["nototal"] = scan_without_absent_totals(
                        exact[i], self.n_bins, self.lam, self.mcw)
                for name, pick in picks.items():
                    also[name] += min(max(room - gain[tuple(pick)], 0.0), best)
            self.walker.step(tree, node)
        out = {"split_gap": lost / avail if avail > 0 else 1.0,
               "widest_gap": worst, "nodes_judged": n_judged,
               "nodes_same": n_same}
        for name, v in also.items():
            out["split_gap_" + name] = v / avail if avail > 0 else 1.0
        if faults and moved is not None and avail > 0:
            out["split_gap_moved"] = moved / avail
        return out


# ------------------------------------------------------- the default direction
def direction_sums(tree: Tree, walker: Walker, g, h):
    """For every node of ``tree``, by one walk of all rows: ``(G, H)`` over
    its rows, over those of them whose value at the node's own feature lies
    under its cut, and over those whose value there is absent.  Also every
    row's leaf."""
    node = np.zeros(len(walker.base), np.int64)
    n = tree.n_nodes
    total, under, absent = np.zeros((n, 2)), np.zeros((n, 2)), np.zeros((n, 2))
    for d in range(int(tree.depth.max()) + 1):
        np.take(tree.feat, node, out=walker.idx, mode="clip")
        walker.idx += walker.base
        np.take(walker.flat, walker.idx, out=walker.v, mode="clip")
        np.take(tree.cond, node, out=walker.c, mode="clip")
        at_depth = tree.depth[node] == d  # a leaf higher up is counted once
        lt = (walker.v < walker.c) & at_depth
        nan = np.isnan(walker.v) & at_depth
        for c, v in enumerate((g, h)):
            total[:, c] += np.bincount(node, weights=v * at_depth, minlength=n)
            under[:, c] += np.bincount(node, weights=v * lt, minlength=n)
            absent[:, c] += np.bincount(node, weights=v * nan, minlength=n)
        walker.step(tree, node)
    return total, under, absent, node


def direction_gains(tree: Tree, total, under, absent, lam: float, mcw: float):
    """Of each inner node's own feature and cut, with the absent rows right
    (0) and left (1): the gain ``(2, n_inner)``, and whether both children
    hold ``min_child_weight`` with room to spare (``clear``) or nearly
    (``near``): float32 sums may put a child that holds it by a thousandth
    on either side."""
    inner = np.flatnonzero(tree.inner)
    G, H = total[inner, 0], total[inner, 1]
    gains = np.empty((2, len(inner)))
    clear = np.empty((2, len(inner)), bool)
    near = np.empty((2, len(inner)), bool)
    for way in (0, 1):
        GL = under[inner, 0] + way * absent[inner, 0]
        HL = under[inner, 1] + way * absent[inner, 1]
        gains[way] = gain_of(GL, HL, G, H, lam)
        lighter = np.minimum(HL, H - HL)
        clear[way] = lighter >= mcw * (1 + MCW_ROOM)
        near[way] = lighter >= mcw * (1 - MCW_ROOM)
    return inner, gains, clear, near


def default_gap_parts(tree: Tree, gains, clear, near, inner, chosen=None):
    """(lost, on offer) of one tree: ``sum max(0, gain(best direction) (1 -
    1e-4) - gain(chosen direction))`` and ``sum gain(best direction)`` over
    its splits; the direction not chosen is on offer where it is clear of
    ``min_child_weight``.  ``chosen``: the direction a fault takes at every
    split."""
    way = tree.dleft[inner].astype(int) if chosen is None else np.full(
        len(inner), chosen)
    at = np.arange(len(inner))
    got = np.where(near[way, at], gains[way, at], -np.inf)
    other = np.where(clear[1 - way, at], gains[1 - way, at], -np.inf)
    best = np.maximum(np.maximum(got, other), 0.0)
    lost = np.minimum(np.maximum(best * (1 - GAIN_TIE) - got, 0.0), best)
    return float(lost.sum()), float(best.sum())


def children_without_absent(tree: Tree, total, absent):
    """Every node's ``(G, H)`` as a level step would record them that left
    the absent rows of the parent's feature out of the children's totals (a
    fault): the child the absent rows went to holds that much less."""
    got = total.copy()
    for n in np.flatnonzero(tree.inner):
        child = tree.left[n] if tree.dleft[n] else tree.right[n]
        got[child] -= absent[n]
    return got


# ---------------------------------------------------------- the comparison
def compare_training(X, y, model: dict, cut_ptrs, cut_values, page_fr,
                     sentinel: int, sample_idx, sample_bins, sample_margin, *,
                     max_bin: int, max_depth: int, eta: float, lam: float,
                     mcw: float, spw: float, base_margin: float, follow: int,
                     split_tree: int, continuous,
                     lower_precision: bool = False, faults: bool = False,
                     log=lambda s: None) -> Dict[str, float]:
    """Every number the comparison reads (``reference.compare_training`` on
    rows with NaN).  ``page_fr`` is the binned page as (F, R) with
    ``sentinel`` where a row has no value, ``sample_bins`` its columns
    ``sample_idx``; ``continuous`` the columns the sketch is judged on.  With ``lower_precision`` also ``*_low``: the sums
    and the splits with the gradient pair in bfloat16.  With ``faults``
    what the measures would read of

      ``*_right``, ``*_left``  every absent entry sent right (left) by the
                               route, whatever the split says, and a scan
                               that offers that direction alone;
      ``*_nototal``   the absent rows left out of the children's totals;
      ``bin_mismatch_nan0``    NaN binned into bin 0;
      ``margin_gap_nodefault`` the margin update sending every absent entry
                               one way (the nearer of the two);
      ``*_nospw``     the gradient without ``scale_pos_weight``;

    and ``reference.py``'s moved cut and halved sketch (``split_gap_moved``,
    ``bin_mass_gap_half``)."""
    trees = model_trees(model)
    out: Dict[str, float] = {}
    out["bin_mass_gap"] = bin_mass_gap(X, cut_ptrs, cut_values, max_bin,
                                       continuous)
    Xs = X[sample_idx]
    ours = bin_rows(Xs, cut_ptrs, cut_values, sentinel)
    out["bin_mismatch"] = float(np.mean(ours != sample_bins))
    if faults:
        out["bin_mass_gap_half"] = bin_mass_gap(
            X, *reference.every_second_cut(cut_ptrs, cut_values), max_bin,
            continuous)
        out["bin_mismatch_nan0"] = float(np.mean(
            bin_rows(Xs, cut_ptrs, cut_values, sentinel, nan_to=0)
            != sample_bins))
    log(f"sketch and binning: bin_mass_gap {out['bin_mass_gap']:.3e} over "
        f"{len(continuous)} continuous columns, bin_mismatch "
        f"{out['bin_mismatch']:.3e} on {Xs.size} sampled cells, "
        f"{np.mean(ours == sentinel):.4f} of them absent")

    index = PresentIndex(page_fr, sentinel)
    log(f"the page's {index.entries} present entries indexed by column "
        f"({index.entries / page_fr.size:.4f} of its cells)")
    y64 = y.astype(np.float64)
    margin = np.full(len(X), base_margin, np.float64)
    walker = Walker(X)
    g, h = np.empty(len(X)), np.empty(len(X))
    names = ("hess_gap", "grad_gap", "leaf_gap")
    out.update({k: 0.0 for k in names})
    planted = (("low",) if lower_precision else ()) + (
        ("right", "left", "nototal", "nospw") if faults else ())
    for tag in planted:
        out.update({f"{k}_{tag}": np.inf for k in names[:2]})
    lost = on_offer = 0.0
    lost_way = [0.0, 0.0]
    check = SplitCheck(walker, index, cut_ptrs, cut_values, lam, mcw,
                       max_depth)
    for t, tree in enumerate(trees[:max(follow, split_tree + 1)]):
        weighted_gpair(margin, y64, spw, g, h)
        total, under, absent, leaf = direction_sums(tree, walker, g, h)
        if t < follow:
            G, H, A = reference.node_sums(tree, leaf, g, h)
            gaps = reference.sums_gaps(tree, G, H, A, lam, eta)
            for k, v in gaps.items():
                out[k] = max(out[k], v)
            inner, gains, clear, near = direction_gains(
                tree, total, under, absent, lam, mcw)
            part = default_gap_parts(tree, gains, clear, near, inner)
            lost, on_offer = lost + part[0], on_offer + part[1]
            log(f"tree {t}: hess_gap {gaps['hess_gap']:.3e}, grad_gap "
                f"{gaps['grad_gap']:.3e}, leaf_gap {gaps['leaf_gap']:.3e} "
                f"over {tree.n_nodes} nodes; of {len(inner)} splits "
                f"{int(tree.dleft[inner].sum())} send absent rows left, "
                f"{int((np.abs(gains[0] - gains[1]) > GAIN_TIE * np.maximum(gains.max(0), 0)).sum())}"
                f" where it matters; default_gap so far "
                f"{lost / max(on_offer, 1e-300):.3e}")
            other = {}
            if lower_precision:
                other["low"] = reference.node_sums(
                    tree, leaf, reference.to_bfloat16(g),
                    reference.to_bfloat16(h))[:2]
            if faults:
                for tag, way in (("right", False), ("left", True)):
                    there = walker.leaves(tree, np.full(tree.n_nodes, way))
                    other[tag] = reference.node_sums(tree, there, g, h)[:2]
                    lost_way[int(way)] += default_gap_parts(
                        tree, gains, clear, near, inner, chosen=int(way))[0]
                short = children_without_absent(tree, total, absent)
                other["nototal"] = (short[:, 0], short[:, 1])
                plain = reference.logistic_gpair(margin, y64)
                other["nospw"] = reference.node_sums(tree, leaf, *plain)[:2]
            for tag, (Gf, Hf) in other.items():
                got = reference.sums_gaps(tree, G, H, A, lam, eta, G_got=Gf,
                                          H_got=Hf)
                for k in names[:2]:  # the least over the trees: the fault
                    out[f"{k}_{tag}"] = min(out[f"{k}_{tag}"], got[k])
        if t == split_tree:
            lowp = ((reference.to_bfloat16(g), reference.to_bfloat16(h))
                    if lower_precision else (None, None))
            got = check.run(tree, g, h, *lowp, faults=faults)
            judged, widest = got.pop("nodes_judged"), got.pop("widest_gap")
            same = got.pop("nodes_same")
            out.update(got)
            log(f"tree {t}: split_gap {got['split_gap']:.3e} of the gain on "
                f"offer over {judged} nodes, both directions on offer "
                f"(widest gap of one node {widest:.3e}; {same} of "
                f"{int(tree.inner.sum())} splits are the reference's own "
                f"first choice)")
        margin += tree.cond[leaf]
    out["default_gap"] = lost / on_offer if on_offer > 0 else 1.0
    if faults:
        out["default_gap_right"] = lost_way[0] / on_offer if on_offer > 0 else 1.0
        out["default_gap_left"] = lost_way[1] / on_offer if on_offer > 0 else 1.0

    ref_margin = walk(trees, Xs, base_margin)
    scale = np.maximum(np.abs(ref_margin), np.median(np.abs(ref_margin)))

    def margin_gap(got):
        return float(np.max(np.abs(got - ref_margin) / scale))

    out["margin_gap"] = margin_gap(sample_margin.astype(np.float64))
    if faults:
        out["margin_gap_nodefault"] = min(
            margin_gap(walk(trees, Xs, base_margin, dleft=way))
            for way in (False, True))
    log(f"margin after {len(trees)} trees on {len(Xs)} sampled rows: "
        f"margin_gap {out['margin_gap']:.3e}")
    return out
