"""The plain reference of best-first (lossguide) growth under a leaf budget,
in numpy and float64.  It imports nothing of the program; the sums, the
gaps, the walker and the histograms are ``benchmarks/reference.py``'s, which
take trees in creation order already.

Two things a leaf budget needs beyond them:

  * ``grow_serial``  the serial driver itself (dmlc/xgboost src/tree/driver.h
                     under ``grow_policy=lossguide``): pop the open leaf of
                     highest gain, split it, evaluate its two children (the
                     smaller built from its rows, its sibling as parent minus
                     child), push them; stop at ``max_leaves - 1`` splits or
                     where the best gain is at most ``max(gamma, 1e-6)``; ids
                     in pop order, children ``(n, n+1)``.  The CPU tests hold
                     the program's tree against it; with ``order="id"`` or
                     ``commit > 1`` it is the planted fault.
  * ``BestFirstCheck``  teacher-forced on the tree the timed call produced:
                     every node's histogram from its rows, down to the
                     tree's own depth, then
       ``split_gap``   as reference.py's, over every inner node;
       ``order_gap``   the queue replayed with this file's float64 gains: at
                       step ``s`` the program split node ``p_s`` (the parent
                       of ``2s+1, 2s+2``) while the best open leaf offered
                       ``best_s``: the sum of ``max(0, best_s (1 - 1e-4) -
                       gain(p_s))`` over the sum of ``best_s``, plus, where
                       fewer splits than the budget were made, the gain of
                       every open leaf above ``max(gamma, 1e-6)``;
       ``leaves_gap``  leaves beyond the budget, or short of it while a
                       leaf could still be split, over the budget;
       ``child_hess_gap``  how far the lightest child lies under
                       ``min_child_weight``, as a share of it.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks import reference
from benchmarks.reference import GAIN_TIE, MOVED_BINS, Tree, Walker

GAIN_FLOOR = 1e-6  # the driver's floor under gamma
TOPK_COMMIT = 16   # the planted out-of-order commit: this many at once


# --------------------------------------------------------- the serial driver
class Grown:
    """A tree as ``grow_serial`` leaves it: arrays in creation order, the
    best gain on offer at every node (``-inf`` where it may not split), and
    the nodes in the order they were split."""

    def __init__(self) -> None:
        self.left: List[int] = [-1]
        self.right: List[int] = [-1]
        self.parent: List[int] = [-1]
        self.depth: List[int] = [0]
        self.feat: List[int] = [-1]
        self.bin: List[int] = [-1]
        self.gain: List[float] = [-np.inf]
        self.hess: List[float] = [0.0]
        self.order: List[int] = []
        self.offer: List[float] = [-np.inf]  # gain on offer in truth (faults)

    @property
    def leaves(self) -> int:
        return len(self.order) + 1


def _node_hist(bins_fr, rows, g, h, n_bin: int) -> np.ndarray:
    out = np.zeros((bins_fr.shape[0], n_bin, 2))
    gr, hr = g[rows], h[rows]
    for f in range(bins_fr.shape[0]):
        b = bins_fr[f][rows]
        out[f, :, 0] = np.bincount(b, weights=gr, minlength=n_bin)[:n_bin]
        out[f, :, 1] = np.bincount(b, weights=hr, minlength=n_bin)[:n_bin]
    return out


def best_split(hist: np.ndarray, n_bins: np.ndarray, lam: float, mcw: float):
    """(gain, feature, bin) of the best split of one node's histogram, the
    first in (feature, bin) order among equals; the driver's own rule, with
    no room about ``min_child_weight``."""
    GL = np.cumsum(hist[:, :, 0], axis=1)
    HL = np.cumsum(hist[:, :, 1], axis=1)
    G, H = GL[0, -1], HL[0, -1]
    GR, HR = G - GL, H - HL
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam) - G ** 2 / (H + lam)
    b = np.arange(hist.shape[1])[None, :]
    ok = ((b < (n_bins[:, None] - 1)) & (HL >= mcw) & (HR >= mcw)
          & (HL > 0) & (HR > 0) & np.isfinite(gain))
    gain = np.where(ok, gain, -np.inf)
    at = int(np.argmax(gain))
    f, k = divmod(at, hist.shape[1])
    return float(gain[f, k]), f, k


def grow_serial(bins_fr: np.ndarray, g: np.ndarray, h: np.ndarray,
                n_bins: np.ndarray, *, max_leaves: int, max_depth: int = 0,
                lam: float = 1.0, mcw: float = 1.0, gamma: float = 0.0,
                order: str = "gain", commit: int = 1,
                no_sibling_at: int = -1) -> Grown:
    """One tree by the serial driver over the binned page ``bins_fr`` (F, R).
    The planted faults: ``order="id"`` spends the budget in node-id order
    (depth-wise under a budget); ``commit=k`` splits the ``k`` best open
    leaves before it looks at any of their children; ``no_sibling_at=s``
    leaves the subtraction out at split ``s`` (the derived child's histogram
    stays zero, so it is never split, whatever it has on ``offer``)."""
    n_bin = int(n_bins.max())
    floor = max(gamma, GAIN_FLOOR)
    t = Grown()
    rows = {0: np.arange(bins_fr.shape[1])}
    hists = {0: _node_hist(bins_fr, slice(None), g, h, n_bin)}
    t.hess[0] = float(hists[0][0, :, 1].sum())
    t.gain[0], t.feat[0], t.bin[0] = best_split(hists[0], n_bins, lam, mcw)
    t.offer[0] = t.gain[0]
    open_ = [0]
    while len(t.order) < max_leaves - 1:
        live = [n for n in open_ if t.gain[n] > floor]
        if not live:
            break
        live.sort(key=(lambda n: n) if order == "id"
                  else (lambda n: (-t.gain[n], n)))
        for p in live[:min(commit, max_leaves - 1 - len(t.order))]:
            open_.remove(p)
            go_left = bins_fr[t.feat[p]][rows[p]] <= t.bin[p]
            kids = (len(t.left), len(t.left) + 1)
            t.left[p], t.right[p] = kids
            t.order.append(p)
            part = (rows[p][go_left], rows[p][~go_left])
            small = 0 if len(part[0]) <= len(part[1]) else 1
            built = _node_hist(bins_fr, part[small], g, h, n_bin)
            pair = {small: built, 1 - small: hists[p] - built}
            lost = 1 - small if len(t.order) - 1 == no_sibling_at else None
            del rows[p], hists[p]
            for side, n in enumerate(kids):
                for arr, v in ((t.left, -1), (t.right, -1), (t.parent, p),
                               (t.depth, t.depth[p] + 1), (t.feat, -1),
                               (t.bin, -1), (t.gain, -np.inf),
                               (t.offer, -np.inf),
                               (t.hess, float(pair[side][0, :, 1].sum()))):
                    arr.append(v)
                rows[n], hists[n] = part[side], pair[side]
                if max_depth <= 0 or t.depth[n] < max_depth:
                    t.offer[n], t.feat[n], t.bin[n] = best_split(
                        pair[side], n_bins, lam, mcw)
                    t.gain[n] = -np.inf if side == lost else t.offer[n]
                open_.append(n)
    return t


def order_gap(parent, gain, n_splits: int, budget: int, floor: float,
              may_split=None, chooser=None) -> float:
    """The queue replayed: ``parent`` and ``gain`` by node id (creation
    order), ``gain[n]`` the best on offer at ``n``.  What is judged at step
    ``s`` is the node the tree split there; with ``chooser`` (gains by node
    id) the open leaf that ``chooser`` puts first, in the tree's own queue:
    what another precision's order would give away."""
    open_ = {0}
    lost = offered = 0.0
    for s in range(n_splits):
        kids = (2 * s + 1, 2 * s + 2)
        p = int(parent[kids[0]])
        if p not in open_ or int(parent[kids[1]]) != p:
            return 1.0  # not a tree in pop order at all
        best = max(gain[n] for n in open_)
        took = p if chooser is None else max(
            open_, key=lambda n: (chooser[n], -n))
        offered += max(best, 0.0)
        lost += max(0.0, best * (1 - GAIN_TIE) - gain[took])
        open_.remove(p)
        open_.update(kids)
    if chooser is None and n_splits < budget:
        lost += sum(gain[n] for n in open_ if gain[n] > floor
                    and (may_split is None or may_split[n]))
    return min(lost / offered, 1.0) if offered > 0 else float(lost > 0)


def grown_gaps(t: Grown, *, max_leaves: int, gamma: float) -> Dict[str, float]:
    """``order_gap`` and ``leaves_gap`` of a tree of ``grow_serial``'s own,
    from the gains it found on offer: what a planted fault reads."""
    gain = np.asarray(t.offer)
    floor = max(gamma, GAIN_FLOOR)
    return {"order_gap": order_gap(t.parent, gain, len(t.order),
                                   max_leaves - 1, floor),
            "leaves_gap": abs(t.leaves - max_leaves) / max_leaves}


# ------------------------------------------------- the teacher-forced check
class BestFirstCheck:
    """Walks one tree level by level over all rows down to its own depth,
    building the exact histogram of every node (the smaller child of each
    sibling pair from its rows, its sibling as parent minus child)."""

    def __init__(self, walker: Walker, bins_fr, cut_ptrs, cut_values, lam,
                 mcw, gamma, max_leaves, max_depth):
        self.walker, self.bins = walker, bins_fr
        self.ptrs, self.cuts = cut_ptrs, cut_values
        self.n_bins = np.diff(cut_ptrs).astype(np.int64)
        self.n_bin = int(self.n_bins.max())
        self.lam, self.mcw, self.gamma = lam, mcw, gamma
        self.max_leaves, self.max_depth = max_leaves, max_depth

    def chosen_bin(self, tree: Tree, n: int) -> int:
        f = int(tree.feat[n])
        seg = self.cuts[self.ptrs[f]:self.ptrs[f + 1]]
        b = int(np.searchsorted(seg, tree.cond[n], side="left"))
        return b if b < len(seg) and seg[b] == tree.cond[n] else -1

    def run(self, tree: Tree, g, h, g_low=None, h_low=None) -> dict:
        node = np.zeros(len(self.walker.base), np.int64)
        N = tree.n_nodes
        best = np.full(N, -np.inf)   # best on offer, clear of mcw
        taken = np.full(N, -np.inf)  # what the tree's own cut gives
        best_low = np.full(N, -np.inf)
        lost = lost_low = avail = worst = 0.0
        moved = None  # the planted fault: the root's cut, MOVED_BINS aside
        # (lightest child, what it would be with its parent's cut moved to
        # an edge of the same column): the other planted fault
        light = (np.inf, np.inf)
        hists: dict = {}
        for d in range(int(tree.depth.max()) + 1):
            here = np.flatnonzero(tree.depth == d)
            count = np.bincount(node, minlength=N)
            build, derive = [], []
            for n in here:
                if d == 0:
                    build.append(n)
                    continue
                p = int(tree.parent[n])
                sib = int(tree.left[p] + tree.right[p] - n)
                if (count[n], n) <= (count[sib], sib):
                    build.append(n)
                else:
                    derive.append((n, p, sib))
            build = np.asarray(build, np.int64)
            exact = reference.level_hists(self.bins, node, build, g, h,
                                          self.n_bin)
            new = {int(n): exact[i] for i, n in enumerate(build)}
            low = None
            if g_low is not None:
                lo = reference.level_hists(self.bins, node, build, g_low,
                                           h_low, self.n_bin)
                low = {int(n): lo[i] for i, n in enumerate(build)}
            for n, p, sib in derive:
                new[int(n)] = hists[p][0] - new[sib]
                if low is not None:
                    low[int(n)] = hists[p][1] - low[sib]
            for n in here:
                n = int(n)
                if 0 < self.max_depth <= d:
                    continue  # may not split: off the queue
                gain, ok = reference.split_gains(new[n], self.n_bins,
                                                 self.lam, self.mcw)
                best[n] = float(np.max(np.where(ok, gain, -np.inf)))
                if low is not None:
                    lgain, lok = reference.split_gains(
                        low[n], self.n_bins, self.lam, self.mcw)
                    best_low[n] = float(np.max(np.where(lok, lgain, -np.inf)))
                if not tree.inner[n]:
                    continue
                b = self.chosen_bin(tree, n)
                taken[n] = gain[int(tree.feat[n]), b] if b >= 0 else -np.inf
                f = int(tree.feat[n])
                if 0 <= b < self.n_bins[f] - 1:
                    left = np.cumsum(new[n][f, :self.n_bins[f] - 1, 1])
                    lighter = np.minimum(left, new[n][f, :, 1].sum() - left)
                    light = min(light, (lighter[b], lighter[[0, -1]].min()))
                if best[n] > 0:
                    gap = (best[n] - taken[n]) / best[n]
                else:  # nothing on offer: any cut that exists will do
                    gap = 0.0 if taken[n] >= best[n] else 1.0
                if n == 0 and b >= 0:
                    f = int(tree.feat[0])
                    near = [gain[f, k] for k in (b - MOVED_BINS, b + MOVED_BINS)
                            if 0 <= k < self.n_bins[f] - 1]
                    moved = best[0] * (1 - GAIN_TIE) - max(near)
                worst = max(worst, min(max(gap, 0.0), 1.0))
                gap = min(max(gap - GAIN_TIE, 0.0), 1.0)
                avail += max(best[n], 0.0)
                lost += gap * max(best[n], 0.0)
                if low is not None and best[n] > 0:
                    pick = np.unravel_index(
                        np.argmax(np.where(lok & ok, lgain, -np.inf)),
                        lgain.shape)
                    lost_low += max(best[n] * (1 - GAIN_TIE) - gain[pick], 0.0)
            hists = {n: (new[n], None if low is None else low[n])
                     for n in new if tree.inner[n]}
            self.walker.step(tree, node)
        floor = max(self.gamma, GAIN_FLOOR)
        n_splits = int(tree.inner.sum())
        leaves = N - n_splits
        may = (tree.depth < self.max_depth if self.max_depth > 0
               else np.ones(N, bool))
        # a node is worth what is on offer there or, if that is more, what
        # its own cut gives (a cut inside the room about min_child_weight)
        worth = np.maximum(best, taken)
        out = {"split_gap": lost / avail if avail > 0 else 1.0,
               "widest_gap": worst, "nodes_judged": n_splits,
               "order_gap": order_gap(tree.parent, worth, n_splits,
                                      self.max_leaves - 1, floor, may),
               "depth": int(tree.depth.max()), "leaves": leaves}
        could = bool(np.any((worth > floor * (1 + GAIN_TIE)) & ~tree.inner
                            & may))
        wrong = (leaves > self.max_leaves
                 or (leaves < self.max_leaves and could))
        out["leaves_gap"] = (abs(leaves - self.max_leaves) / self.max_leaves
                             if wrong else 0.0)
        if moved is not None and avail > 0:
            out["split_gap_moved"] = moved / avail
        if light[1] < np.inf:
            out["child_hess_gap_moved"] = max(0.0, 1.0 - light[1] / self.mcw)
        if g_low is not None:
            out["split_gap_low"] = lost_low / avail if avail > 0 else 1.0
            # the order a bfloat16 pair would have chosen, judged in float64:
            # at every step the leaf its gains put first
            out["order_gap_low"] = order_gap(
                tree.parent, worth, n_splits, self.max_leaves - 1, floor,
                chooser=best_low)
        return out


# ---------------------------------------------------------- the comparison
def compare_bestfirst(X, y, model: dict, cut_ptrs, cut_values, page_bins,
                      sample_idx, sample_bins, sample_margin, *, max_bin: int,
                      max_leaves: int, max_depth: int, eta: float, lam: float,
                      mcw: float, gamma: float, base_margin: float,
                      follow: int, split_tree: int,
                      lower_precision: bool = False, faults: bool = False,
                      log=lambda s: None) -> Dict[str, float]:
    """Every number the comparison reads: ``reference.compare_training``'s
    (sketch, binning, node sums of the first ``follow`` trees, leaves,
    margin) with ``BestFirstCheck`` on tree ``split_tree`` in the place of
    the level-wise split check.  With ``lower_precision`` also what the same
    trees would read with the pair in bfloat16 (``*_low``).  With ``faults``
    the faults planted in the reference's place: half of the rows, doubled
    (``*_half``); the margin one round old (``*_stale``); every second cut
    left out (``bin_mass_gap_half``); the root's cut moved by ``MOVED_BINS``
    bins (``split_gap_moved``, read in every run as in ``reference.py``);
    the cut above the tree's lightest child moved to an edge of its
    column, which leaves a child under ``min_child_weight``
    (``child_hess_gap_moved``, read in every run too);
    the budget short and over by one
    (``leaves_gap_short``, ``leaves_gap_over``); and three trees this file
    grows itself on the same page and pair: the budget spent in node-id
    order (``order_gap_by_id``), ``TOPK_COMMIT`` leaves split at once
    before any of their children is looked at (``order_gap_topk``), and the
    subtraction left out for the root's children (``order_gap_nosub``: the
    one pair a pass holds alone; left out at a pair whose derived child is a
    leaf of the tree anyway, it changes nothing and reads nought)."""
    trees = reference.model_trees(model)
    out: Dict[str, float] = {}
    out["bin_mass_gap"] = reference.bin_mass_gap(X, cut_ptrs, cut_values,
                                                 max_bin)
    Xs = X[sample_idx]
    out["bin_mismatch"] = float(np.mean(
        reference.bin_rows(Xs, cut_ptrs, cut_values) != sample_bins))
    if faults:
        out["bin_mass_gap_half"] = reference.bin_mass_gap(
            X, *reference.every_second_cut(cut_ptrs, cut_values), max_bin)
    log(f"sketch and binning: bin_mass_gap {out['bin_mass_gap']:.3e}, "
        f"bin_mismatch {out['bin_mismatch']:.3e} on {Xs.size} sampled values")

    y64 = y.astype(np.float64)
    margin = np.full(len(X), base_margin, np.float64)
    walker = Walker(X)
    g, h = np.empty(len(X)), np.empty(len(X))
    names = ("hess_gap", "grad_gap", "leaf_gap")
    out.update({k: 0.0 for k in names})
    out["child_hess_gap"] = 0.0
    if lower_precision:
        out.update({k + "_low": 0.0 for k in names[:2]})
    check = BestFirstCheck(walker, page_bins, cut_ptrs, cut_values, lam, mcw,
                           gamma, max_leaves, max_depth)
    for t, tree in enumerate(trees[:max(follow, split_tree + 1)]):
        if faults:
            stale = (g.copy(), h.copy())
        reference.logistic_gpair(margin, y64, g, h)
        leaf = walker.leaves(tree)
        if t < follow:
            G, H, A = reference.node_sums(tree, leaf, g, h)
            gaps = reference.sums_gaps(tree, G, H, A, lam, eta)
            for k, v in gaps.items():
                out[k] = max(out[k], v)
            light = float(H[1:].min()) if tree.n_nodes > 1 else mcw
            out["child_hess_gap"] = max(out["child_hess_gap"],
                                        max(0.0, 1.0 - light / mcw))
            log(f"tree {t}: hess_gap {gaps['hess_gap']:.3e}, grad_gap "
                f"{gaps['grad_gap']:.3e}, leaf_gap {gaps['leaf_gap']:.3e} "
                f"over {tree.n_nodes} nodes of depth up to "
                f"{int(tree.depth.max())}; lightest child {light:.2f}")
            if lower_precision:
                G16, H16, _ = reference.node_sums(
                    tree, leaf, reference.to_bfloat16(g),
                    reference.to_bfloat16(h))
                low = reference.sums_gaps(tree, G, H, A, lam, eta,
                                          G_got=G16, H_got=H16)
                for k in names[:2]:
                    out[k + "_low"] = max(out[k + "_low"], low[k])
                log(f"tree {t} with the pair in bfloat16: hess_gap "
                    f"{low['hess_gap']:.3e}, grad_gap {low['grad_gap']:.3e}")
            if faults:
                Gh, Hh, _ = reference.node_sums(tree, leaf[::2], 2 * g[::2],
                                                2 * h[::2])
                got = reference.sums_gaps(tree, G, H, A, lam, eta,
                                          G_got=Gh, H_got=Hh)
                if t > 0:
                    Gs, Hs, _ = reference.node_sums(tree, leaf, *stale)
                    old = reference.sums_gaps(tree, G, H, A, lam, eta,
                                              G_got=Gs, H_got=Hs)
                for k in names[:2]:
                    out[k + "_half"] = min(out.get(k + "_half", np.inf), got[k])
                    if t > 0:
                        out[k + "_stale"] = min(out.get(k + "_stale", np.inf),
                                                old[k])
        if t == split_tree:
            lowp = ((reference.to_bfloat16(g), reference.to_bfloat16(h))
                    if lower_precision else (None, None))
            got = check.run(tree, g, h, *lowp)
            judged, widest = got.pop("nodes_judged"), got.pop("widest_gap")
            depth, leaves = got.pop("depth"), got.pop("leaves")
            out.update(got)
            log(f"tree {t}: {leaves} leaves, depth {depth}; split_gap "
                f"{got['split_gap']:.3e} of the gain on offer over {judged} "
                f"nodes (widest gap of one node {widest:.3e}); order_gap "
                f"{got['order_gap']:.3e}; leaves_gap {got['leaves_gap']:.3e}"
                f"; child_hess_gap with the cut above the lightest child at "
                f"its column's edge {got.get('child_hess_gap_moved', 0):.3e}"
                + (f"; in bfloat16: splits {got['split_gap_low']:.3e}, "
                   f"order {got['order_gap_low']:.3e}"
                   if lower_precision else ""))
            if faults:
                out["leaves_gap_short"] = out["leaves_gap_over"] = (
                    1.0 / max_leaves)
                n_bins = np.diff(cut_ptrs).astype(np.int64)
                kw = dict(max_leaves=max_leaves, max_depth=max_depth, lam=lam,
                          mcw=mcw, gamma=gamma)
                for name, how in (
                        ("by_id", dict(order="id")),
                        ("topk", dict(commit=TOPK_COMMIT)),
                        ("nosub", dict(no_sibling_at=0))):
                    grown = grow_serial(page_bins, g, h, n_bins, **kw, **how)
                    got = grown_gaps(grown, max_leaves=max_leaves, gamma=gamma)
                    out["order_gap_" + name] = got["order_gap"]
                    log(f"tree {t} grown here, {name}: {grown.leaves} leaves, "
                        f"depth {max(grown.depth)}, order_gap "
                        f"{got['order_gap']:.3e}")
        margin += tree.cond[leaf]

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
