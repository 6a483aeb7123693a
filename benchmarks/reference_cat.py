"""The plain reference of hist gradient boosting with categorical columns,
in numpy and float64: xgboost's native categorical splits as
dmlc/xgboost ``doc/tutorials/categorical.rst`` describes them (the scan of
``src/tree/hist/evaluate_splits.h``, written from memory: docs/categorical.md
has what this file takes it to be).

It imports nothing of the program (``reference.py``'s and
``reference_missing.py``'s helpers only) and reads, from the raw rows and
labels, what ``reference.py`` reads, with the categorical columns where they
belong:

  * a categorical column's bin is its code (a category is a cut, whatever
    ``max_bin``); a numeric column's the count of its cuts at or under the
    value; NaN the sentinel (the page's width); the numeric columns' cuts
    against an exact sketch of them (``bin_mass_gap``);
  * a node's totals ``(G, H)`` over all its rows, a column's histogram over
    the rows that have a value there, the absent rows' sums what is left;
  * a numeric column offers every cut in both directions of the absent rows
    (``reference_missing.split_gains``);
  * a categorical column of fewer than ``max_cat_to_onehot`` categories
    offers each category alone on the right, the absent rows either way; a
    wider one a partition: its categories sorted by their own weight
    (``-G / (H + lambda)``, 0 under ``min_child_weight``; categories with no
    row last), the first ``k`` sent right with the absent rows left, or the
    last ``k`` sent left with them right, ``k`` at most ``n - 1``, ``n =
    min(max_cat_threshold, categories)``;
  * ``gain = G_L^2/(H_L+lambda) + G_R^2/(H_R+lambda) - G^2/(H+lambda)``
    where both children hold ``min_child_weight``;
  * a row goes right at a categorical split if its code is in the split's
    set, left if not (a code the set never saw too), by ``default_left`` if
    its value is NaN; at a numeric split left if its value is under the cut.

Beside ``reference.py``'s numbers it reads

  * ``catset_gap``  over the categorical splits of the followed tree: the
                    share whose set of categories (of those with rows at the
                    node) parts the node's rows otherwise than the
                    reference's best partition on that column does, where
                    that best beats every other partition on the column by
                    more than ``GAIN_TIE``.

With ``faults`` it also reads what the measures would read of a program with
a fault in it, each planted in the reference's place (``compare_training``).
"""
from __future__ import annotations

import copy
from typing import Dict, List

import numpy as np

from benchmarks import reference
from benchmarks.reference import GAIN_TIE, MCW_ROOM
from benchmarks.reference_missing import gain_of


class Tree(reference.Tree):
    """``reference.Tree`` with each split's absent-row direction, whether it
    is categorical, and the codes a categorical split sends right."""

    def __init__(self, tree: dict) -> None:
        super().__init__(tree)
        self.dleft = np.asarray(tree["default_left"]).astype(bool)
        self.is_cat = np.asarray(
            tree.get("split_type", np.zeros(self.n_nodes)), np.int64) == 1
        cats = np.asarray(tree.get("categories", []), np.int64)
        self.sets = {int(n): cats[s:s + k] for n, s, k in zip(
            tree.get("categories_nodes", []),
            tree.get("categories_segments", []),
            tree.get("categories_sizes", []))}

    def member_table(self, n_codes: int, flip=None) -> np.ndarray:
        """(n_nodes * n_codes,) bool: code ``c`` in node ``n``'s set at
        ``n * n_codes + c``; ``flip`` {node: code} moves one code across."""
        table = np.zeros((self.n_nodes, n_codes), bool)
        for n, codes in self.sets.items():
            table[n, codes[codes < n_codes]] = True
        for n, c in (flip or {}).items():
            table[n, c] = not table[n, c]
        return table.reshape(-1)


def model_trees(model: dict) -> List[Tree]:
    return [Tree(t) for t in
            model["learner"]["gradient_booster"]["model"]["trees"]]


class Walker(reference.Walker):
    """``reference.Walker`` with categorical splits and NaN: a code in the
    node's set goes right, any other code left, NaN by ``default_left``.
    ``n_codes`` bounds the codes a set can hold (the widest column)."""

    def __init__(self, X: np.ndarray, n_codes: int) -> None:
        super().__init__(X)
        self.n_codes = int(n_codes)
        self.absent = np.empty(len(X), bool)
        self.way = np.empty(len(X), bool)
        self.code = np.empty(len(X), np.int64)

    def step(self, tree: Tree, node: np.ndarray, table=None) -> None:
        np.take(tree.feat, node, out=self.idx, mode="clip")
        self.idx += self.base
        np.take(self.flat, self.idx, out=self.v, mode="clip")
        np.take(tree.cond, node, out=self.c, mode="clip")
        np.less(self.v, self.c, out=self.mask)  # NaN compares false
        if tree.sets:
            member = tree.member_table(self.n_codes) if table is None else table
            np.isnan(self.v, out=self.absent)
            np.copyto(self.code, np.where(self.absent, 0, self.v),
                      casting="unsafe")
            inside = (self.code >= 0) & (self.code < self.n_codes)
            np.clip(self.code, 0, self.n_codes - 1, out=self.code)
            self.code += node * self.n_codes
            right = member[self.code] & inside
            np.copyto(self.mask, ~right, where=tree.is_cat[node])
        np.isnan(self.v, out=self.absent)
        np.take(tree.dleft, node, out=self.way, mode="clip")
        np.copyto(self.mask, self.way, where=self.absent)
        np.take(tree.left, node, out=self.kid, mode="clip")
        np.take(tree.right, node, out=self.other, mode="clip")
        np.copyto(self.other, self.kid, where=self.mask)  # the child taken
        np.greater_equal(self.kid, 0, out=self.mask)  # -1: a leaf stays
        np.copyto(node, self.other, where=self.mask)

    def leaves(self, tree: Tree, table=None) -> np.ndarray:
        node = np.zeros(len(self.base), np.int64)
        if table is None and tree.sets:
            table = tree.member_table(self.n_codes)
        for _ in range(int(tree.depth.max())):
            self.step(tree, node, table)
        return node


def walk(trees: List[Tree], X: np.ndarray, base_margin: float,
         n_codes: int) -> np.ndarray:
    """Float64 margin of ``X`` under ``trees``."""
    walker = Walker(X, n_codes)
    margin = np.full(len(X), base_margin, np.float64)
    for t in trees:
        margin += t.cond[walker.leaves(t)]
    return margin


# ---------------------------------------------------------------- binning
def quantile_cuts(col: np.ndarray, max_bin: int) -> np.ndarray:
    """Cuts a numeric sketch would give ``col`` (exact quantiles at
    ``i / max_bin``, values above the least, then one past the greatest): the
    planted fault of a categorical column binned as a numeric one."""
    col = np.sort(col[~np.isnan(col)])
    at = np.ceil(np.arange(1, max_bin) / max_bin * len(col)).astype(np.int64)
    cand = np.unique(col[np.clip(at - 1, 0, len(col) - 1)])
    return np.append(cand[cand > col[0]], col[-1] + 1.0)


def bin_mass_gap(X, cut_ptrs, cut_values, max_bin: int, columns) -> float:
    """The sketch of the numeric ``columns``: the fullest bin's share of a
    column's values under its cuts over the same under exact quantile cuts
    of ``max_bin`` bins (``quantile_cuts``), less 1, the worst column.  A
    column of few distinct values (DepTime in minutes, Distance in miles)
    fills its bins unevenly whatever sketches it, so the ideal is the exact
    sketch's own fullest bin, not ``1 / max_bin``: an exact sketch reads 0,
    one of half the bins about 1."""
    worst = 0.0
    for f in columns:
        col = np.sort(X[:, f][~np.isnan(X[:, f])])
        if not len(col):
            continue

        def fullest(cuts):
            edges = np.searchsorted(col, cuts[:-1], side="left")
            return np.diff(np.concatenate([[0], edges, [len(col)]])).max()

        own = cut_values[cut_ptrs[f]:cut_ptrs[f + 1]]
        worst = max(worst, fullest(own) / fullest(quantile_cuts(col, max_bin)))
    return worst - 1.0


def bin_rows(X, cut_ptrs, cut_values, sentinel: int, cat_cols,
             as_numeric: int = 0) -> np.ndarray:
    """(F, rows) int16: a categorical column's code, a numeric column's
    count of cuts at or under the value (the top bin past the last cut),
    NaN the sentinel.  ``as_numeric``: bin the categorical columns through
    quantile cuts of that many bins instead (a fault)."""
    out = np.empty((X.shape[1], len(X)), np.int16)
    for f in range(X.shape[1]):
        v = X[:, f]
        if f in cat_cols and not as_numeric:
            found = np.where(np.isnan(v), 0, v).astype(np.int64)
        else:
            cuts = (quantile_cuts(v, as_numeric) if f in cat_cols
                    else cut_values[cut_ptrs[f]:cut_ptrs[f + 1]])
            found = np.minimum(np.searchsorted(cuts, v, side="right"),
                               len(cuts) - 1)
        found[np.isnan(v)] = sentinel
        out[f] = found
    return out


def level_hists(page_fr, node, wanted, g, h, n_bin: int) -> np.ndarray:
    """Exact histograms ``(len(wanted), F, n_bin, 2)`` of the nodes
    ``wanted`` over the present entries of their rows; ``page_fr`` is the
    page as (F, R), ``n_bin`` its sentinel."""
    slot = np.full(max(int(node.max()), int(np.max(wanted))) + 2,
                   len(wanted), np.int64)  # every other node: a dump slot
    slot[wanted] = np.arange(len(wanted))
    width = n_bin + 1  # the sentinel's own column, dropped
    base = slot[node] * width
    n = (len(wanted) + 1) * width
    out = np.zeros((len(wanted), page_fr.shape[0], n_bin, 2))
    flat = np.empty(len(base), np.int64)
    for f, col in enumerate(page_fr):
        np.add(base, col, out=flat)
        for c, v in enumerate((g, h)):
            out[:, f, :, c] = np.bincount(flat, weights=v, minlength=n)[
                :len(wanted) * width].reshape(len(wanted), width)[:, :n_bin]
    return out


# ------------------------------------------------------------ the split scan
class Scan:
    """Every candidate split of one node: ``gain`` and ``ok`` (2, F, B) by
    direction of the absent rows (0: right, 1: left), feature and candidate
    ``j``; for a categorical column candidate ``j`` sends ``order[f, :j+1]``
    right (``order[f, j]`` alone in a one-hot column)."""

    def __init__(self, hist, G: float, H: float, n_bins, cat_cols, *,
                 lam: float, mcw: float, onehot_max: int, threshold: int):
        F, B, _ = hist.shape
        self.hist, self.n_bins = hist, n_bins
        feat = hist.sum(axis=1)  # (F, 2) over the present rows
        miss = np.array([G, H]) - feat
        self.order = np.tile(np.arange(B), (F, 1))
        self.onehot = np.zeros(F, bool)
        self.rows = hist[:, :, 1] > 0  # the categories with rows (by H)
        present_left = np.cumsum(hist, axis=1)  # numeric: bins 0..j left
        j = np.arange(B)
        okj = np.tile(j < n_bins[:, None], (2, 1, 1))
        for f in cat_cols:
            nb = int(n_bins[f])
            if nb < onehot_max:
                self.onehot[f] = True
                present_left[f] = feat[f] - hist[f]
                okj[:, f] = j < nb
                continue
            Gc, Hc = hist[f, :, 0], hist[f, :, 1]
            with np.errstate(divide="ignore", invalid="ignore"):
                weight = np.where(Hc < mcw, 0.0, -Gc / (Hc + lam))
            key = np.where(Hc > 0, weight, np.inf)
            self.order[f] = np.argsort(key, kind="stable")
            right = np.cumsum(hist[f, self.order[f]], axis=0)
            present_left[f] = feat[f] - right
            n = min(threshold, nb)
            okj[1, f] = j <= n - 2              # forward: right k <= n - 1
            okj[0, f] = (j >= nb - n) & (j <= nb - 2)  # backward: left k
        GL = np.stack([present_left[:, :, 0],
                       present_left[:, :, 0] + miss[:, None, 0]])
        HL = np.stack([present_left[:, :, 1],
                       present_left[:, :, 1] + miss[:, None, 1]])
        self.gain = gain_of(GL, HL, G, H, lam)
        least = mcw * (1 + MCW_ROOM)
        self.ok = okj & (HL >= least) & (H - HL >= least)
        self.offered = np.where(self.ok, self.gain, -np.inf)
        self.G, self.H, self.miss, self.lam = G, H, miss, lam

    def best(self) -> float:
        return float(self.offered.max())

    def right_set(self, f: int, j: int) -> np.ndarray:
        """The codes candidate ``j`` of categorical column ``f`` sends right."""
        return (self.order[f, j:j + 1] if self.onehot[f]
                else self.order[f, :j + 1])

    def gain_of_set(self, f: int, codes, dleft: bool, mcw: float) -> float:
        """Gain of sending ``codes`` of column ``f`` right, the absent rows
        left if ``dleft``; -inf where a child holds less than
        ``min_child_weight`` by more than float32's room."""
        right = self.hist[f, np.asarray(codes, np.int64)].sum(axis=0)
        GL = self.G - right[0] - (0.0 if dleft else self.miss[f, 0])
        HL = self.H - right[1] - (0.0 if dleft else self.miss[f, 1])
        if min(HL, self.H - HL) < mcw * (1 - MCW_ROOM):
            return -np.inf
        return float(gain_of(GL, HL, self.G, self.H, self.lam))

    def partition(self, f: int, codes) -> frozenset:
        """The node's categories with rows on the right of a set, as the
        unordered partition of them it makes."""
        having = set(np.flatnonzero(self.rows[f]).tolist())
        right = having & set(np.asarray(codes).tolist())
        return frozenset([frozenset(right), frozenset(having - right)])

    def column_best(self, f: int):
        """(gain, partition) of the best candidate of categorical column
        ``f``, and the best gain of a candidate that parts its rows
        otherwise: its runner-up."""
        gains = self.offered[:, f]
        flat = np.argsort(-gains, axis=None, kind="stable")
        top, runner, part = -np.inf, -np.inf, None
        for at in flat:
            way, j = np.unravel_index(at, gains.shape)
            g = gains[way, j]
            if not np.isfinite(g):
                break
            p = self.partition(f, self.right_set(f, j))
            if part is None:
                top, part = g, p
            elif p != part:
                runner = g
                break
        return top, runner, part


def clear_winner(top: float, runner: float) -> bool:
    return top > 0 and top - max(runner, 0.0) > GAIN_TIE * top


class SplitCheck:
    """Walks one tree level by level over all rows (the program's own tree:
    teacher-forced), builds every node's histogram from the present entries
    of its rows and its totals from all of them, and reads how far the
    chosen split's gain lies below the best (``split_gap``) and how often a
    categorical split's set parts the rows otherwise than the reference's
    clear best on that column (``catset_gap``)."""

    def __init__(self, walker: Walker, page_fr, cut_ptrs, cut_values,
                 cat_cols, *, lam: float, mcw: float, max_depth: int,
                 onehot_max: int, threshold: int) -> None:
        self.walker, self.page = walker, page_fr
        self.ptrs, self.cuts = cut_ptrs, cut_values
        self.n_bins = np.diff(cut_ptrs).astype(np.int64)
        self.n_bin = int(self.n_bins.max())
        self.cat_cols = list(cat_cols)
        self.lam, self.mcw, self.max_depth = lam, mcw, max_depth
        self.onehot_max, self.threshold = onehot_max, threshold

    def chosen_bin(self, tree: Tree, n: int) -> int:
        f = int(tree.feat[n])
        seg = self.cuts[self.ptrs[f]:self.ptrs[f + 1]]
        b = int(np.searchsorted(seg, tree.cond[n], side="left"))
        return b if b < len(seg) and seg[b] == tree.cond[n] else -1

    def scan(self, hist, G, H, threshold=None) -> Scan:
        return Scan(hist, G, H, self.n_bins, self.cat_cols, lam=self.lam,
                    mcw=self.mcw, onehot_max=self.onehot_max,
                    threshold=threshold or self.threshold)

    def chosen_gain(self, s: Scan, tree: Tree, n: int) -> float:
        f = int(tree.feat[n])
        if tree.is_cat[n]:
            return s.gain_of_set(f, tree.sets.get(n, []), bool(tree.dleft[n]),
                                 self.mcw)
        b = self.chosen_bin(tree, n)
        return s.gain[int(tree.dleft[n]), f, b] if b >= 0 else -np.inf

    def run(self, tree: Tree, g, h, g_low=None, h_low=None,
            faults: bool = False) -> dict:
        """``split_gap`` and ``catset_gap`` of the tree; with ``g_low, h_low``
        also ``*_low`` (the splits that lower-precision sums put first); with
        ``faults`` ``catset_gap_nothreshold``: the sets a scan that ignores
        ``max_cat_threshold`` would choose."""
        node = np.zeros(len(self.walker.base), np.int64)
        worst, n_judged = 0.0, 0
        lost = avail = 0.0
        cat_splits = 0
        differ = {"": 0, "low": 0, "nothreshold": 0}
        lost_low = 0.0
        for d in range(self.max_depth):
            here = np.flatnonzero(tree.depth == d)
            if not len(here):
                break
            sums = [np.bincount(node, weights=v, minlength=tree.n_nodes)
                    for v in (g, h)]
            exact = level_hists(self.page, node, here, g, h, self.n_bin)
            if g_low is not None:
                low = level_hists(self.page, node, here, g_low, h_low,
                                  self.n_bin)
                sums_low = [np.bincount(node, weights=v,
                                        minlength=tree.n_nodes)
                            for v in (g_low, h_low)]
            for i, n in enumerate(int(n) for n in here):
                s = self.scan(exact[i], sums[0][n], sums[1][n])
                best = s.best()
                if tree.inner[n]:
                    got = self.chosen_gain(s, tree, n)
                    gap = ((best - got) / best if best > 0
                           else 0.0 if got >= best else 1.0)
                elif best > 1e-3:
                    gap = 1.0  # a leaf above the last level that could split
                else:
                    gap = 0.0
                worst = max(worst, min(max(gap, 0.0), 1.0))
                n_judged += 1
                avail += max(best, 0.0)
                lost += min(max(gap - GAIN_TIE, 0.0), 1.0) * max(best, 0.0)
                if g_low is not None and best > 0:
                    ls = self.scan(low[i], sums_low[0][n], sums_low[1][n])
                    pick = np.unravel_index(np.argmax(np.where(
                        ls.ok & s.ok, ls.gain, -np.inf)), ls.gain.shape)
                    lost_low += min(max(best * (1 - GAIN_TIE)
                                        - s.gain[pick], 0.0), best)
                if not (tree.inner[n] and tree.is_cat[n]):
                    continue
                cat_splits += 1
                f = int(tree.feat[n])
                top, runner, part = s.column_best(f)
                if not clear_winner(top, runner):
                    continue
                picks = {"": s.partition(f, tree.sets.get(n, []))}
                if g_low is not None:
                    picks["low"] = ls.column_best(f)[2]
                if faults:
                    picks["nothreshold"] = self.scan(
                        exact[i], sums[0][n], sums[1][n],
                        threshold=1 << 30).column_best(f)[2]
                for name, p in picks.items():
                    differ[name] += p != part
            self.walker.step(tree, node)
        share = max(cat_splits, 1)
        out = {"split_gap": lost / avail if avail > 0 else 1.0,
               "catset_gap": differ[""] / share, "widest_gap": worst,
               "nodes_judged": n_judged, "cat_splits": cat_splits}
        if g_low is not None:
            out["split_gap_low"] = lost_low / avail if avail > 0 else 1.0
            out["catset_gap_low"] = differ["low"] / share
        if faults:
            out["catset_gap_nothreshold"] = differ["nothreshold"] / share
        return out


def flipped_sets(tree: Tree, walker: Walker, n_codes: int) -> np.ndarray:
    """The member table of ``tree`` with, at every categorical split, the
    code of its set that has the most rows at the node moved to the left
    (a route that flips one category of a set: a fault)."""
    table = tree.member_table(n_codes).reshape(tree.n_nodes, n_codes)
    node = np.zeros(len(walker.base), np.int64)
    flip = {}
    for d in range(int(tree.depth.max())):
        at = [int(n) for n in np.flatnonzero((tree.depth == d) & tree.is_cat)
              if n in tree.sets]
        if at:
            f_of = tree.feat[node]
            v = walker.flat[walker.base + f_of]
            code = np.where(np.isnan(v), -1, v).astype(np.int64)
            keep = np.isin(node, at) & (code >= 0) & (code < n_codes)
            counts = np.bincount(node[keep] * n_codes + code[keep],
                                 minlength=tree.n_nodes * n_codes).reshape(
                                     tree.n_nodes, n_codes)
            for n in at:
                members = tree.sets[n][tree.sets[n] < n_codes]
                if len(members):
                    flip[n] = int(members[np.argmax(counts[n, members])])
        walker.step(tree, node, table.reshape(-1))
    return tree.member_table(n_codes, flip)


def altered_leaf(tree: Tree) -> Tree:
    """``tree`` with its first leaf's value altered by 1% (a fault)."""
    bent = copy.copy(tree)
    bent.cond = tree.cond.copy()
    bent.cond[np.flatnonzero(~tree.inner)[0]] *= 1.01
    return bent


# ---------------------------------------------------------- the comparison
def compare_training(X, y, model: dict, cut_ptrs, cut_values, page_fr,
                     sentinel: int, sample_idx, sample_bins, sample_margin, *,
                     cat_cols, numeric_cols, max_bin: int, max_depth: int,
                     eta: float, lam: float, mcw: float, onehot_max: int,
                     threshold: int, base_margin: float, follow: int,
                     split_tree: int, lower_precision: bool = False,
                     faults: bool = False,
                     log=lambda s: None) -> Dict[str, float]:
    """Every number the comparison reads (``reference.compare_training``
    with categorical columns).  ``page_fr`` is the binned page as (F, R),
    ``sentinel`` its symbol of an absent entry, ``sample_bins`` its (F, n)
    columns ``sample_idx``.  With ``lower_precision`` also ``*_low``: the
    sums and the splits with the gradient pair in bfloat16 (the control).
    With ``faults`` what the measures would read of

      ``*_flip``        a route that moves one category of every
                        categorical split's set (its most populous at the
                        node) to the other side;
      ``catset_gap_nothreshold``  a scan that ignores ``max_cat_threshold``;
      ``bin_mismatch_numeric``    the categorical columns binned through a
                        numeric sketch of ``max_bin`` bins;
      ``leaf_gap_altered``  the first leaf of each followed tree altered by
                        1% (the least reading of those trees);
      ``margin_gap_stale``  the margin with the last tree's update left out;

    and ``reference.py``'s halved sketch (``bin_mass_gap_half``)."""
    trees = model_trees(model)
    n_codes = int(np.diff(cut_ptrs).max())
    out: Dict[str, float] = {}
    out["bin_mass_gap"] = bin_mass_gap(X, cut_ptrs, cut_values, max_bin,
                                       numeric_cols)
    Xs = X[sample_idx]
    cats = set(cat_cols)
    out["bin_mismatch"] = float(np.mean(
        bin_rows(Xs, cut_ptrs, cut_values, sentinel, cats) != sample_bins))
    if faults:
        out["bin_mass_gap_half"] = bin_mass_gap(
            X, *reference.every_second_cut(cut_ptrs, cut_values), max_bin,
            numeric_cols)
        out["bin_mismatch_numeric"] = float(np.mean(bin_rows(
            Xs, cut_ptrs, cut_values, sentinel, cats, as_numeric=max_bin)
            != sample_bins))
    log(f"sketch and binning: bin_mass_gap {out['bin_mass_gap']:.3e} over "
        f"{len(numeric_cols)} numeric columns, bin_mismatch "
        f"{out['bin_mismatch']:.3e} on {Xs.size} sampled cells, the widest "
        f"column {n_codes} bins")

    y64 = y.astype(np.float64)
    margin = np.full(len(X), base_margin, np.float64)
    walker = Walker(X, n_codes)
    g, h = np.empty(len(X)), np.empty(len(X))
    names = ("hess_gap", "grad_gap", "leaf_gap")
    out.update({k: 0.0 for k in names})
    planted = (("low",) if lower_precision else ()) + (
        ("flip",) if faults else ())
    for tag in planted:
        out.update({f"{k}_{tag}": 0.0 if tag == "low" else np.inf
                    for k in names[:2]})
    if faults:
        out["leaf_gap_altered"] = np.inf
    check = SplitCheck(walker, page_fr, cut_ptrs, cut_values, cat_cols,
                       lam=lam, mcw=mcw, max_depth=max_depth,
                       onehot_max=onehot_max, threshold=threshold)
    for t, tree in enumerate(trees[:max(follow, split_tree + 1)]):
        reference.logistic_gpair(margin, y64, g, h)
        leaf = walker.leaves(tree)
        if t < follow:
            G, H, A = reference.node_sums(tree, leaf, g, h)
            gaps = reference.sums_gaps(tree, G, H, A, lam, eta)
            for k, v in gaps.items():
                out[k] = max(out[k], v)
            log(f"tree {t}: hess_gap {gaps['hess_gap']:.3e}, grad_gap "
                f"{gaps['grad_gap']:.3e}, leaf_gap {gaps['leaf_gap']:.3e} "
                f"over {tree.n_nodes} nodes, {int(tree.is_cat.sum())} "
                f"categorical splits")
            other = {}
            if lower_precision:
                other["low"] = reference.node_sums(
                    tree, leaf, reference.to_bfloat16(g),
                    reference.to_bfloat16(h))[:2]
            if faults and tree.sets:
                there = walker.leaves(tree, flipped_sets(tree, walker,
                                                         n_codes))
                other["flip"] = reference.node_sums(tree, there, g, h)[:2]
            if faults:
                out["leaf_gap_altered"] = min(
                    out["leaf_gap_altered"],
                    reference.sums_gaps(altered_leaf(tree), G, H, A, lam,
                                        eta)["leaf_gap"])
            for tag, (Gf, Hf) in other.items():
                got = reference.sums_gaps(tree, G, H, A, lam, eta, G_got=Gf,
                                          H_got=Hf)
                for k in names[:2]:  # the control's worst tree, the
                    # fault's least (tree 0's pair is exact in bfloat16)
                    pick = max if tag == "low" else min
                    out[f"{k}_{tag}"] = pick(out[f"{k}_{tag}"], got[k])
        if t == split_tree:
            lowp = ((reference.to_bfloat16(g), reference.to_bfloat16(h))
                    if lower_precision else (None, None))
            got = check.run(tree, g, h, *lowp, faults=faults)
            judged, widest = got.pop("nodes_judged"), got.pop("widest_gap")
            n_cat = got.pop("cat_splits")
            out.update(got)
            log(f"tree {t}: split_gap {got['split_gap']:.3e} of the gain on "
                f"offer over {judged} nodes (widest gap of one node "
                f"{widest:.3e}); catset_gap {got['catset_gap']:.3e} over "
                f"{n_cat} categorical splits")
        margin += tree.cond[leaf]

    ref_margin = walk(trees, Xs, base_margin, n_codes)
    scale = np.maximum(np.abs(ref_margin), np.median(np.abs(ref_margin)))
    out["margin_gap"] = float(np.max(
        np.abs(sample_margin.astype(np.float64) - ref_margin) / scale))
    if faults:
        short = walk(trees[:-1], Xs, base_margin, n_codes)
        out["margin_gap_stale"] = float(np.max(np.abs(short - ref_margin)
                                               / scale))
    log(f"margin after {len(trees)} trees on {len(Xs)} sampled rows: "
        f"margin_gap {out['margin_gap']:.3e}")
    return out
