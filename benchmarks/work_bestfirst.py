"""The work a best-first tree needs, whatever grew it: the first half of
``expand_roofline`` (the peaks and ``least_seconds`` are ``work.py``'s).

By ``work.py``'s argument a float32-histogram trainer must read, for every
row it visits, the row's ``F`` bin indices at one byte each and its gradient
pair (8 B).  The root visits all ``R`` rows; every split after it need
visit only the smaller of its two children (the sibling is parent minus
child).  So a tree costs ``(R + sum over its splits of the smaller child's
rows) (F + 8)`` bytes, counted on the tree that was grown, from the rows
that really sit in its nodes: a floor for any order of growth and any number
of passes, so a share of the roofline computed from it cannot pass 100%.
"""
from __future__ import annotations

import numpy as np

from benchmarks import work


def smaller_child_rows(tree, leaf: np.ndarray) -> int:
    """Sum over the splits of ``tree`` (a ``reference.Tree``) of the rows of
    the smaller child; ``leaf`` is every row's leaf (``Walker.leaves``)."""
    count = np.bincount(leaf, minlength=tree.n_nodes).astype(np.int64)
    for n in range(tree.n_nodes - 1, -1, -1):
        if tree.inner[n]:
            count[n] = count[tree.left[n]] + count[tree.right[n]]
    inner = np.flatnonzero(tree.inner)
    return int(np.minimum(count[tree.left[inner]],
                          count[tree.right[inner]]).sum())


def tree_bytes(rows: int, smaller_rows: int, features: int) -> float:
    """Bytes one tree's histograms have to read."""
    return float(rows + smaller_rows) * (features + work.GPAIR_BYTES)


def tree_flops(rows: int, smaller_rows: int, features: int) -> float:
    return float(rows + smaller_rows) * 2.0 * features
