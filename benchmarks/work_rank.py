"""The work the top-k LambdaMART gradient of a round needs, whatever
implements it: the second half of ``gradient_roofline`` (the peaks and
``least_seconds`` are ``work.py``'s).

A round reads every document's margin and label and writes its gradient
pair: 16 B a document.  Each of a group's top ``k`` documents pairs with
every document ranked below it: ``k' n - k'(k' + 1)/2`` pairs in a group of
``n`` with ``k' = min(k, n)``, each costing ``OPS_A_PAIR`` floating-point
operations.  The count is of the documents and pairs that exist, not of a
grid that holds them, so a share of the roofline computed from it cannot
pass 100% however the groups are laid out.
"""
from __future__ import annotations

import numpy as np

GRADIENT_BYTES_A_DOC = 16  # margin and label in, gradient and hessian out
# score, gain and discount differences (3); their product, its magnitude,
# over the ideal DCG (3); over |score difference| + 0.01 (3); the sigmoid
# (3); lambda (2); the hessian (4); both accumulated at both ends (4)
OPS_A_PAIR = 22


def pair_count(sizes, k: int) -> int:
    """Pairs (i, j) with i among a group's top ``k`` and j ranked below i."""
    n = np.asarray(sizes, np.int64)
    top = np.minimum(n, k)
    return int(np.sum(top * n - top * (top + 1) // 2))


def gradient_bytes(docs: int) -> float:
    return float(GRADIENT_BYTES_A_DOC) * docs


def gradient_flops(pairs: int) -> float:
    return float(OPS_A_PAIR) * pairs
