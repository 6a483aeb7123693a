"""The work a boosting round needs on a page whose entries are mostly
absent: the first half of ``level_present_roofline`` (the peaks and
``least_seconds`` are ``work.py``'s).

``work.level_bytes`` counts a dense page: ``F`` bin indices a visited row.
A trainer that kept only the entries that exist (upstream's ELLPACK keeps a
row stride under ``F``) would have to read, for every row a level visits,
the row's present entries at one byte each and its gradient pair (8 B):
``work.visited_rows x (F x present share + 8)`` bytes, the share being what
the program counted over its own page (``bins.missing`` of ``bins.cells``).
It counts less than ``work.level_bytes`` does, so a share of the roofline
computed from it reads under ``level_roofline`` and cannot pass 100%.
"""
from __future__ import annotations

from benchmarks import work


def level_present_bytes(rows: int, features: int, depth: int,
                        present_share: float) -> float:
    """Bytes all the levels of one tree have to read of a page that holds
    ``present_share`` of its cells."""
    return work.visited_rows(rows, depth) * (
        features * present_share + work.GPAIR_BYTES)


def level_present_flops(rows: int, features: int, depth: int,
                        present_share: float) -> float:
    return work.visited_rows(rows, depth) * 2.0 * features * present_share
