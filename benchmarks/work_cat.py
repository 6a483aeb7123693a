"""The work a boosting round's levels need on a page of categorical codes:
the first half of ``cat_level_roofline`` (the peaks and ``least_seconds``
are ``work.py``'s).

``work.level_bytes`` counts one byte a column, which holds a bin index of
``max_bin <= 256`` bins; a categorical column's bins are its categories,
past ``max_bin`` (Origin and Dest: about 300, 9 bits).  Here a level reads,
for every row it visits, each column's bin index at the bits its bins need
(``ceil(log2(bins))``: a numeric column's ``max_bin``, a categorical
column's categories) and the row's gradient pair (8 B): whatever implements
it, no trainer of this configuration reads less.
"""
from __future__ import annotations

import math

from benchmarks import work


def column_bits(config: dict) -> list:
    """Bits a column's bin index needs, column by column."""
    ds = config["dataset"]
    cats = ds.get("categorical", {})
    numeric = int(config["params"]["max_bin"])
    return [max(1, math.ceil(math.log2(cats.get(name, numeric))))
            for name in ds["columns"]]


def level_bytes(config: dict, rows: int) -> float:
    """Bytes all the levels of one tree have to read."""
    depth = int(config["params"]["max_depth"])
    return work.visited_rows(rows, depth) * (
        sum(column_bits(config)) / 8.0 + work.GPAIR_BYTES)


def level_flops(config: dict, rows: int) -> float:
    depth = int(config["params"]["max_depth"])
    return work.visited_rows(rows, depth) * 2.0 * len(column_bits(config))
