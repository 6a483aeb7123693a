"""The work a boosting round needs, whatever implements it, and the chip's
peaks: the two halves of every roofline share the benchmark reports.

The count is a floor for any float32-histogram trainer of these
configurations, so a share of the roofline computed from it cannot pass
100%.  A level must read, for every row it visits, the row's ``F`` bin
indices at one byte each (``max_bin <= 256``) and its gradient pair (two
float32, 8 B).  The root visits all ``R`` rows; a deeper level need visit
only the smaller child of every sibling pair (the sibling is parent minus
child), at most ``R/2`` rows.  A round adds the gradient (margin and label
in, pair out: 16 B a row) and the margin update (12 B a row).  The adds are
``2*F`` a visited row, four orders under the chip's peak, so bytes bind.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

GPAIR_BYTES = 8
ROUND_BYTES_PER_ROW = 28  # gradient 16 B + margin update 12 B


def visited_rows(rows: int, depth: int) -> float:
    """Rows the ``depth`` levels of one tree have to visit."""
    return rows * (1.0 + (depth - 1) / 2.0)


def level_bytes(rows: int, features: int, depth: int) -> float:
    """Bytes all the levels of one tree have to read."""
    return visited_rows(rows, depth) * (features + GPAIR_BYTES)


def level_flops(rows: int, features: int, depth: int) -> float:
    return visited_rows(rows, depth) * 2.0 * features


def round_bytes(rows: int, features: int, depth: int,
                trees_per_round: int = 1) -> float:
    """Bytes one boosting round has to move."""
    return trees_per_round * (level_bytes(rows, features, depth)
                              + ROUND_BYTES_PER_ROW * rows)


def round_flops(rows: int, features: int, depth: int,
                trees_per_round: int = 1) -> float:
    return trees_per_round * level_flops(rows, features, depth)


def config_shape(config: dict) -> tuple:
    """(rows, features, depth, trees a round) of a training configuration."""
    ds = config["dataset"]
    return (int(ds["rows"]), int(ds["features"]),
            int(config["params"]["max_depth"]),
            int(config.get("guarantees", {}).get("trees_per_round", 1)))


def load_peaks(device_kind: str, path: str = None) -> dict:
    """The peaks of ``device_kind``; a device the table lacks is an error."""
    with open(path or os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"benchmarks/peaks.json has no peaks for device_kind "
            f"{device_kind!r} (it has {sorted(k for k in table if not k.startswith('_'))}): "
            f"add the device with its source, do not default")
    return table[device_kind]


def least_seconds(n_bytes: float, n_flops: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which peak binds it."""
    by_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    by_flops = n_flops / peaks["bf16_flops_per_s"]
    return ((by_bytes, "hbm_bytes_per_s") if by_bytes >= by_flops
            else (by_flops, "bf16_flops_per_s"))
