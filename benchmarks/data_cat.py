"""Seeded maker of rows shaped like the airline on-time data as
github.com/szilard/benchm-ml frames it (ASA Data Expo 2009): eight columns,
six of them categorical codes, and the label ``dep_delayed_15min``.

The columns, in benchm-ml's order (``COLUMNS``): Month, DayofMonth,
DayOfWeek (codes 0-11, 0-30, 0-6), DepTime (hhmm, numeric), UniqueCarrier
(22 codes), Origin and Dest (300 codes each), Distance (miles, numeric).

The fixed part (``world()``) is drawn once from ``WORLD_SEED``, as the
published table's airports and carriers are fixed: each airport's share of
the flights falls off as a power of its rank (a few hubs take a large share,
as ATL and ORD do), with the ranks dealt to codes at random (codes are
alphabetical in the source, unrelated to size); the airports sit on a plane
2,500 miles across, so Distance is a function of the (Origin, Dest) pair,
the same both ways; the carriers' shares are skewed too.  Every effect on
the label (hour of departure, carrier, origin, destination, month, weekday,
day of month) is drawn per code at random, so that no effect is monotone in
code order: a split on a set of codes is the right one, and a split at a
code threshold is not.

A row comes from the seed: the airports of a flight by their shares (a
flight never lands where it left), the carrier by its share, the date
uniform, the departure hour from a daily profile and the minute uniform.
The label is 1 where a latent score (the effects plus logistic noise) passes
its ``POSITIVE_SHARE`` quantile in the seed's own rows: 19% positive.  The
same seed gives the same rows; any whole number from 0 up is a seed.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

COLUMNS = ("Month", "DayofMonth", "DayOfWeek", "DepTime", "UniqueCarrier",
           "Origin", "Dest", "Distance")
# codes a categorical column holds; the numeric columns are absent
CATEGORIES = {"Month": 12, "DayofMonth": 31, "DayOfWeek": 7,
              "UniqueCarrier": 22, "Origin": 300, "Dest": 300}
FEATURE_TYPES = tuple("c" if c in CATEGORIES else "q" for c in COLUMNS)
AIRPORTS = CATEGORIES["Origin"]
CARRIERS = CATEGORIES["UniqueCarrier"]
AIRPORT_POWER = 0.7   # the busiest airport about 6% of the departures
CARRIER_POWER = 0.8
POSITIVE_SHARE = 0.19
WORLD_SEED = 20090101
URN = 1 << 20
# departures by hour (local time, 0-23): none at night, a morning and an
# evening bank
HOURS = np.array([1, 0.4, 0.2, 0.1, 0.1, 2, 6, 8, 8, 7, 7, 7, 7, 7, 7, 7, 7,
                  8, 8, 7, 6, 5, 3, 2], np.float64)


class World(NamedTuple):
    """The fixed part: who flies where and what each code does to delay."""

    airport_share: np.ndarray   # (300,) a code's share of the departures
    carrier_share: np.ndarray   # (22,)
    airport_urn: np.ndarray     # (URN,) codes by their shares (``_urn``)
    carrier_urn: np.ndarray
    hour_urn: np.ndarray        # (URN,) departure hours by HOURS
    distance: np.ndarray        # (300, 300) miles between two airports
    effects: dict               # column name -> (codes,) effect on the latent
    hour_effect: np.ndarray     # (24,)


def _power_shares(rng, n: int, power: float) -> np.ndarray:
    share = 1.0 / np.arange(1, n + 1) ** power
    return rng.permutation(share / share.sum())


def _urn(share: np.ndarray) -> np.ndarray:
    """Codes, each ``share * URN`` times (largest remainders): a uniform draw
    from it is a draw by the shares to within ``1 / URN``, at the cost of a
    gather (a draw by cumulative sums searches them, several times slower)."""
    want = share * URN
    count = np.floor(want).astype(np.int64)
    short = URN - count.sum()
    count[np.argsort(count - want, kind="stable")[:short]] += 1
    return np.repeat(np.arange(len(share)), count)


@functools.lru_cache(maxsize=1)
def world() -> World:
    rng = np.random.default_rng(WORLD_SEED)
    airports = _power_shares(rng, AIRPORTS, AIRPORT_POWER)
    carriers = _power_shares(rng, CARRIERS, CARRIER_POWER)
    at = rng.uniform(0.0, 2500.0, size=(AIRPORTS, 2))
    miles = np.sqrt(((at[:, None, :] - at[None, :, :]) ** 2).sum(-1))
    distance = np.maximum(np.round(miles), 31.0)
    scale = {"Month": 0.25, "DayofMonth": 0.1, "DayOfWeek": 0.2,
             "UniqueCarrier": 0.4, "Origin": 0.45, "Dest": 0.3}
    effects = {c: rng.normal(0.0, s, size=CATEGORIES[c])
               for c, s in scale.items()}
    # later in the day, later the flight: delays pile up into the evening
    hour = np.clip((np.arange(24) - 6) / 12.0, 0.0, 1.2) + rng.normal(
        0.0, 0.05, size=24)
    return World(airports, carriers, _urn(airports), _urn(carriers),
                 _urn(HOURS / HOURS.sum()), distance, effects, hour)


def airline_like(seed: int, rows: int):
    """(X float32 (rows, 8) in ``COLUMNS`` order, y float32 in {0, 1})."""
    w = world()
    rng = np.random.default_rng(int(seed))
    X = np.empty((rows, len(COLUMNS)), np.float32)
    col = {c: i for i, c in enumerate(COLUMNS)}
    codes = {
        "Month": rng.integers(0, 12, size=rows),
        "DayofMonth": rng.integers(0, 31, size=rows),
        "DayOfWeek": rng.integers(0, 7, size=rows),
        "UniqueCarrier": w.carrier_urn[rng.integers(0, URN, size=rows)],
        "Origin": w.airport_urn[rng.integers(0, URN, size=rows)],
    }
    # the destination by its share, never the origin: a draw that lands
    # there is shifted to the next airport
    dest = w.airport_urn[rng.integers(0, URN, size=rows)]
    same = dest == codes["Origin"]
    dest[same] = (dest[same] + 1) % AIRPORTS
    codes["Dest"] = dest
    hour = w.hour_urn[rng.integers(0, URN, size=rows)]
    minute = rng.integers(0, 60, size=rows)
    for c, v in codes.items():
        X[:, col[c]] = v
    X[:, col["DepTime"]] = hour * 100 + minute
    X[:, col["Distance"]] = w.distance[codes["Origin"], dest]
    latent = w.hour_effect[hour] + rng.logistic(0.0, 0.6, size=rows)
    for c, v in codes.items():
        latent += w.effects[c][v]
    cut = np.partition(latent, int(rows * (1.0 - POSITIVE_SHARE)))[
        int(rows * (1.0 - POSITIVE_SHARE))]
    return X, (latent > cut).astype(np.float32)


def categorical_columns() -> list:
    """The indices of the categorical columns."""
    return [i for i, t in enumerate(FEATURE_TYPES) if t == "c"]


def numeric_columns() -> list:
    return [i for i, t in enumerate(FEATURE_TYPES) if t == "q"]
