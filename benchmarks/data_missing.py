"""Seeded maker of rows shaped like Kaggle's "Bosch Production Line
Performance" (``train_numeric.csv``): a wide table in which a measurement
exists only where a part passed the station that takes it.

The plant (``plant()``) is fixed, as the published file's columns are: 968
numeric columns dealt unevenly to 52 stations on 4 lines (stations 0-23 on
line 0, 24-25 on line 1, 26-28 on line 2, 29-51 on line 3; 1 to 100 columns
a station), and 36 paths, each a set of stations with the share of the parts
that take it: a part starts on line 0 or 1, rarely on line 2, and nearly
always ends on line 3.  Of a column 12 in 968 are constant, 120 continuous
(at stations that at least 8% of the parts pass, so that the sketch is judged
where it has values to sketch) and the rest quantised to 3-250 levels.

The rows come from the seed, in blocks, each from a stream of its own spawned
from the seed (``data.py`` has the reason): a part draws its path, has a
value in every column of the stations it passes but for an independent
drop-out of 2%, and NaN elsewhere.  The label is 1 where a latent score
passes a fixed threshold (0.58% of the parts): low-order interactions of a
few columns' values **and of whether they exist**, with absence standing for
a high value in one column and for a low one in another, so that the
direction a split gives its absent rows carries gain both ways.  The same
seed gives the same rows; any whole number from 0 up is a seed.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

FEATURES = 968
STATIONS = 52
LINE_OF_STATION = np.repeat([0, 1, 2, 3], [24, 2, 3, 23])
PATHS = 36
DROP_OUT = 0.02
CONSTANT, CONTINUOUS = 12, 120
CONTINUOUS_LEAST_PASSED = 0.08
LEAST_PASSED = 0.015
PLANT_SEED = 20161111
BLOCK = 1 << 15
THREADS = 8
# the latent score's 99.42nd percentile over 2M parts of seeds 0 and 1
# (tests/bench_harness/test_data_missing.py holds the share it leaves)
THRESHOLD = 4.4215


class Plant(NamedTuple):
    """The fixed part of the data: what a column is and who passes it."""

    first: np.ndarray    # (52,) a station's first column
    columns: np.ndarray  # (52,) its number of columns
    station: np.ndarray  # (968,) a column's station
    passes: np.ndarray   # (36, 52) bool: the stations of a path
    share: np.ndarray    # (36,) the share of the parts on a path
    mu: np.ndarray       # (968,) float32: a column's centre
    scale: np.ndarray    # (968,) float32: its spread
    load: np.ndarray     # (968,) float32: how far it follows the part's own latent
    steps: np.ndarray    # (968,) float32: quantisation steps a unit (0: continuous; inf: constant)
    told: np.ndarray     # (6,) the columns the label reads
    told_absent: np.ndarray   # (6,) what an absent entry of them stands for
    told_stations: np.ndarray  # (2,) the stations whose passing the label reads

    def passed(self) -> np.ndarray:
        """(52,) the share of the parts that pass each station."""
        return self.share @ self.passes

    def present(self) -> np.ndarray:
        """(968,) the share of the parts that have a value in each column."""
        return self.passed()[self.station] * (1.0 - DROP_OUT)

    def path_columns(self) -> np.ndarray:
        """(36,) the columns of the stations of each path."""
        return self.passes @ self.columns


@functools.lru_cache(maxsize=1)
def plant() -> Plant:
    rng = np.random.default_rng(PLANT_SEED)
    # columns a station: uneven, 1 to 100, line 1's two stations the widest
    raw = rng.lognormal(mean=2.3, sigma=0.9, size=STATIONS)
    raw[LINE_OF_STATION == 1] = [100.0, 64.0]
    columns = np.clip(np.round(raw), 1, 100).astype(np.int64)
    while columns.sum() != FEATURES:
        s = int(rng.integers(STATIONS))
        step = 1 if columns.sum() < FEATURES else -1
        if LINE_OF_STATION[s] != 1 and 1 <= columns[s] + step <= 100:
            columns[s] += step
    first = np.concatenate([[0], np.cumsum(columns)[:-1]])
    station = np.repeat(np.arange(STATIONS), columns)

    # paths: a first line, some of its stations, then some of line 3's
    line_share = np.array([0.55, 0.38, 0.07])
    line_paths = np.array([20, 10, 6])
    keep = np.array([0.21, 0.85, 0.8])
    passes = np.zeros((PATHS, STATIONS), bool)
    share = np.zeros(PATHS)
    p = 0
    for line in range(3):
        weights = rng.dirichlet(np.full(line_paths[line], 2.0))
        for k in range(line_paths[line]):
            while True:
                row = np.zeros(STATIONS, bool)
                own = LINE_OF_STATION == line
                row[own] = rng.random(own.sum()) < keep[line]
                last = LINE_OF_STATION == 3
                row[last] = rng.random(last.sum()) < (0.0 if k % 9 == 8 else 0.19)
                if row[own].any() and 60 <= row @ columns <= 420:
                    break
            passes[p], share[p] = row, line_share[line] * weights[k]
            p += 1
    # every column in at least 1% of the parts: a station that fewer pass
    # joins further paths of its line, those that many parts take first
    for s in range(STATIONS):
        line = LINE_OF_STATION[s]
        lo = int(np.sum(line_paths[:line])) if line < 3 else 0
        hi = lo + line_paths[line] if line < 3 else PATHS
        for q in lo + np.argsort(-share[lo:hi]):
            if share @ passes[:, s] >= LEAST_PASSED:
                break
            if not passes[q, s] and passes[q] @ columns + columns[s] <= 420:
                passes[q, s] = True

    mu = rng.uniform(-0.3, 0.3, FEATURES).astype(np.float32)
    scale = rng.uniform(0.08, 0.22, FEATURES).astype(np.float32)
    load = np.where(rng.random(FEATURES) < 0.2,
                    rng.uniform(0.3, 0.7, FEATURES), 0.0).astype(np.float32)
    passed = share @ passes
    levels = np.exp(rng.uniform(np.log(3), np.log(250), FEATURES))
    steps = ((levels - 1.0) / 6.0).astype(np.float32)  # values span 6 units
    often = np.flatnonzero(passed[station] >= CONTINUOUS_LEAST_PASSED)
    steps[rng.choice(often, CONTINUOUS, replace=False)] = 0.0
    quantised = np.flatnonzero(steps > 0)
    steps[rng.choice(quantised, CONSTANT, replace=False)] = np.inf

    # the label reads six columns at six stations that 15-55% of the parts
    # pass, none constant, and whether two further stations were passed
    fair = [s for s in np.argsort(-columns)
            if 0.15 <= passed[s] <= 0.55]
    told = np.array([first[s] + int(np.flatnonzero(np.isfinite(
        steps[first[s]:first[s] + columns[s]]))[0]) for s in fair[:6]])
    load[told] = 0.0
    told_stations = np.array(fair[6:8])
    return Plant(first=first, columns=columns, station=station, passes=passes,
                 share=share, mu=mu, scale=scale, load=load, steps=steps,
                 told=told,
                 told_absent=np.array([1.2, 0.5, 0.5, 0.0, -1.2, 0.8],
                                      np.float32),
                 told_stations=told_stations)


def _fill(X: np.ndarray, y: np.ndarray, stream) -> None:
    """One block of parts, in place."""
    pl = plant()
    rng = np.random.default_rng(stream)
    n = len(X)
    X.fill(np.nan)
    path = rng.choice(PATHS, size=n, p=pl.share)
    latent = rng.standard_normal(n, dtype=np.float32)
    for s in range(STATIONS):
        rows = np.flatnonzero(pl.passes[path, s])
        if not len(rows):
            continue
        c0, c1 = pl.first[s], pl.first[s] + pl.columns[s]
        t = rng.standard_normal((len(rows), c1 - c0), dtype=np.float32)
        load = pl.load[c0:c1]
        t *= np.sqrt(1.0 - load * load)
        t += latent[rows, None] * load
        np.clip(t, -3.0, 3.0, out=t)
        steps = pl.steps[c0:c1]
        coarse = np.flatnonzero(steps > 0)
        if len(coarse):
            k = np.where(np.isfinite(steps[coarse]), steps[coarse], 1.0)
            t[:, coarse] = np.where(np.isfinite(steps[coarse]),
                                    np.round(t[:, coarse] * k) / k, 0.0)
        t *= pl.scale[c0:c1]
        t += pl.mu[c0:c1]
        t[rng.random(t.shape, dtype=np.float32) < DROP_OUT] = np.nan
        X[rows, c0:c1] = t
    u = (X[:, pl.told] - pl.mu[pl.told]) / pl.scale[pl.told]
    u = np.where(np.isnan(u), pl.told_absent, u)
    went = pl.passes[path][:, pl.told_stations]
    score = (1.0 * u[:, 0] + 0.8 * u[:, 1] * u[:, 2] - 0.7 * np.abs(u[:, 3])
             + 0.9 * u[:, 4] + 0.6 * np.maximum(u[:, 5], 0.0)
             + 0.7 * went[:, 0] - 0.5 * went[:, 1]
             + 0.5 * latent
             + 0.6 * rng.standard_normal(n, dtype=np.float32))
    y[:] = score > THRESHOLD


def bosch_like(seed: int, rows: int):
    """(X, y): ``rows`` parts x 968 float32 with NaN where a part did not
    pass, and the float32 label."""
    X = np.empty((rows, FEATURES), np.float32)
    y = np.empty(rows, np.float32)
    starts = range(0, rows, BLOCK)
    streams = np.random.SeedSequence(int(seed)).spawn(len(starts))

    def fill(job):
        lo, stream = job
        _fill(X[lo:lo + BLOCK], y[lo:lo + BLOCK], stream)

    with ThreadPoolExecutor(min(THREADS, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, zip(starts, streams)))
    return X, y


def continuous_columns() -> np.ndarray:
    return np.flatnonzero(plant().steps == 0)
