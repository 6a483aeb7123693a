"""Seeded data makers, one per ``dataset.maker`` a configuration may name.

``higgs_like`` is ``bench.py:make_data`` (informative low-order interactions
plus noise features) in float32 throughout and in row blocks, each block
from a stream of its own spawned from the seed, so that a few threads can
fill them at once and the rows do not depend on which thread ran when (the
one-shot form makes 2.5 GB of float64 at 10.5M rows and takes one core
half a minute).  The same seed gives the same rows; any whole number from
0 up is a seed.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 19
THREADS = 8


def higgs_like(rows: int, features: int, seed: int):
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    starts = range(0, rows, BLOCK)
    streams = np.random.SeedSequence(int(seed)).spawn(len(starts))

    def fill(job):
        lo, stream = job
        hi = min(lo + BLOCK, rows)
        rng = np.random.default_rng(stream)
        # filled in place: a fresh array a call holds the interpreter lock
        # while its pages are first touched, and the threads then queue
        xb = X[lo:hi]
        rng.standard_normal(out=xb, dtype=np.float32)
        noise = y[lo:hi]
        rng.standard_normal(out=noise, dtype=np.float32)
        logits = (np.float32(1.5) * xb[:, 0] + xb[:, 1] * xb[:, 2]
                  - np.float32(0.8) * np.abs(xb[:, 3])
                  + np.float32(0.5) * xb[:, 4] + np.float32(0.3) * noise)
        y[lo:hi] = logits > 0

    with ThreadPoolExecutor(min(THREADS, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, zip(starts, streams)))
    return X, y


MAKERS = {"higgs_like": higgs_like}


def make(dataset: dict, seed: int, rows: int = None):
    """(X, y) of ``rows`` rows (the data set's published count unless a
    rehearsal cuts it; the width is never cut)."""
    maker = MAKERS[dataset["maker"]]
    return maker(int(rows or dataset["rows_published"]),
                 int(dataset["features"]), seed)
