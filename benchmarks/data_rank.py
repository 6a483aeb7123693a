"""Seeded learning-to-rank data in the shape of MSLR-WEB30K (``mslr_like``):
documents in ragged query groups, 136 float32 columns, grades 0-4.

There is no network, so the LETOR files are not read; what the maker keeps
of them is what the trainer feels.  Group sizes are heavy-tailed between 1
and 1,251 and add up to the published row count exactly, with at least one
group of each extreme.  ``COUNT_COLUMNS`` of the columns are integer-valued
with few distinct values (MSLR's term counts, URL lengths, link counts), the
first ``QUERY_COLUMNS`` of them the same for every document of a query; the
rest are continuous, half of them heavy-tailed.  The grade is cut, at the
published shares, from a latent score of low-order interactions of the
columns plus an offset a query plus noise, so a ranker has something to
learn.  Rows are made in blocks, each from a stream of its own spawned from
the seed, so a few threads fill them at once and the rows do not depend on
which thread ran when.  The same seed gives the same rows; any whole number
from 0 up is a seed.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 17
THREADS = 8
COUNT_COLUMNS = 40  # integer-valued, each with under 256 distinct values
QUERY_COLUMNS = 5   # of those, constant over a query's documents
COUNT_CAPS = (1, 3, 7, 12, 20, 40, 100, 250)  # a count column's largest value
GRADE_SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)  # grades 0..4
SIZE_SIGMA = 0.85  # of the log of a group's size


def group_sizes(groups: int, rows: int, smallest: int, largest: int,
                rng) -> np.ndarray:
    """``groups`` sizes in ``smallest..largest`` that add up to ``rows``:
    log-normal, scaled to the sum, one group pinned to each extreme, and the
    rounding's remainder spread a document at a time."""
    raw = rng.lognormal(0.0, SIZE_SIGMA, groups)
    sizes = np.clip(np.rint(raw * (rows / raw.sum())), smallest + 1,
                    largest - 1).astype(np.int64)
    pinned = rng.choice(groups, 2, replace=False)
    sizes[pinned] = (largest, smallest)
    free = np.ones(groups, bool)
    free[pinned] = False
    while (short := rows - int(sizes.sum())) != 0:
        step = 1 if short > 0 else -1
        room = free & ((sizes < largest - 1) if step > 0
                       else (sizes > smallest + 1))
        pick = rng.choice(np.flatnonzero(room),
                          min(abs(short), int(room.sum())), replace=False)
        sizes[pick] += step
    return sizes


def mslr_like(dataset: dict, seed: int, rows: int = None,
              held_groups: int = 0):
    """(X, y, qid, held) of the data set ``dataset`` describes.  ``rows``
    (a rehearsal) keeps the longest run of whole leading groups that fits;
    the width is never cut.  ``held`` is (X, y, qid) of ``held_groups``
    further queries from the same process, for a held-out NDCG."""
    gs = dataset["group_size"]
    root = np.random.SeedSequence(int(seed))
    layout, held_layout, cols = root.spawn(3)
    rng = np.random.default_rng(layout)
    sizes = group_sizes(int(dataset["query_groups"]), int(dataset["rows"]),
                        int(gs["min"]), int(gs["max"]), rng)
    if rows is not None and rows < sizes.sum():
        sizes = sizes[:max(int(np.searchsorted(np.cumsum(sizes), rows,
                                               side="right")), 1)]
    if held_groups:
        extra = np.random.default_rng(held_layout).lognormal(
            np.log(gs["mean"]) - SIZE_SIGMA ** 2 / 2, SIZE_SIGMA, held_groups)
        sizes = np.concatenate([sizes, np.clip(np.rint(extra), gs["min"],
                                               gs["max"]).astype(np.int64)])
    X, y, qid = _fill(sizes, int(dataset["features"]), cols)
    cut = int(sizes[:len(sizes) - held_groups].sum())
    held = (X[cut:], y[cut:], qid[cut:]) if held_groups else None
    return X[:cut], y[:cut], qid[:cut], held


def _fill(sizes: np.ndarray, features: int, stream):
    n = int(sizes.sum())
    qid = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    X = np.empty((n, features), np.float32)
    latent = np.empty(n, np.float32)
    starts = range(0, n, BLOCK)
    per_query, *streams = stream.spawn(1 + len(starts))
    qrng = np.random.default_rng(per_query)
    offset = qrng.standard_normal(len(sizes)).astype(np.float32)
    caps = np.resize(np.asarray(COUNT_CAPS, np.float32), COUNT_COLUMNS)
    of_query = np.floor(np.abs(qrng.standard_normal(
        (len(sizes), QUERY_COLUMNS), np.float32)) * (caps[:QUERY_COLUMNS] / 2))

    def fill(job):
        lo, sub = job
        hi = min(lo + BLOCK, n)
        rng = np.random.default_rng(sub)
        xb = X[lo:hi]
        rng.standard_normal(out=xb, dtype=np.float32)
        noise = rng.standard_normal(hi - lo, np.float32)
        q = qid[lo:hi]
        # the latent score reads the columns while all are still normal
        latent[lo:hi] = (np.float32(1.2) * xb[:, 50] + xb[:, 51] * xb[:, 52]
                         - np.float32(0.8) * np.abs(xb[:, 53])
                         + np.float32(0.5) * np.abs(xb[:, 10])
                         + np.float32(0.4) * np.abs(xb[:, 11]) * xb[:, 90]
                         + np.float32(0.6) * offset[q]
                         + np.float32(0.9) * noise)
        counts = xb[:, :COUNT_COLUMNS]
        np.abs(counts, out=counts)
        counts *= caps / 2
        np.floor(counts, out=counts)
        np.minimum(counts, caps, out=counts)
        counts[:, :QUERY_COLUMNS] = np.minimum(of_query[q],
                                               caps[:QUERY_COLUMNS])
        heavy = xb[:, COUNT_COLUMNS + 1::2]  # every second continuous column
        np.exp(heavy, out=heavy)

    with ThreadPoolExecutor(min(THREADS, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, zip(starts, streams)))
    cuts = np.quantile(latent[::16], np.cumsum(GRADE_SHARES)[:-1])
    y = np.searchsorted(cuts.astype(np.float32), latent).astype(np.float32)
    return X, y, qid


MAKERS = {"mslr_like": mslr_like}


def make(dataset: dict, seed: int, rows: int = None, held_groups: int = 0):
    return MAKERS[dataset["maker"]](dataset, seed, rows, held_groups)
