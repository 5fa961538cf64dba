"""Seeded stand-in for MSLR-WEB30K (MS LTR) at any row count: judged
query-document rows, 137 columns, relevance labels 0-4, rows grouped by
query. There is no network, so none of the real rows; what the published
table fixes (docs/Experiments.rst, row MS LTR: 2,270,296 x 137 in 18,919
queries, so 120 documents a query on average) is kept, and everything
else is ASSUMED, here:

* query sizes: a log-normal with mean 120 and sigma 0.75 of the log
  (median ~90, E[size^2] ~ 2.5e4), rounded, clipped to 1..1,251 (the
  fold's largest query), then fitted to the asked row count one document
  at a time, so rows and queries are both exact;
* columns: the 136 published features are statistics of five text streams
  (counts, sums, minima, maxima, means, variances of term frequencies,
  BM25 and language-model scores) and a few page-quality numbers; the
  doc's table says 137. Here: 48 dense reals, 64 zero-heavy counts with
  many ties (max(0, round(1.5 x - 1)): ~60 % zeros, a dozen distinct
  values), 8 booleans, 17 more dense reals. No column is invented beyond
  the 137;
* relevance: a fixed nonlinear function of 13 of those columns (dense,
  count and boolean ones), standardised, weighted `signal` against
  per-document noise, plus a per-query shift (sigma `query_sigma`) so
  that queries differ in how many relevant documents they hold; labels
  by four thresholds on that latent value, set so that the shares are
  ~52 / 32 / 13 / 2 / 1 % overall. With signal 0.85, 255-leaf trees
  reach a held-out NDCG@10 of ~0.5-0.6 at 20 trees (the published model
  reaches 0.524 at 500 on the real table).

Rows are drawn in 64 fixed blocks, each from its own spawned stream and in
its own thread (as generators/_draw.py), so the same seed gives the same
table on any number of cores.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist

import numpy as np

from benchmarks.generators._draw import BLOCKS

MEAN_SIZE, SIGMA_LOG, MIN_SIZE, MAX_SIZE = 120.0, 0.75, 1, 1251
SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)         # labels 0..4
COUNTS, BOOLS = slice(48, 112), slice(112, 120)    # dense: 0-47, 120-136
MIN_COLS = 120


def query_sizes(rng, queries: int, rows: int | None = None) -> np.ndarray:
    """`queries` sizes in 1..1,251 from the clipped log-normal; with
    `rows`, fitted to sum to it exactly."""
    raw = rng.lognormal(np.log(MEAN_SIZE) - 0.5 * SIGMA_LOG ** 2, SIGMA_LOG,
                        queries)
    if rows is None:
        return np.clip(np.rint(raw), MIN_SIZE, MAX_SIZE).astype(np.int64)
    if not queries * MIN_SIZE <= rows <= queries * MAX_SIZE:
        raise ValueError(f"{rows} rows do not fit {queries} queries")
    sizes = np.clip(np.rint(raw * (rows / raw.sum())), MIN_SIZE,
                    MAX_SIZE).astype(np.int64)
    while (gap := rows - int(sizes.sum())) != 0:
        step = 1 if gap > 0 else -1
        room = np.flatnonzero((sizes + step >= MIN_SIZE)
                              & (sizes + step <= MAX_SIZE))
        sizes[rng.choice(room, min(abs(gap), len(room)), replace=False)] += step
    return sizes


def _relevance(X):
    """The fixed function of 13 columns the labels follow."""
    c0, c1, c2 = X[:, 48], np.minimum(X[:, 49], 3.0), X[:, 50]
    return (0.9 * X[:, 0] - 0.7 * X[:, 1] + 0.8 * X[:, 2] * X[:, 3]
            + 0.6 * np.sin(2 * X[:, 4]) * X[:, 5] + 0.5 * (X[:, 6] ** 2 - 1)
            + 0.35 * c0 + 0.3 * c1 - 0.25 * c2 * (X[:, 7] > 0)
            + 0.8 * X[:, 112] + 0.4 * X[:, 113] * X[:, 0])


def make(rows: int, queries: int, heldout_queries: int, seed: int,
         cols: int = 137, signal: float = 0.7, query_sigma: float = 0.5):
    """(X [n, cols] float32 row-major, y [n] int32 in 0..4, group, heldout
    group): the `rows` training rows of `queries` queries first, then the
    rows of `heldout_queries` more queries; the two `group` vectors hold
    the query sizes in row order."""
    if cols < MIN_COLS:
        raise ValueError(f"the column kinds take {MIN_COLS} columns")
    seq = np.random.SeedSequence(seed)
    layout, *streams = seq.spawn(BLOCKS + 1)
    rng = np.random.default_rng(layout)
    group = query_sizes(rng, queries, rows)
    held = query_sizes(rng, heldout_queries)
    sizes = np.concatenate([group, held])
    n = int(sizes.sum())
    shift = np.repeat(rng.normal(0.0, query_sigma, len(sizes))
                      .astype(np.float32), sizes)

    X = np.empty((n, cols), np.float32)
    rel = np.empty(n, np.float32)
    noise = np.empty(n, np.float32)
    edges = np.linspace(0, n, BLOCKS + 1).astype(np.int64)

    def fill(i):
        rng = np.random.default_rng(streams[i])
        lo, hi = edges[i], edges[i + 1]
        Xb = X[lo:hi]
        rng.standard_normal(out=Xb, dtype=np.float32)
        counts = Xb[:, COUNTS]
        np.multiply(counts, 1.5, out=counts)
        counts -= 1.0
        np.rint(counts, out=counts)
        np.maximum(counts, 0.0, out=counts)
        bools = Xb[:, BOOLS]
        bools[...] = bools > 0.8
        rel[lo:hi] = _relevance(Xb)
        noise[lo:hi] = rng.standard_normal(hi - lo, dtype=np.float32)

    with ThreadPoolExecutor(len(os.sched_getaffinity(0))) as pool:
        list(pool.map(fill, range(BLOCKS)))
    mean, std = rel.mean(dtype=np.float64), rel.std(dtype=np.float64)
    latent = (rel - np.float32(mean)) * np.float32(signal / std) \
        + noise * np.float32(np.sqrt(1.0 - signal ** 2)) + shift
    spread = NormalDist(0.0, float(np.sqrt(1.0 + query_sigma ** 2)))
    cuts = [spread.inv_cdf(p) for p in np.cumsum(SHARES)[:-1]]
    y = np.searchsorted(np.asarray(cuts, np.float32), latent).astype(np.int32)
    return X, y, group, held
