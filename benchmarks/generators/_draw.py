"""What the data generators share: standard-normal rows drawn in 64 fixed
blocks, each from its own spawned stream and in its own thread, so that the
same seed gives the same rows on any number of cores; and labels DRAWN
from p = sigmoid(s(x)) with the score s standardised to `scale`, so that a
broken split search visibly loses."""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCKS = 64


def rows_and_labels(rows: int, cols: int, seed: int, score, scale: float):
    """(X [rows, cols] float32 row-major, y [rows] float32 in {0, 1});
    `score(block)` gives the raw score of a block of rows."""
    X = np.empty((rows, cols), np.float32)
    u = np.empty(rows, np.float32)
    s = np.empty(rows, np.float32)
    edges = np.linspace(0, rows, BLOCKS + 1).astype(np.int64)
    streams = np.random.SeedSequence(seed).spawn(BLOCKS)

    def fill(i):
        rng = np.random.default_rng(streams[i])
        lo, hi = edges[i], edges[i + 1]
        rng.standard_normal(out=X[lo:hi], dtype=np.float32)
        u[lo:hi] = rng.random(hi - lo, dtype=np.float32)
        s[lo:hi] = score(X[lo:hi])

    with ThreadPoolExecutor(len(os.sched_getaffinity(0))) as pool:
        list(pool.map(fill, range(BLOCKS)))
    mean, std = s.mean(dtype=np.float64), s.std(dtype=np.float64)
    s = (s - np.float32(mean)) * np.float32(scale / std)
    y = (u < 1.0 / (1.0 + np.exp(-s))).astype(np.float32)
    return X, y
