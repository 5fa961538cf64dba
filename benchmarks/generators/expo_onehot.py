"""Seeded stand-in for Expo, LightGBM's airline on-time table (11,000,000 x
700, the categorical fields one-hot encoded; no network, so no real rows).

A row is a flight: two numeric columns and six categorical fields, each
field one-hot over its levels, so a row stores exactly 8 values of 700:

    column 0        DepTime, hours in (0, 24]
    column 1        Distance, hundreds of miles, log-normal
    2 .. 13         Month        12 levels
    14 .. 44        DayofMonth   31
    45 .. 51        DayOfWeek     7
    52 .. 73        Carrier      22
    74 .. 386       Origin      313
    387 .. 699      Dest        313

Levels are Zipf-skewed, `1 / (rank + 1) ** a` with the field's own `a`:
months and days nearly even, carriers and airports as uneven as real ones
(the busiest airport holds a sixth of the flights, the 313th one in two
thousand). The field list, the cardinalities and the skews are ASSUMED
(configs/expo700goss.json): the published table says 700 columns and
"one-hot", no more.

Labels are DRAWN from p = sigmoid(s): s is a per-level effect of every
field, a Carrier x Month and a DayOfWeek x departure-hour interaction, a
daily wave of DepTime and a slope in log Distance, standardised over the
draw to `scale` (Bayes AUC ~0.80 at 1.4; the published model reaches
0.777). Which levels carry which effect is fixed by `problem_seed`, so
every `seed` draws new flights of the same problem. Rows are drawn in the
64 fixed blocks of generators/_draw.py's scheme, each from its own spawned
stream and in its own thread: the same seed gives the same rows on any
number of cores.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

from benchmarks.generators._draw import BLOCKS

FIELDS = (("Month", 12, 0.1), ("DayofMonth", 31, 0.05), ("DayOfWeek", 7, 0.05),
          ("Carrier", 22, 0.8), ("Origin", 313, 1.0), ("Dest", 313, 1.0))
NUMERIC = 2
STORED = NUMERIC + len(FIELDS)      # values a row stores
COLS = NUMERIC + sum(card for _, card, _ in FIELDS)


def field_offsets() -> np.ndarray:
    """First column of each field's indicators."""
    cards = [card for _, card, _ in FIELDS]
    return NUMERIC + np.concatenate([[0], np.cumsum(cards)[:-1]])


def _problem(problem_seed: int):
    rng = np.random.default_rng(problem_seed)
    effects = [rng.standard_normal(card) * w for (_, card, _), w in
               zip(FIELDS, (0.5, 0.15, 0.4, 0.9, 0.7, 0.5))]
    carrier_month = 0.6 * rng.standard_normal((22, 12))
    dow_hour = 0.5 * rng.standard_normal((7, 24))
    return effects, carrier_month, dow_hour


def make(rows: int, seed: int, cols: int = COLS, scale: float = 1.4,
         problem_seed: int = 700):
    """(X scipy CSR float32 [rows, 700] with 8 stored values a row,
    y [rows] float32 in {0, 1})."""
    if cols != COLS:
        raise ValueError(f"the field list makes {COLS} columns, not {cols}")
    effects, carrier_month, dow_hour = _problem(problem_seed)
    cdfs = []
    for _, card, a in FIELDS:
        p = 1.0 / np.arange(1, card + 1) ** a
        cdfs.append(np.cumsum(p / p.sum()))
    offsets = field_offsets()

    indices = np.empty((rows, STORED), np.int32)
    data = np.ones((rows, STORED), np.float32)
    indices[:, 0], indices[:, 1] = 0, 1
    u = np.empty(rows, np.float32)
    s = np.empty(rows, np.float32)
    edges = np.linspace(0, rows, BLOCKS + 1).astype(np.int64)
    streams = np.random.SeedSequence(seed).spawn(BLOCKS)

    def fill(i):
        rng = np.random.default_rng(streams[i])
        lo, hi = edges[i], edges[i + 1]
        m = hi - lo
        dep = 24.0 * (1.0 - rng.random(m, dtype=np.float32))     # (0, 24]
        logd = rng.standard_normal(m, dtype=np.float32)
        data[lo:hi, 0] = dep
        data[lo:hi, 1] = np.exp(1.6 + 0.7 * logd)
        lev = []
        for f, cdf in enumerate(cdfs):
            lv = np.minimum(np.searchsorted(cdf, rng.random(m)), len(cdf) - 1)
            indices[lo:hi, NUMERIC + f] = offsets[f] + lv
            lev.append(lv)
        u[lo:hi] = rng.random(m, dtype=np.float32)
        hour = np.minimum(dep.astype(np.int64), 23)
        score = (0.6 * np.sin(dep * (2 * np.pi / 24.0) - 2.0) + 0.3 * logd
                 + carrier_month[lev[3], lev[0]] + dow_hour[lev[2], hour])
        for f, e in enumerate(effects):
            score += e[lev[f]]
        s[lo:hi] = score

    with ThreadPoolExecutor(len(os.sched_getaffinity(0))) as pool:
        list(pool.map(fill, range(BLOCKS)))
    mean, std = s.mean(dtype=np.float64), s.std(dtype=np.float64)
    s = (s - np.float32(mean)) * np.float32(scale / std)
    y = (u < 1.0 / (1.0 + np.exp(-s))).astype(np.float32)
    X = sp.csr_matrix(
        (data.reshape(-1), indices.reshape(-1),
         np.arange(0, rows * STORED + 1, STORED, dtype=np.int32)),
        shape=(rows, COLS))
    X.has_sorted_indices = True
    return X, y
