"""Seeded stand-in for a wide dense table such as Epsilon (400,000 x 2,000).

Standard-normal features, which is what Epsilon's standardised columns
look like to a binner. The score is sparse: `informative` of the columns
carry it (three in four enter linearly, the rest as products of pairs) and
it is standardised to `scale`; which columns and which weights is fixed by
`problem_seed`, so every `seed` draws new rows of the same problem
(generators/_draw.py). Most columns carry nothing, as in any wide table,
and the split search has to find the ones that do.
"""
from __future__ import annotations

import numpy as np

from benchmarks.generators._draw import rows_and_labels


def make(rows: int, seed: int, cols: int = 2000, informative: int = 48,
         scale: float = 4.0, problem_seed: int = 2000):
    """(X [rows, cols] float32 row-major, y [rows] float32 in {0, 1})."""
    problem = np.random.default_rng(problem_seed)
    used = problem.choice(cols, informative, replace=False)
    n_lin = 3 * informative // 4
    lin, pairs = used[:n_lin], used[n_lin:].reshape(-1, 2)
    w_lin = problem.standard_normal(n_lin).astype(np.float32)
    w_pair = (1.5 * problem.standard_normal(len(pairs))).astype(np.float32)

    def score(X):
        return X[:, lin] @ w_lin + (X[:, pairs[:, 0]] * X[:, pairs[:, 1]]) @ w_pair

    return rows_and_labels(rows, cols, seed, score, scale)
