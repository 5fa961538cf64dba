"""Seeded stand-in for HIGGS at any row count (no network, so no real rows).

Copied from `chip_smoke.make_higgs_like` (PR 21) so that the yardstick
does not move when that file does; the score is computed in the same
threads as the draw (generators/_draw.py). Standard-normal features; the
score is a fixed nonlinear function of the first 12 columns standardised
to `scale` (Bayes AUC ~0.875 at 2.4, near real HIGGS's difficulty).
"""
from __future__ import annotations

import numpy as np

from benchmarks.generators._draw import rows_and_labels


def _score(X):
    return (0.9 * X[:, 0] - 0.8 * X[:, 1] + 1.1 * X[:, 2] * X[:, 3]
            + 0.8 * np.sin(2 * X[:, 4]) * X[:, 5] + 0.6 * (X[:, 6] ** 2 - 1)
            + 0.7 * X[:, 7] * X[:, 8] * X[:, 9]
            + 0.5 * np.tanh(X[:, 10]) * X[:, 11])


def make(rows: int, seed: int, cols: int = 28, scale: float = 2.4):
    """(X [rows, cols] float32 row-major, y [rows] float32 in {0, 1})."""
    if cols < 12:
        raise ValueError("the score reads 12 columns")
    return rows_and_labels(rows, cols, seed, _score, scale)
