"""The work a LambdaRank gradient needs, whatever implements it, computed
from the run's query sizes and labels alone. Kept with the benchmark,
beside harness/work.py, so that no PR that claims a gain can change what
the scope `lgbm.rank_grad` is held against.

The pair terms are compute (an exponential and two divisions a pair), so
the scope's honest yardstick is time a pair (`label_pairs`); its memory
roofline (`rank_grad_bytes`) is the least the step must move and reads
far below 1 %, by design: it says how far from free the pair work is.
"""
from __future__ import annotations

import numpy as np

F32 = 4


def label_pairs(group, label) -> int:
    """Pairs of documents of one query whose labels differ, summed over
    the queries: what one iteration's gradient sums over. Per query,
    (size^2 - sum over labels of count^2) / 2."""
    group = np.asarray(group, np.int64)
    lab = np.asarray(label).astype(np.int64)
    levels = int(lab.max()) + 1 if len(lab) else 1
    query = np.repeat(np.arange(len(group)), group)
    counts = np.bincount(query * levels + lab,
                         minlength=len(group) * levels)
    same = np.sum(counts.reshape(len(group), levels) ** 2, axis=1)
    return int(np.sum(group * group - same) // 2)


def rank_grad_bytes(rows: int, queries: int, rounds: int) -> int:
    """One round reads every row's score and label and writes its
    gradient and hessian (16 B a row), and reads each query's place and
    inverse max DCG (8 B a query)."""
    return rounds * (4 * F32 * rows + 2 * F32 * queries)
