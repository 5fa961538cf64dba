"""LambdaRank-NDCG gradients and NDCG@k in plain numpy: float64, one query
at a time, every pair written out. The reference `correct` and the tests
hold the device program to; it imports nothing from `lightgbm_tpu`. One
query is one `[M, M]` matrix of pairs: no bucket, no padding, no chunk.

The equations are LightGBM 3.0's `rank_objective.hpp`
(`LambdarankNDCG::GetGradientsForOneQuery`), as ISSUE 34 writes them
out. For one query with documents d = 0..M-1, scores s_d, integer labels
l_d, gain g(l) = 2^l - 1 (or `label_gain[l]`), discount
D(r) = 1 / log2(r + 2), sigma = `sigmoid`, T = the truncation level:

  r_d      position of d after a STABLE sort by score descending (ties
           keep row order); best = s at r = 0, worst = s at r = M - 1
  maxDCG_T sum over the T largest labels, sorted descending, of
           g(l) D(position); inv = 1 / maxDCG_T if it is > 0, else 0.
           T enters nowhere else: every pair counts
  pair     (h, w) with l_h > l_w: delta = s_h - s_w;
           dN = (g(l_h) - g(l_w)) |D(r_h) - D(r_w)| inv;
           if norm and best != worst: dN /= 0.01 + |delta|;
           rho = 1 / (1 + exp(sigma delta));
           lam = -sigma dN rho; hes = sigma^2 dN rho (1 - rho)
  sums     grad_h += lam, grad_w -= lam, hess_h += hes, hess_w += hes;
           S = sum over pairs of -2 lam
  norm     if norm and S > 0: every grad and hess of the query is
           multiplied by log2(1 + S) / S

Departures from the upstream binary: it reads the sigmoid from a
1M-entry table over a clipped range, this computes it (SURVEY.md: the
table exists for the CPU's sake); documents whose score is upstream's
`kMinScore` sentinel do not occur here.

`terms=` lets a test or a control round every pair term before it is
summed (bf16: what a lower precision would give); `truncation` moves
only `inv`.
"""
from __future__ import annotations

import numpy as np


def label_gain(max_label: int = 31) -> np.ndarray:
    """g(l) = 2^l - 1 for l = 0..max_label - 1."""
    return np.power(2.0, np.arange(max_label)) - 1.0


def discount(ranks) -> np.ndarray:
    """D(r) = 1 / log2(r + 2), r counted from 0."""
    return 1.0 / np.log2(np.asarray(ranks, np.float64) + 2.0)


def max_dcg(labels: np.ndarray, truncation: int, gain: np.ndarray) -> float:
    """DCG of the ideal order, cut at `truncation` positions."""
    top = np.sort(np.asarray(labels).astype(np.int64))[::-1][:truncation]
    return float(np.sum(gain[top] * discount(np.arange(len(top)))))


def round_bf16(x: np.ndarray) -> np.ndarray:
    """x rounded to bfloat16's 8 significant bits (round to nearest
    even on the float32 pattern), as float64."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def query_gradients(score: np.ndarray, label: np.ndarray, *,
                    sigmoid: float = 1.0, truncation: int = 30,
                    norm: bool = True, gain: np.ndarray | None = None,
                    terms=None) -> tuple:
    """(grad [M], hess [M], pairs) of one query, float64; `pairs` is
    the number of pairs with different labels. Row h, column w of every
    matrix below is the pair (h, w); only those with l_h > l_w count."""
    gain = label_gain() if gain is None else np.asarray(gain, np.float64)
    s = np.asarray(score, np.float64)
    lab = np.asarray(label).astype(np.int64)
    m = len(s)
    order = np.argsort(-s, kind="stable")
    rank = np.empty(m, np.int64)
    rank[order] = np.arange(m)
    best, worst = s[order[0]], s[order[-1]]
    top = max_dcg(lab, truncation, gain)
    inv = 1.0 / top if top > 0 else 0.0
    disc = discount(rank)

    is_pair = lab[:, None] > lab[None, :]
    delta = s[:, None] - s[None, :]
    d_ndcg = (gain[lab][:, None] - gain[lab][None, :]) \
        * np.abs(disc[:, None] - disc[None, :]) * inv
    if norm and best != worst:
        d_ndcg = d_ndcg / (0.01 + np.abs(delta))
    with np.errstate(over="ignore"):
        rho = 1.0 / (1.0 + np.exp(sigmoid * delta))
    lam = np.where(is_pair, -sigmoid * d_ndcg * rho, 0.0)
    hes = np.where(is_pair, sigmoid * sigmoid * d_ndcg * rho * (1.0 - rho),
                   0.0)
    if terms is not None:
        lam, hes = terms(lam), terms(hes)
    grad = lam.sum(axis=1) - lam.sum(axis=0)        # += as h, -= as w
    hess = hes.sum(axis=1) + hes.sum(axis=0)
    total = -2.0 * lam.sum()
    if norm and total > 0:
        factor = np.log2(1.0 + total) / total
        grad = grad * factor
        hess = hess * factor
    return grad, hess, int(is_pair.sum())


def gradients(score: np.ndarray, label: np.ndarray, group: np.ndarray,
              queries=None, **kw) -> tuple:
    """(grad [n], hess [n]) over whole queries; `group` holds the query
    sizes in row order. With `queries` (indices), only those are
    computed and every other row reads NaN."""
    bounds = np.concatenate([[0], np.cumsum(np.asarray(group, np.int64))])
    grad = np.full(bounds[-1], np.nan)
    hess = np.full(bounds[-1], np.nan)
    for q in (range(len(group)) if queries is None else queries):
        lo, hi = bounds[q], bounds[q + 1]
        grad[lo:hi], hess[lo:hi], _ = query_gradients(
            score[lo:hi], label[lo:hi], **kw)
    return grad, hess


def count_pairs(label: np.ndarray, group: np.ndarray) -> int:
    """Pairs of documents of one query with different labels, by brute
    force: what every iteration's gradient sums over."""
    bounds = np.concatenate([[0], np.cumsum(np.asarray(group, np.int64))])
    total = 0
    for q in range(len(group)):
        lab = np.asarray(label[bounds[q]:bounds[q + 1]]).astype(np.int64)
        total += int(np.sum(lab[:, None] > lab[None, :]))
    return total


def ndcg_at_k(score: np.ndarray, label: np.ndarray, group: np.ndarray,
              k: int, queries=None, gain: np.ndarray | None = None) -> float:
    """Mean over queries of DCG@k / maxDCG@k, documents ordered by a
    stable sort of the score descending; a query whose maxDCG@k is 0
    (no relevant document) counts 1, as LightGBM's NDCG metric does."""
    gain = label_gain() if gain is None else np.asarray(gain, np.float64)
    bounds = np.concatenate([[0], np.cumsum(np.asarray(group, np.int64))])
    queries = range(len(group)) if queries is None else queries
    total = 0.0
    for q in queries:
        lo, hi = bounds[q], bounds[q + 1]
        lab = np.asarray(label[lo:hi]).astype(np.int64)
        top = max_dcg(lab, k, gain)
        if top <= 0:
            total += 1.0
            continue
        order = np.argsort(-np.asarray(score[lo:hi], np.float64),
                           kind="stable")[:k]
        total += float(np.sum(gain[lab[order]]
                              * discount(np.arange(len(order))))) / top
    return total / len(queries)
