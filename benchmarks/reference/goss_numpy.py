"""The plain reference for sampled training on sparse one-hot input:
Gradient-based One-Side Sampling (GOSS) and what a tree grown on a GOSS
sample must contain, in numpy and float64. No JAX, nothing of the package
under test; `gbdt_numpy` beside it has the model text, the objective, the
histogram's split gain and the dense walker.

GOSS as the reference states it (`goss.hpp:111-147`, and the paper's
Algorithm 2), from per-row gradients g and hessians h of n rows:

    top_k   = max(1, int(n * top_rate));  other_k = int(n * other_rate)
    weight  = |g * h| (summed over the classes)
    threshold = the top_k-th largest weight
    every row with weight >= threshold is kept as it is;
    of the others, other_k are drawn at random and their g and h are
    multiplied by (n - top_k) / other_k;  a tree is grown on both.

Departures from `goss.hpp`, each deliberate:

- `goss.hpp` keeps every row AT the threshold too, so that ties enlarge the
  bag, and draws the others in one sequential pass whose probability
  adapts to what is still needed. The system under test takes exactly
  top_k rows (ties go to the lower row id) and exactly other_k others
  without replacement. `sample` here does the same with its own generator,
  so row ids never agree: `check_sample` compares PROPERTIES of a bag (its
  size, who must be in it, how many others, whether they are spread as a
  random draw is), not ids.
- The reference never sees Exclusive Feature Bundling: histograms and
  thresholds are computed per original column straight from CSC columns.
  The one thing it knows of bundles is what they lose (`bundle_keeps`):
  where a row sets two members of one bundle, the later member in the
  bundle's order keeps the row (LightGBM pushes a row's features into the
  group's one bin column in order, each write replacing the last), and the
  earlier one reads as its default. Which member that is comes from the
  bundle's member list and the raw values, never from the binned codes.
"""
from __future__ import annotations

import numpy as np

from benchmarks.reference import gbdt_numpy as ref


# ---------------------------------------------------------------- the rule

def counts(n: int, top_rate: float, other_rate: float) -> tuple:
    """(top_k, other_k) for n rows."""
    top_k = max(1, int(n * top_rate))
    return top_k, max(1, min(int(n * other_rate), n - top_k))


def weight(g: np.ndarray, h: np.ndarray) -> np.ndarray:
    """|g * h| per row; [C, n] inputs are summed over the classes."""
    w = np.abs(np.asarray(g, np.float64) * np.asarray(h, np.float64))
    return w.sum(axis=0) if w.ndim == 2 else w


def threshold(w: np.ndarray, top_k: int) -> float:
    """The top_k-th largest weight."""
    return float(np.partition(w, len(w) - top_k)[len(w) - top_k])


def multiplier(n: int, top_k: int, other_k: int) -> float:
    return (n - top_k) / other_k


def sample(w: np.ndarray, top_k: int, other_k: int, rng) -> tuple:
    """(top rows, other rows), both ascending: the top_k heaviest rows
    (of equal weights the lower row id first) and other_k of the rest,
    drawn without replacement by `rng`."""
    order = np.lexsort((np.arange(len(w)), -w))
    top = np.sort(order[:top_k])
    other = np.sort(rng.choice(order[top_k:], other_k, replace=False))
    return top, other


def check_sample(w: np.ndarray, bag: np.ndarray, is_other: np.ndarray,
                 top_k: int, other_k: int, *, tie_rtol: float,
                 blocks: int = 64, sigmas: float = 5.0) -> dict:
    """What a GOSS bag must look like, measured on one. `w`: the
    reference's float64 weights of all n rows; `bag`: row ids of the bag;
    `is_other[i]`: bag[i] was drawn as an "other" row (its gradient came
    back multiplied). Weights within `tie_rtol` of the threshold count as
    ties: the system computes its weights in float32, so which side of the
    threshold such a row falls is not the reference's to say.

    Returns counts; `ok` is the conjunction the check prints:
      bag_size == top_k + other_k, no duplicates;
      left_out == 0     rows clearly above the threshold outside the bag;
      others == other_k, top_as_other == 0 (no clearly-top row weighted);
      light_as_top == 0 (no clearly-light row kept unweighted);
      worst_block_sigmas <= sigmas: over `blocks` equal blocks of row ids,
        the number of others in a block against the binomial draw of
        other_k / (n - top_k) of the block's non-top rows."""
    n = len(w)
    thr = threshold(w, top_k)
    above = w > thr * (1.0 + tie_rtol)
    below = w < thr * (1.0 - tie_rtol)
    in_bag = np.zeros(n, bool)
    in_bag[bag] = True
    other_rows = bag[is_other]
    top_rows = bag[~is_other]
    out = {
        "threshold": thr,
        "bag_size": int(len(bag)),
        "distinct": int(in_bag.sum()),
        "ties": int(n - above.sum() - below.sum()),
        "left_out": int(np.sum(above & ~in_bag)),
        "others": int(len(other_rows)),
        "top_as_other": int(above[other_rows].sum()),
        "light_as_top": int(below[top_rows].sum()),
    }
    # are the others spread over the row ids as a random draw is?
    edges = np.linspace(0, n, blocks + 1).astype(np.int64)
    pool = np.add.reduceat((~above).astype(np.int64), edges[:-1])
    took = np.histogram(other_rows, bins=edges)[0]
    q = other_k / max(n - top_k, 1)
    sd = np.sqrt(np.maximum(pool * q * (1.0 - q), 1e-12))
    out["worst_block_sigmas"] = float(np.max(np.abs(took - pool * q) / sd))
    out["ok"] = (out["bag_size"] == top_k + other_k == out["distinct"]
                 and out["left_out"] == 0 and out["others"] == other_k
                 and out["top_as_other"] == 0 and out["light_as_top"] == 0
                 and out["worst_block_sigmas"] <= sigmas)
    return out


# --------------------------------------- histograms from sparse columns

def column_bins(values: np.ndarray, upper_bounds: np.ndarray) -> np.ndarray:
    """Bin of each value: the first bin whose upper bound is not below it
    (the reference's ValueToBin for numerical features, bin.h:457)."""
    return np.minimum(np.searchsorted(upper_bounds, values, side="left"),
                      len(upper_bounds) - 1)


def csc_histogram(csc, col: int, upper_bounds: np.ndarray, g: np.ndarray,
                  h: np.ndarray, totals: np.ndarray) -> np.ndarray:
    """[num_bin, 3] sums of (g, h, 1) by bin of one column of a scipy CSC
    matrix: the stored values are binned and summed, and every row that
    stores nothing is a zero, so the bin of 0.0 takes what is left of
    `totals` = (sum g, sum h, n)."""
    lo, hi = csc.indptr[col], csc.indptr[col + 1]
    rows = csc.indices[lo:hi]
    hist = ref.histogram(column_bins(csc.data[lo:hi], upper_bounds),
                         g[rows], h[rows], len(upper_bounds))
    hist[column_bins(np.zeros(1), upper_bounds)[0]] += \
        totals - hist.sum(axis=0)
    return hist


def bundle_keeps(bundle: np.ndarray, rank: np.ndarray,
                 claims: np.ndarray) -> np.ndarray:
    """Of ONE row's stored entries, which still read as stored once the
    columns are bundled. `bundle[i]`: the bundle of entry i's column (-1:
    a column of its own); `rank[i]`: the column's place in its bundle's
    member order; `claims[i]`: the entry's bin is not its column's default
    (an entry at its default never writes the bundle's code). Of the
    claiming entries of one bundle the one of the highest rank keeps the
    row; the others read as their default bin."""
    keeps = np.ones(len(bundle), bool)
    for b in np.unique(bundle[(bundle >= 0) & claims]):
        mine = np.flatnonzero((bundle == b) & claims)
        keeps[mine] = False
        keeps[mine[np.argmax(rank[mine])]] = True
    return keeps


# ------------------------------------------------- walking sparse rows

def _padded(csr, lo: int, hi: int) -> tuple:
    """(columns [m, k], values [m, k]) of rows lo..hi of a scipy CSR
    matrix, each row's stored entries padded to the longest row's count
    with column -1: a sparse row is looked up by comparing, never made
    dense."""
    ptr = csr.indptr[lo:hi + 1].astype(np.int64)
    lens = np.diff(ptr)
    k = int(lens.max()) if len(lens) else 0
    flat = slice(ptr[0], ptr[-1])
    if np.all(lens == k):
        return (csr.indices[flat].reshape(-1, k),
                csr.data[flat].reshape(-1, k))
    cols = np.full((hi - lo, k), -1, csr.indices.dtype)
    vals = np.zeros((hi - lo, k), csr.data.dtype)
    mask = np.arange(k) < lens[:, None]
    cols[mask], vals[mask] = csr.indices[flat], csr.data[flat]
    return cols, vals


def leaf_of_csr(tree: ref.Tree, csr, lo: int = 0, hi=None) -> np.ndarray:
    """Leaf index of rows lo..hi of a scipy CSR matrix: all rows step down
    one level at a time, as `gbdt_numpy.leaf_of` does; the value a row
    holds in its node's split column is the stored entry of that column,
    or 0.0 where it stores none."""
    hi = csr.shape[0] if hi is None else hi
    cols, vals = _padded(csr, lo, hi)
    m = hi - lo
    if tree.num_leaves <= 1:
        return np.zeros(m, np.int64)
    node = np.zeros(m, np.int64)
    rows = np.arange(m)
    while rows.size:
        at = node[rows]
        hit = cols[rows] == tree.split_feature[at][:, None]
        x = np.where(hit, vals[rows], 0).sum(axis=1, dtype=np.float64)
        nxt = np.where(x <= tree.threshold[at],
                       tree.left_child[at], tree.right_child[at])
        node[rows] = nxt
        rows = rows[nxt >= 0]
    return ~node


def predict_raw_csr(trees: list, csr, lo: int = 0, hi=None) -> np.ndarray:
    hi = csr.shape[0] if hi is None else hi
    out = np.zeros(hi - lo, np.float64)
    for tree in trees:
        out += tree.leaf_value[leaf_of_csr(tree, csr, lo, hi)]
    return out


def round_to_bits(x: np.ndarray, bits: int) -> np.ndarray:
    """x rounded to `bits` significant binary digits, the leading one
    among them: bfloat16 keeps 8, float8 (e4m3) keeps 4."""
    m, e = np.frexp(np.asarray(x, np.float64))
    return np.ldexp(np.round(m * 2.0 ** bits) / 2.0 ** bits, e)


def leaf_sums(leaf: np.ndarray, g: np.ndarray, h: np.ndarray,
              num_leaves: int) -> tuple:
    """(count, sum g, sum h) per leaf, float64."""
    return (np.bincount(leaf, None, num_leaves),
            np.bincount(leaf, g, num_leaves),
            np.bincount(leaf, h, num_leaves))
