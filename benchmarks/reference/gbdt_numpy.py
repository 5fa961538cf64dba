"""The plain reference: gradient-boosted trees in numpy and float64.

No JAX and nothing of the package under test. It holds what `correct`
needs and no more:

- the LightGBM model text, parsed, and a level-wise walker over it;
- the binary objective's gradients, its log-loss, and AUC;
- histograms by `np.bincount`, the split gain and leaf output of the
  reference (`feature_histogram.hpp`: GetLeafGain, CalculateSplittedLeaf-
  Output, the reverse threshold scan), and a leaf-wise grower over them.

Scope: numerical features without missing values, no L1, no
max_delta_step, no path smoothing, no monotone constraints. That is what
the benchmark's configurations train.
"""
from __future__ import annotations

import dataclasses

import numpy as np


# ------------------------------------------------------------- model text

@dataclasses.dataclass
class Tree:
    split_feature: np.ndarray       # [L-1] int
    threshold: np.ndarray           # [L-1] float64, go left when x <= t
    left_child: np.ndarray          # [L-1] int, ~leaf when negative
    right_child: np.ndarray
    leaf_value: np.ndarray          # [L] float64
    leaf_count: np.ndarray          # [L] int
    internal_count: np.ndarray      # [L-1] int
    split_gain: np.ndarray          # [L-1] float64

    @property
    def num_leaves(self) -> int:
        return len(self.leaf_value)


def _field(block: dict, key: str, dtype) -> np.ndarray:
    text = block.get(key, "")
    return np.asarray(text.split(), dtype=dtype) if text else \
        np.zeros(0, dtype)


def parse_model(text: str) -> list:
    """The trees of a LightGBM v3 model text, in order."""
    trees = []
    for chunk in text.split("\nTree=")[1:]:
        block = dict(line.split("=", 1) for line in chunk.splitlines()[1:]
                     if "=" in line and not line.startswith("["))
        if int(block.get("num_cat", "0")):
            raise ValueError("the reference walks numerical splits only")
        trees.append(Tree(
            _field(block, "split_feature", np.int64),
            _field(block, "threshold", np.float64),
            _field(block, "left_child", np.int64),
            _field(block, "right_child", np.int64),
            _field(block, "leaf_value", np.float64),
            _field(block, "leaf_count", np.int64),
            _field(block, "internal_count", np.int64),
            _field(block, "split_gain", np.float64)))
    return trees


def leaf_of(tree: Tree, X: np.ndarray) -> np.ndarray:
    """Leaf index of every row: all rows step down one level at a time."""
    n = X.shape[0]
    if tree.num_leaves <= 1:
        return np.zeros(n, np.int64)
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    while rows.size:
        at = node[rows]
        x = X[rows, tree.split_feature[at]].astype(np.float64)
        nxt = np.where(x <= tree.threshold[at],
                       tree.left_child[at], tree.right_child[at])
        node[rows] = nxt
        rows = rows[nxt >= 0]
    return ~node


def predict_raw(trees: list, X: np.ndarray) -> np.ndarray:
    out = np.zeros(X.shape[0], np.float64)
    for tree in trees:
        out += tree.leaf_value[leaf_of(tree, X)]
    return out


# -------------------------------------------------- objective and metrics

def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def binary_init_score(y: np.ndarray) -> float:
    p = float(np.mean(y > 0))
    return float(np.log(p / (1.0 - p)))


def binary_grad_hess(y: np.ndarray, raw: np.ndarray):
    p = sigmoid(raw)
    return p - (y > 0), p * (1.0 - p)


def binary_logloss(y: np.ndarray, raw: np.ndarray) -> float:
    z = np.where(y > 0, raw, -raw)
    return float(np.mean(np.logaddexp(0.0, -z)))


def auc(y: np.ndarray, score: np.ndarray) -> float:
    order = np.argsort(-score, kind="stable")
    pos = y[order] > 0
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    ranks = np.arange(1, len(pos) + 1)
    return float(1.0 - (ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


# ------------------------------------------------- histograms and splits

def histogram(codes: np.ndarray, g: np.ndarray, h: np.ndarray,
              num_bin: int) -> np.ndarray:
    """[num_bin, 3] float64 sums of (gradient, hessian, 1) by bin code."""
    return np.stack([np.bincount(codes, w, num_bin) for w in (g, h, None)],
                    axis=1).astype(np.float64)


def leaf_output(sum_g, sum_h, lambda_l2: float):
    return -sum_g / (sum_h + lambda_l2)


def leaf_gain(sum_g, sum_h, lambda_l2: float):
    return sum_g * sum_g / (sum_h + lambda_l2)


def best_threshold(hist: np.ndarray, min_data_in_leaf: int,
                   min_sum_hessian: float, lambda_l2: float):
    """(gain over the unsplit leaf, threshold bin) of one feature's
    histogram, -inf when no threshold is allowed. Thresholds are scanned
    from the top bin down and a later one must be strictly better, as the
    reference's reverse scan does, so of equal gains the highest wins."""
    total = hist.sum(axis=0)
    left = np.cumsum(hist, axis=0)[:-1]         # rows with code <= t
    right = total - left
    ok = ((left[:, 2] >= min_data_in_leaf) & (right[:, 2] >= min_data_in_leaf)
          & (left[:, 1] >= min_sum_hessian) & (right[:, 1] >= min_sum_hessian))
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = (leaf_gain(left[:, 0], left[:, 1], lambda_l2)
                + leaf_gain(right[:, 0], right[:, 1], lambda_l2)
                - leaf_gain(total[0], total[1], lambda_l2))
    gain = np.where(ok & (gain > 0), gain, -np.inf)
    t = len(gain) - 1 - int(np.argmax(gain[::-1])) if len(gain) else 0
    return (float(gain[t]) if len(gain) else -np.inf), t


def best_split(hists: list, **limits):
    """(gain, feature, threshold bin) over every feature's histogram; of
    equal gains the first feature wins. Two features that part a leaf's
    rows alike gain the same, but their float64 sums run in another order
    and differ in the last digits: gains within 1e-10 of each other count
    as equal."""
    best = (-np.inf, -1, -1)
    for f, hist in enumerate(hists):
        gain, t = best_threshold(hist, **limits)
        if gain > (best[0] * (1 + 1e-10) if best[0] > 0 else best[0]):
            best = (gain, f, t)
    return best


def grow_tree(bins: np.ndarray, num_bins, upper_bounds, g: np.ndarray,
              h: np.ndarray, *, num_leaves: int, min_data_in_leaf: int,
              min_sum_hessian: float, lambda_l2: float = 0.0,
              shrinkage: float = 1.0, bias: float = 0.0) -> Tree:
    """Leaf-wise growth of one tree on a binned matrix: split the leaf
    whose best split gains most until `num_leaves` leaves or no gain.
    `upper_bounds[f][t]` is the real-valued threshold of bin t."""
    limits = dict(min_data_in_leaf=min_data_in_leaf,
                  min_sum_hessian=min_sum_hessian, lambda_l2=lambda_l2)
    n, num_features = bins.shape

    def leaf_hists(rows):
        return [histogram(bins[rows, f], g[rows], h[rows], int(num_bins[f]))
                for f in range(num_features)]

    rows_of = [np.arange(n)]
    splits = [best_split(leaf_hists(rows_of[0]), **limits)]
    parent_of = [(-1, False)]               # (node, is the left child)
    feat, thr, left, right, gains, counts = [], [], [], [], [], []
    while len(rows_of) < num_leaves:
        leaf = int(np.argmax([s[0] for s in splits]))
        gain, f, t = splits[leaf]
        if not np.isfinite(gain):
            break
        node = len(feat)
        rows = rows_of[leaf]
        go_left = bins[rows, f] <= t
        feat.append(f)
        thr.append(float(upper_bounds[f][t]))
        gains.append(gain)
        counts.append(len(rows))
        new_leaf = len(rows_of)
        left.append(~leaf)
        right.append(~new_leaf)
        up, was_left = parent_of[leaf]
        if up >= 0:
            (left if was_left else right)[up] = node
        rows_of[leaf] = rows[go_left]
        rows_of.append(rows[~go_left])
        parent_of[leaf] = (node, True)
        parent_of.append((node, False))
        splits[leaf] = best_split(leaf_hists(rows_of[leaf]), **limits)
        splits.append(best_split(leaf_hists(rows_of[new_leaf]), **limits))
    value = [bias + shrinkage * leaf_output(g[r].sum(), h[r].sum(), lambda_l2)
             for r in rows_of]
    return Tree(np.asarray(feat, np.int64), np.asarray(thr, np.float64),
                np.asarray(left, np.int64), np.asarray(right, np.int64),
                np.asarray(value, np.float64),
                np.asarray([len(r) for r in rows_of], np.int64),
                np.asarray(counts, np.int64), np.asarray(gains, np.float64))
