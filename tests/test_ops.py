"""Unit tests for the core ops against sequential numpy oracles.

The oracles are independent re-implementations of the reference
semantics (feature_histogram.hpp scan loops, data_partition.hpp,
tree.h decisions) written as plain per-element loops, mirroring the
role of GPU_DEBUG_COMPARE in the reference GPU learner.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops import split as S
from lightgbm_tpu.ops import partition as P
from lightgbm_tpu.ops import traverse as T

K_EPS = 1e-15


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------

def _np_hist(bins, grad, hess, B):
    f = bins.shape[1]
    out = np.zeros((f, B, 2), dtype=np.float64)
    for i in range(bins.shape[0]):
        for j in range(f):
            out[j, bins[i, j], 0] += grad[i]
            out[j, bins[i, j], 1] += hess[i]
    return out


def test_histogram_scatter_matches_numpy(rng):
    n, f, B = 500, 7, 16
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = rng.rand(n).astype(np.float32)
    got = np.asarray(H.histogram_scatter(jnp.asarray(bins), jnp.asarray(grad),
                                         jnp.asarray(hess), B))
    want = _np_hist(bins, grad, hess, B)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_leaf_histogram_respects_count(rng):
    n, f, B = 300, 4, 8
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = np.ones(n, dtype=np.float32)
    perm = rng.permutation(n).astype(np.int32)
    start, count, cap = 37, 100, 128
    rows = perm[start:start + count]
    want = _np_hist(bins[rows], grad[rows], hess[rows], B)
    got = np.asarray(H.leaf_histogram(jnp.asarray(bins), jnp.asarray(perm),
                                      start, count, jnp.asarray(grad),
                                      jnp.asarray(hess), cap, B))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# numerical split scan oracle — sequential transliteration of
# FindBestThresholdSequentially semantics
# ---------------------------------------------------------------------------

def _np_leaf_output(g, h, l1, l2):
    if l1 > 0:
        s = np.sign(g) * max(0.0, abs(g) - l1)
    else:
        s = g
    return -s / (h + l2)


def _np_gain_out(g, h, l1, l2, out):
    if l1 > 0:
        g = np.sign(g) * max(0.0, abs(g) - l1)
    return -(2.0 * g * out + (h + l2) * out * out)


def _np_best_numerical(hist, num_bin, missing_type, default_bin,
                       sum_g, sum_h, num_data, cfg):
    """Oracle: evaluate every (threshold, direction) candidate."""
    sh = sum_h + 2 * K_EPS
    cnt_factor = num_data / sh
    g = hist[:, 0].astype(np.float64)
    h = hist[:, 1].astype(np.float64)
    cnt = np.floor(h * cnt_factor + 0.5).astype(np.int64)
    two_scan = num_bin > 2 and missing_type != S.MISSING_NONE
    if missing_type == S.MISSING_NAN:
        miss = num_bin - 1
    elif missing_type == S.MISSING_ZERO:
        miss = default_bin
    else:
        miss = -1

    gain_shift = _np_gain_out(sum_g, sh, cfg.lambda_l1, cfg.lambda_l2,
                              _np_leaf_output(sum_g, sh, cfg.lambda_l1,
                                              cfg.lambda_l2))
    min_gain_shift = gain_shift + cfg.min_gain_to_split

    best = (-np.inf, -1, None)
    directions = [(True, True), (False, True)] if two_scan else [(True, False)]
    for dl, use_excl in directions:
        for t in range(num_bin - 1):
            if use_excl and missing_type == S.MISSING_ZERO:
                if (not dl and t == default_bin) or (dl and t == default_bin - 1):
                    continue
            ar = np.arange(num_bin)
            if dl:
                # reverse scan: right side accumulated from the top;
                # missing implicitly joins the left complement
                rsel = ar > t
                if use_excl:
                    rsel = rsel & (ar != miss)
                rg = g[rsel].sum()
                rh = h[rsel].sum() + K_EPS
                rc = cnt[rsel].sum()
                lg, lh, lc = sum_g - rg, sh - rh, num_data - rc
            else:
                lsel = ar <= t
                if use_excl:
                    lsel = lsel & (ar != miss)
                lg = g[lsel].sum()
                lh = h[lsel].sum() + K_EPS
                lc = cnt[lsel].sum()
                rg, rh, rc = sum_g - lg, sh - lh, num_data - lc
            if lc < cfg.min_data_in_leaf or rc < cfg.min_data_in_leaf:
                continue
            if lh < cfg.min_sum_hessian_in_leaf or rh < cfg.min_sum_hessian_in_leaf:
                continue
            ol = _np_leaf_output(lg, lh, cfg.lambda_l1, cfg.lambda_l2)
            orr = _np_leaf_output(rg, rh, cfg.lambda_l1, cfg.lambda_l2)
            gain = (_np_gain_out(lg, lh, cfg.lambda_l1, cfg.lambda_l2, ol)
                    + _np_gain_out(rg, rh, cfg.lambda_l1, cfg.lambda_l2, orr))
            if gain <= min_gain_shift:
                continue
            if gain > best[0]:
                best = (gain, t, dl)
    if best[1] < 0:
        return None
    return best[0] - min_gain_shift, best[1], best[2]


def _run_split(hist_np, num_bin, missing_type, default_bin, sum_g, sum_h,
               num_data, cfg):
    f = hist_np.shape[0]
    meta = S.FeatureMeta.build(
        num_bin=[num_bin] * f, missing_type=[missing_type] * f,
        default_bin=[default_bin] * f, is_categorical=[False] * f,
        monotone=[0] * f, penalty=[1.0] * f)
    return S.numerical_split_scan(
        jnp.asarray(hist_np, jnp.float32), meta, cfg,
        jnp.float32(sum_g), jnp.float32(sum_h), jnp.int32(num_data),
        jnp.float32(0.0), jnp.float32(-np.inf), jnp.float32(np.inf))


@pytest.mark.parametrize("missing_type,default_bin", [
    (S.MISSING_NONE, 0), (S.MISSING_ZERO, 3), (S.MISSING_ZERO, 0),
    (S.MISSING_NAN, 0),
])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_numerical_split_matches_oracle(missing_type, default_bin, seed):
    rng = np.random.RandomState(seed)
    num_bin, n = 12, 4000
    bins = rng.randint(0, num_bin, size=n)
    grad = rng.randn(n)
    hess = np.ones(n)
    hist = np.zeros((num_bin, 2))
    np.add.at(hist[:, 0], bins, grad)
    np.add.at(hist[:, 1], bins, hess)
    sum_g, sum_h = grad.sum(), hess.sum()
    cfg = S.SplitConfig(min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3)

    want = _np_best_numerical(hist, num_bin, missing_type, default_bin,
                              sum_g, sum_h, n, cfg)
    res = _run_split(hist[None], num_bin, missing_type, default_bin,
                     sum_g, sum_h, n, cfg)
    if want is None:
        assert not bool(res["found"][0])
        return
    assert bool(res["found"][0])
    np.testing.assert_allclose(float(res["gain"][0]), want[0],
                               rtol=2e-3, atol=1e-3)
    assert int(res["threshold"][0]) == want[1]
    assert bool(res["default_left"][0]) == want[2]


def test_split_respects_min_data():
    # one dominant bin: every cut leaves <min_data on one side
    num_bin = 5
    hist = np.zeros((num_bin, 2))
    hist[2] = [-50.0, 95.0]
    hist[0] = [1.0, 2.0]
    hist[4] = [1.5, 3.0]
    cfg = S.SplitConfig(min_data_in_leaf=10)
    res = _run_split(hist[None], num_bin, S.MISSING_NONE, 0,
                     hist[:, 0].sum(), hist[:, 1].sum(), 100, cfg)
    assert not bool(res["found"][0])


def test_split_l1_l2_change_gain(rng):
    num_bin, n = 8, 1000
    bins = rng.randint(0, num_bin, size=n)
    grad = rng.randn(n)
    hess = np.ones(n)
    hist = np.zeros((num_bin, 2))
    np.add.at(hist[:, 0], bins, grad)
    np.add.at(hist[:, 1], bins, hess)
    for l1, l2 in [(0.0, 0.0), (0.5, 0.0), (0.0, 5.0), (1.0, 2.0)]:
        cfg = S.SplitConfig(lambda_l1=l1, lambda_l2=l2, min_data_in_leaf=5)
        want = _np_best_numerical(hist, num_bin, S.MISSING_NONE, 0,
                                  grad.sum(), hess.sum(), n, cfg)
        res = _run_split(hist[None], num_bin, S.MISSING_NONE, 0,
                         grad.sum(), hess.sum(), n, cfg)
        assert bool(res["found"][0]) == (want is not None)
        if want:
            np.testing.assert_allclose(float(res["gain"][0]), want[0],
                                       rtol=2e-3, atol=1e-3)
            assert int(res["threshold"][0]) == want[1]


def test_split_left_right_sums_consistent(rng):
    num_bin, n = 10, 2000
    bins = rng.randint(0, num_bin, size=n)
    grad = rng.randn(n)
    hess = np.full(n, 0.25)
    hist = np.zeros((num_bin, 2))
    np.add.at(hist[:, 0], bins, grad)
    np.add.at(hist[:, 1], bins, hess)
    cfg = S.SplitConfig(min_data_in_leaf=10)
    res = _run_split(hist[None], num_bin, S.MISSING_NONE, 0,
                     grad.sum(), hess.sum(), n, cfg)
    assert bool(res["found"][0])
    t = int(res["threshold"][0])
    lg_want = hist[:t + 1, 0].sum()
    np.testing.assert_allclose(float(res["left_sum_gradient"][0]), lg_want,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        float(res["left_sum_gradient"][0]) + float(res["right_sum_gradient"][0]),
        grad.sum(), rtol=1e-4, atol=1e-4)
    assert (int(res["left_count"][0]) + int(res["right_count"][0])) == n


# ---------------------------------------------------------------------------
# partition
# ---------------------------------------------------------------------------

def test_partition_stable_and_counts(rng):
    n, f, B = 400, 3, 16
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    perm = rng.permutation(n).astype(np.int32)
    start, count, cap = 50, 200, 256
    feat, thr = 1, 7
    window = perm[start:start + count]
    go_left = bins[window, feat] <= thr
    want_left = window[go_left]
    want_right = window[~go_left]

    new_perm, lc = P.partition_leaf(
        jnp.asarray(bins), jnp.asarray(perm), start, count, feat, thr,
        False, -1, False, jnp.zeros(8, jnp.uint32), cap)
    new_perm = np.asarray(new_perm)
    assert int(lc) == len(want_left)
    np.testing.assert_array_equal(new_perm[start:start + len(want_left)],
                                  want_left)
    np.testing.assert_array_equal(
        new_perm[start + len(want_left):start + count], want_right)
    # outside the window untouched
    np.testing.assert_array_equal(new_perm[:start], perm[:start])
    np.testing.assert_array_equal(new_perm[start + count:], perm[start + count:])


def test_partition_window_past_end(rng):
    """Leaf near the end of perm: read window gets clamped left; rows of
    other leaves must stay untouched (code-review regression)."""
    n, f, B = 300, 3, 16
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    perm = rng.permutation(n).astype(np.int32)
    start, count, cap = 250, 50, 128
    feat, thr = 0, 8
    window = perm[start:start + count]
    want_left = window[bins[window, feat] <= thr]
    new_perm, lc = P.partition_leaf(
        jnp.asarray(bins), jnp.asarray(perm), start, count, feat, thr,
        False, -1, False, jnp.zeros(8, jnp.uint32), cap)
    new_perm = np.asarray(new_perm)
    assert int(lc) == len(want_left)
    np.testing.assert_array_equal(new_perm[:start], perm[:start])
    np.testing.assert_array_equal(new_perm[start:start + len(want_left)],
                                  want_left)


def test_partition_capacity_exceeds_n(rng):
    n, f, B = 100, 2, 8
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    perm = rng.permutation(n).astype(np.int32)
    start, count, cap = 60, 40, 256
    window = perm[start:start + count]
    want_left = window[bins[window, 1] <= 3]
    new_perm, lc = P.partition_leaf(
        jnp.asarray(bins), jnp.asarray(perm), start, count, 1, 3,
        False, -1, False, jnp.zeros(8, jnp.uint32), cap)
    new_perm = np.asarray(new_perm)
    assert int(lc) == len(want_left)
    np.testing.assert_array_equal(new_perm[:start], perm[:start])
    np.testing.assert_array_equal(new_perm[start:start + len(want_left)],
                                  want_left)


def test_leaf_histogram_window_past_end(rng):
    n, f, B = 300, 4, 8
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = np.ones(n, dtype=np.float32)
    perm = rng.permutation(n).astype(np.int32)
    for start, count, cap in [(250, 50, 128), (60, 40, 512)]:
        rows = perm[start:start + count]
        want = _np_hist(bins[rows], grad[rows], hess[rows], B)
        got = np.asarray(H.leaf_histogram(jnp.asarray(bins), jnp.asarray(perm),
                                          start, count, jnp.asarray(grad),
                                          jnp.asarray(hess), cap, B))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_partition_missing_default_left(rng):
    n, f, B = 100, 2, 8
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    perm = np.arange(n, dtype=np.int32)
    miss_bin, thr = 7, 3
    new_perm, lc = P.partition_leaf(
        jnp.asarray(bins), jnp.asarray(perm), 0, n, 0, thr,
        True, miss_bin, False, jnp.zeros(8, jnp.uint32), 128)
    b0 = bins[:, 0]
    want_left = ((b0 <= thr) | (b0 == miss_bin)).sum()
    assert int(lc) == want_left


# ---------------------------------------------------------------------------
# traversal
# ---------------------------------------------------------------------------

def _mk_tree():
    """Two-split tree: node0 (f0 <= 3) -> [node1, leaf1];
    node1 (f1 <= 5) -> [leaf0, leaf2]. Leaf ids via ~leaf convention."""
    return dict(
        split_feature=jnp.asarray([0, 1], jnp.int32),
        threshold_bin=jnp.asarray([3, 5], jnp.int32),
        left_child=jnp.asarray([1, -1], jnp.int32),
        right_child=jnp.asarray([-2, -3], jnp.int32),
        default_left=jnp.asarray([True, False]),
        miss_bin=jnp.asarray([-1, -1], jnp.int32),
        is_cat=jnp.asarray([False, False]),
        cat_bitset_inner=jnp.zeros(1, jnp.uint32),
        cat_boundaries_inner=jnp.zeros(3, jnp.int32),
    )


def test_traverse_binned(rng):
    n = 200
    bins = rng.randint(0, 16, size=(n, 2)).astype(np.uint8)
    tree = _mk_tree()
    leaf = np.asarray(T.traverse_binned(jnp.asarray(bins), **tree))
    for i in range(n):
        if bins[i, 0] <= 3:
            want = 0 if bins[i, 1] <= 5 else 2
        else:
            want = 1
        assert leaf[i] == want, i


def test_traverse_raw_missing(rng):
    n = 50
    x = rng.randn(n, 2) * 4
    x[::7, 0] = np.nan
    tree = dict(
        split_feature=jnp.asarray([0], jnp.int32),
        threshold=jnp.asarray([0.5]),
        left_child=jnp.asarray([-1], jnp.int32),
        right_child=jnp.asarray([-2], jnp.int32),
        default_left=jnp.asarray([True]),
        missing_type=jnp.asarray([2], jnp.int32),  # NaN
        is_cat=jnp.asarray([False]),
        cat_bitset=jnp.zeros(1, jnp.uint32),
        cat_boundaries=jnp.zeros(2, jnp.int32),
        cat_idx=jnp.asarray([0], jnp.int32),
    )
    leaf = np.asarray(T.traverse_raw(jnp.asarray(x), **tree))
    for i in range(n):
        if np.isnan(x[i, 0]):
            want = 0  # default left
        else:
            want = 0 if x[i, 0] <= 0.5 else 1
        assert leaf[i] == want


def test_traverse_raw_categorical():
    # bitset holds categories {2, 5}
    bitset = np.zeros(1, np.uint32)
    bitset[0] = (1 << 2) | (1 << 5)
    x = np.array([[2.0], [5.0], [3.0], [-1.0], [np.nan], [40.0]])
    tree = dict(
        split_feature=jnp.asarray([0], jnp.int32),
        threshold=jnp.asarray([0.0]),  # cat_idx slot
        left_child=jnp.asarray([-1], jnp.int32),
        right_child=jnp.asarray([-2], jnp.int32),
        default_left=jnp.asarray([False]),
        missing_type=jnp.asarray([0], jnp.int32),
        is_cat=jnp.asarray([True]),
        cat_bitset=jnp.asarray(bitset),
        cat_boundaries=jnp.asarray([0, 1], jnp.int32),
        cat_idx=jnp.asarray([0], jnp.int32),
    )
    leaf = np.asarray(T.traverse_raw(jnp.asarray(x), **tree))
    # NaN with missing none -> int 0 -> not in set -> right
    np.testing.assert_array_equal(leaf, [0, 0, 1, 1, 1, 1])
