"""Categorical-feature training cost in the fused path, vs dense.

Reference semantics being exercised: one-vs-rest + sorted many-vs-many
categorical splits (feature_histogram.hpp:278) with the left-set bitset
routed through the partition kernel's prefetched scalars. The question
this answers (round-4 verdict item 9): does a bench-shaped run with a
few categorical columns stay within 1.5x of the all-dense iteration
time? Appends the measured table to docs/PERF_NOTES.md by hand — run,
read, record.

Run on the TPU chip: python scripts/categorical_perf.py
Env: CAT_ROWS (default 2_097_152), CAT_ITERS (default 30).
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("CAT_ROWS", 2_097_152))
ITERS = int(os.environ.get("CAT_ITERS", 30))
COLS = 28
N_CAT = 4
N_LEVELS = 50


def make(n, with_cats: bool, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, COLS).astype(np.float32)
    logit = 0.9 * X[:, 4] - 0.8 * X[:, 5] + 0.6 * X[:, 6] * X[:, 7]
    if with_cats:
        for c in range(N_CAT):
            cats = rng.randint(0, N_LEVELS, n)
            w = rng.randn(N_LEVELS) * 0.4
            logit += w[cats]
            X[:, c] = cats
    y = (logit + rng.randn(n) > 0).astype(np.float32)
    return X, y


def steady_iter_time(bst, iters):
    import jax
    jax.block_until_ready(bst._gbdt.device_score_state())
    t0 = time.time()
    for _ in range(iters):
        bst.update()
    jax.block_until_ready(bst._gbdt.device_score_state())
    return (time.time() - t0) / iters


def main():
    import jax
    import lightgbm_tpu as lgb

    results = {}
    for name, with_cats in (("dense", False), ("categorical", True)):
        X, y = make(ROWS, with_cats)
        params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
                  "learning_rate": 0.1, "verbose": -1,
                  "min_data_in_leaf": 20}
        if with_cats:
            params["categorical_feature"] = ",".join(
                str(c) for c in range(N_CAT))
        t0 = time.time()
        bst = lgb.train(dict(params), lgb.Dataset(X, label=y),
                        num_boost_round=1, verbose_eval=False,
                        keep_training_booster=True)
        jax.block_until_ready(bst._gbdt.device_score_state())
        compile_s = time.time() - t0
        s_iter = steady_iter_time(bst, ITERS)
        # quality sanity
        p = bst.predict(X[:200_000])
        ys = y[:200_000]
        order = np.argsort(-p)
        yy = ys[order] > 0
        pos, neg = yy.sum(), len(yy) - yy.sum()
        auc = 1.0 - (np.sum(np.arange(1, len(yy) + 1)[yy])
                     - pos * (pos + 1) / 2) / (pos * neg)
        results[name] = (s_iter, compile_s, auc)
        print(f"{name:12s}: {s_iter*1e3:7.1f} ms/iter "
              f"(compile+first {compile_s:.0f}s, sampled AUC {auc:.4f})")

    ratio = results["categorical"][0] / results["dense"][0]
    print(f"\ncategorical/dense iteration-time ratio: {ratio:.2f}x "
          f"({ROWS} rows x {COLS} cols, {N_CAT} categorical x {N_LEVELS} "
          f"levels, 255 leaves/bins, {ITERS} steady iters)")
    assert results["categorical"][2] > 0.75, "categorical model broken"


if __name__ == "__main__":
    main()
