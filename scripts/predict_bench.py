"""Batch-prediction throughput: PathForest vs the packed-forest walker.

Measures warm us/row at HIGGS-bench model scale (500 trees x 255
leaves) on 1M fresh rows per call.
Run on the TPU chip:  python scripts/predict_bench.py

The model is trained once at 50k rows (shape of the trees is what
matters for traversal cost) and cached as a text model next to this
script so repeat runs skip training.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODEL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".predict_bench_model.txt")
N = 1 << 20
TREES = int(os.environ.get("PRED_TREES", 500))
LEAVES = int(os.environ.get("PRED_LEAVES", 255))


def main():
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.compile import ensure_compile_cache
    ensure_compile_cache()

    rng = np.random.RandomState(0)
    if os.path.exists(MODEL):
        bst = lgb.Booster(model_file=MODEL)
    else:
        X = rng.randn(50000, 28).astype(np.float32)
        y = (X[:, 0] * X[:, 1] + X[:, 2]
             + 0.5 * rng.randn(len(X)) > 0).astype(float)
        t0 = time.time()
        bst = lgb.train({"objective": "binary", "num_leaves": LEAVES,
                         "verbose": -1, "min_data_in_leaf": 20},
                        lgb.Dataset(X, label=y), num_boost_round=TREES,
                        verbose_eval=False)
        print(f"trained {TREES}x{LEAVES} in {time.time() - t0:.0f}s")
        bst.save_model(MODEL)

    def bench(label):
        t0 = time.time()
        bst.predict(rng.randn(N, 28).astype(np.float32))
        cold = time.time() - t0
        t0 = time.time()
        bst.predict(rng.randn(N, 28).astype(np.float32))
        warm = time.time() - t0
        print(f"{label}: first {cold:.1f}s, warm {warm:.2f}s "
              f"= {warm / N * 1e6:.3f} us/row", flush=True)
        return warm

    w_path = bench("pathforest (default)")
    os.environ["LGBM_TPU_PRED_PATH"] = "0"
    bst._gbdt._path_forest_cache = None
    w_walk = bench("walker (LGBM_TPU_PRED_PATH=0)")
    print(f"speedup: {w_walk / w_path:.1f}x")


if __name__ == "__main__":
    main()
