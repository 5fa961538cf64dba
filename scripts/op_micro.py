"""Microbenchmark the fused-path component ops on the real device.

WARNING: per-dispatch host timing is a poor proxy for device time — XLA
dead-code-eliminates unconsumed outputs and dispatch latency is not
device time. Treat these numbers as smoke only; for real attribution
use scripts/profile_fused.py (device-side profiler trace) or end-to-end
iterations."""
import time

import numpy as np
import jax
import jax.numpy as jnp


def timeit(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def main():
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.ops.partition import partition_leaf

    print("backend:", jax.default_backend())
    n, f, B = 1 << 20, 28, 255
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, B, size=(n, f), dtype=np.uint8))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(rng.rand(n).astype(np.float32))
    perm = jnp.arange(n, dtype=jnp.int32)

    t = timeit(lambda: H.histogram_scatter(bins, grad, hess, B))
    print(f"{'scatter':14s} rows={n} {t * 1e3:8.2f} ms")

    # leaf gather + histogram at half/quarter capacity
    for cap in (n, n // 4, n // 16):
        t = timeit(lambda c=cap: H.leaf_histogram(
            bins, perm, 0, c, grad, hess, c, B))
        print(f"leaf_hist cap={cap:8d} {t * 1e3:8.2f} ms")

    # partition at capacities
    for cap in (n, n // 4, n // 16):
        t = timeit(lambda c=cap: partition_leaf(
            bins, perm, 0, c, jnp.int32(0), jnp.int32(127),
            jnp.bool_(False), jnp.int32(-1), jnp.bool_(False),
            jnp.zeros(1, jnp.uint32), c))
        print(f"partition cap={cap:8d} {t * 1e3:8.2f} ms")

    # split scan
    from lightgbm_tpu.ops import split as S
    meta = S.FeatureMeta.build(
        num_bin=[B] * f, missing_type=[0] * f, default_bin=[0] * f,
        is_categorical=[False] * f, monotone=[0] * f, penalty=[1.0] * f)
    cfg = S.SplitConfig(lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=20,
                        min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
                        max_delta_step=0.0, path_smooth=0.0)
    hist = H.histogram_scatter(bins[:4096], grad[:4096], hess[:4096], B)
    scan = jax.jit(lambda h: S.numerical_split_scan(
        h, meta, cfg, jnp.float32(0.0), jnp.float32(4096.0),
        jnp.int32(4096), jnp.float32(0.0), jnp.float32(-np.inf),
        jnp.float32(np.inf)))
    t = timeit(scan, hist)
    print(f"split_scan          {t * 1e3:8.2f} ms")


if __name__ == "__main__":
    main()
