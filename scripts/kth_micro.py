"""The k-th largest value of a seeded float32 vector, by `jnp.sort` (what
`_largest_k_mask` did until PR 35) and by the package's counting select
`gbdt._kth_largest` at 1-, 4- and 8-bit digits, at 11M / 22M / 33M rows
on whatever device it finds; then the whole sampler round
(`_goss_sample_device`, one class) at the same sizes. ms each, and for
the select the ms a pass and, on a device benchmarks/harness/peaks.json
knows, the share of its HBM rate that a pass's 4 bytes a row are
(PERF.md section 6, PR 35).

Run: python scripts/kth_micro.py [million rows ...]     (default 11 22 33)
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np


def timed(name, fn, *args, reps=5):
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / reps * 1e3
    print(f"{name}: {ms:.2f} ms (first call {first:.1f} s)", flush=True)
    return ms, out


def main():
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.boosting import gbdt as G

    sizes = [int(a) * 1_000_000 for a in sys.argv[1:]] or \
        [11_000_000, 22_000_000, 33_000_000]
    kind = jax.devices()[0].device_kind
    with open(os.path.join(REPO, "benchmarks/harness/peaks.json")) as f:
        hbm = json.load(f).get(kind, {}).get("hbm_bytes_per_s")
    print(f"device {kind}", flush=True)
    for n in sizes:
        k = n // 5
        rng = np.random.default_rng(n)
        g = rng.normal(size=(1, n)).astype(np.float32)
        h = rng.uniform(0.05, 0.25, size=(1, n)).astype(np.float32)
        x = jnp.asarray(np.abs(g * h)[0])
        print(f"n = {n}, k = {k}", flush=True)
        _, want = timed("  jnp.sort(x)[n - k]",
                        jax.jit(lambda x: jnp.sort(x)[n - k]), x)
        for bits in (1, 4, 8):
            ms, got = timed(
                f"  select, {bits}-bit digits, {32 // bits} passes",
                jax.jit(lambda x: G._kth_largest(x, k, bits)), x)
            assert float(got) == float(want), bits
            rate = 4 * n / (ms * 1e-3 / (32 // bits))
            print(f"    {ms / (32 // bits):.3f} ms a pass: {rate / 1e9:.0f} "
                  "GB/s" + (f", {100 * rate / hbm:.1f} % of the HBM's "
                            f"{hbm / 1e9:g}" if hbm else ""), flush=True)
        timed("  _largest_k_mask (select, compares, tie cumsum)",
              jax.jit(lambda x: G._largest_k_mask(x, k)), x)
        timed("  _goss_sample_device, one class (0.2 / 0.1)",
              jax.jit(lambda g, h: G._goss_sample_device(
                  g, h, jnp.int32(7), top_k=k, other_k=n // 10)),
              jnp.asarray(g), jnp.asarray(h), reps=3)


if __name__ == "__main__":
    main()
