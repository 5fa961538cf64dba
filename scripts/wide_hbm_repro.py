"""Fast repro for the wide-EFB (Allstate-shape) training HBM OOM.

Builds the 13.2M x 581-bundle 4-bit planar geometry directly from
random codes (no CSR generation, no EFB search — ~2 min instead of
~40), then runs a few persistent iterations. Shapes match
scripts/sparse_scale.py exactly: P=80 planes x 13.37M lanes.

``--lower-proof`` (or REPRO_MODE=lower) skips training and instead
proves the compile-window collapse: it traces, lowers, and compiles
the grid-parameterized planar histogram at the FULL 581-column width
and fails unless that completes inside REPRO_LOWER_BUDGET_S (default
300 s). The legacy body unrolled every feature chunk into the kernel,
and Mosaic lowering of the resulting program took ~70 minutes at this
width; the grid body is constant-size in the column count (width only
moves the grid bounds — tests/test_compile_collapse.py pins the
equation-count claim), so the same lowering is seconds.

Env: REPRO_ROWS (default 13_200_000), REPRO_COLS (581), REPRO_ITERS (3),
REPRO_LOWER_BUDGET_S (300).
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("REPRO_ROWS", 13_200_000))
COLS = int(os.environ.get("REPRO_COLS", 581))
ITERS = int(os.environ.get("REPRO_ITERS", 3))
BINS = 16


def main():
    import jax
    from lightgbm_tpu.compile import ensure_compile_cache
    ensure_compile_cache()
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset, Metadata
    from lightgbm_tpu.io.binning import BinMapper
    from lightgbm_tpu.boosting.gbdt import create_boosting
    from lightgbm_tpu.objective.functions import create_objective

    rng = np.random.RandomState(0)
    t0 = time.time()
    bins = rng.randint(0, BINS, size=(ROWS, COLS), dtype=np.uint8)
    y = ((bins[:, 0] > 7) ^ (bins[:, 1] > 9)
         | (rng.rand(ROWS) < 0.1)).astype(np.float64)
    print(f"codes generated in {time.time() - t0:.0f}s", flush=True)

    proto = BinMapper()
    proto.find_bin(rng.rand(5000) * 16, 5000, BINS)
    ds = BinnedDataset()
    ds.num_data = ROWS
    ds.num_total_features = COLS
    ds.bins = bins
    ds.bin_mappers = [proto] * COLS
    ds.real_feature_index = list(range(COLS))
    ds.inner_feature_index = {f: f for f in range(COLS)}
    ds.feature_names = [f"Column_{i}" for i in range(COLS)]
    ds.max_bin = BINS
    ds.metadata = Metadata(ROWS)
    ds.metadata.set_label(y)

    cfg = Config.from_params({"objective": "binary", "num_leaves": 255,
                              "max_bin": BINS, "verbose": -1,
                              "min_data_in_leaf": 20})
    gbdt = create_boosting("gbdt")
    obj = create_objective(cfg)
    gbdt.init(cfg, ds, obj, [])
    print(f"grower: fused={gbdt._fused is not None} "
          f"persist={gbdt._fused_persist}", flush=True)
    if gbdt._fused is not None:
        Ly = gbdt._fused.layout
        print(f"layout: P={Ly.num_planes} R={Ly.num_lanes} "
              f"bits={Ly.code_bits} tile={Ly.tile} "
              f"part={gbdt._fused._part_method}", flush=True)

    for i in range(ITERS):
        t0 = time.time()
        gbdt.train_one_iter()
        jax.block_until_ready(gbdt.device_score_state())
        print(f"iter {i}: {time.time() - t0:.1f}s", flush=True)
    print("OK", flush=True)


def lower_proof():
    """Bounded trace+lower+compile of the full-width histogram program
    through Mosaic — the lowering the 70-minute cliff lived in, so it
    needs a TPU and refuses to run without one (the width-independence
    of the traced program itself is pinned on the CPU tier by
    tests/test_compile_collapse.py). Shapes are abstract — no 13M-row
    buffer is materialized."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (histogram_planar_pallas,
                                            planar_grid_dims)

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"--lower-proof is the Mosaic proof and needs a TPU; JAX "
            f"initialised the {jax.default_backend()} backend")
    code_bits = 4
    budget = float(os.environ.get("REPRO_LOWER_BUDGET_S", 300))
    Fc, SP, CC, CS = planar_grid_dims(BINS, code_bits, COLS)
    gp = -(-CS * SP // 8) * 8
    R = -(-ROWS // 1024) * 1024
    print(f"geometry: {COLS} cols -> {CC * CS} feature chunks "
          f"(Fc={Fc} CC={CC} CS={CS}), R={R}, mosaic lowering", flush=True)

    def fn(d, start, cnt):
        return histogram_planar_pallas(
            d, start, cnt, num_bins=BINS, num_cols=COLS,
            code_bits=code_bits, grad_plane=gp, cap=None)

    spec = (jax.ShapeDtypeStruct((gp + 8, R), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    t0 = time.time()
    lowered = jax.jit(fn).lower(*spec)
    t1 = time.time()
    lowered.compile()
    t2 = time.time()
    print(f"lower {t1 - t0:.1f}s  compile {t2 - t1:.1f}s  "
          f"(budget {budget:.0f}s)", flush=True)
    assert t2 - t0 < budget, (
        f"full-width lowering took {t2 - t0:.0f}s > {budget:.0f}s "
        f"budget — the compile-window cliff is back")
    print("OK (Mosaic lower+compile proved)", flush=True)


if __name__ == "__main__":
    if "--lower-proof" in sys.argv or os.environ.get("REPRO_MODE") == "lower":
        lower_proof()
    else:
        main()
