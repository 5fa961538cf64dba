"""Wide-sparse scale proof: the Allstate shape (13.2M rows x 4000
sparse binary features, ~95% sparse) trained end-to-end on one chip.

The reference trains Allstate in 148.2s/500 iters on the CPU box with
1.1 GB RAM (docs/Experiments.rst:121,174) — the shape's hazard for the
TPU build is HBM: naive dense u8 storage would be 13.2M x 4000 = 53 GB.
The pipeline that makes it fit:
  raw CSR -> EFB bundling (4000 one-hot columns -> ~500 bundle
  columns) -> 4-bit planar code packing (group bins <= 16)
  => ~250 B/row of codes instead of 4000.

Run on the TPU chip:  python scripts/sparse_scale.py
                          [--layout {auto,planar,multival}]
Env: SPARSE_ROWS (default 13_200_000), SPARSE_VARS (default 500; 8
one-hot categories each -> 4000 columns), SPARSE_ITERS (default 10),
SPARSE_LAYOUT (same values as --layout, which wins when both given).

--layout pins tpu_hist_layout for A/B runs of the histogram layout on
the same shape: "planar" forces the column bin-plane kernels,
"multival" the row-wise packed-code kernels (ops/multival.py), "auto"
(default) lets the occupancy dispatcher decide.

Writes docs/SPARSE_SCALE.md with the measured footprint + AUC sanity.
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("SPARSE_ROWS", 13_200_000))
VARS = int(os.environ.get("SPARSE_VARS", 500))
CATS = 8
ITERS = int(os.environ.get("SPARSE_ITERS", 10))
LAYOUT = os.environ.get("SPARSE_LAYOUT", "auto")


def make_sparse(n, nvars, ncats, seed=0):
    """One-hot design matrix in CSR: nvars categorical variables of
    ncats levels each -> nvars*ncats binary columns, exactly one
    nonzero per variable per row (the Allstate-like structure EFB
    exploits). Written for full 13.2M-row generation on one CPU core:
    inverse-CDF sampling per variable (vectorized searchsorted) and the
    column-index array built in place — no [n, nvars] intermediates
    beyond the one CSR index array itself."""
    import scipy.sparse as sp
    rng = np.random.RandomState(seed)
    # skewed category popularity so bundles get a dominant bin
    probs = rng.dirichlet(np.ones(ncats) * 0.7, size=nvars)
    cum = np.cumsum(probs, axis=1)
    w = rng.randn(nvars, ncats) * (rng.rand(nvars) < 0.2)[:, None]
    # [nvars, n] for contiguous row writes (a column write into a
    # C-order [n, nvars] array is a 13M-element strided scatter per
    # variable — 4x slower on this one-core host)
    colsT = np.empty((nvars, n), dtype=np.int32)
    logit = np.zeros(n, np.float32)
    for v in range(nvars):
        cat_v = np.searchsorted(cum[v], rng.rand(n)).astype(np.int32)
        np.clip(cat_v, 0, ncats - 1, out=cat_v)
        logit += w[v][cat_v].astype(np.float32)
        colsT[v] = cat_v + v * ncats
    y = (logit + rng.randn(n).astype(np.float32) * 0.5 > 0).astype(np.float32)
    del logit
    cols = np.ascontiguousarray(colsT.T).reshape(-1)
    del colsT
    indptr = np.arange(n + 1, dtype=np.int64) * nvars
    # int8 ones: the one-hot values; keeps the 6.6e9-nnz data array at
    # 6.6 GB instead of 26.4 GB (the CSR+CSC pair must fit in host RAM)
    data = np.ones(n * nvars, dtype=np.int8)
    X = sp.csr_matrix((data, cols, indptr), shape=(n, nvars * ncats))
    return X, y


def main():
    import jax
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--layout", default=LAYOUT,
                        choices=("auto", "planar", "multival"),
                        help="pin tpu_hist_layout (default: %(default)s)")
    ns = parser.parse_args()
    T0 = time.time()
    import lightgbm_tpu as lgb

    t0 = time.time()
    X, y = make_sparse(ROWS, VARS, CATS)
    t_gen = time.time() - t0
    print(f"generated {ROWS}x{VARS * CATS} CSR "
          f"(density {X.nnz / (ROWS * VARS * CATS):.3%}) in {t_gen:.0f}s")

    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "learning_rate": 0.1, "verbose": -1, "min_data_in_leaf": 20,
              "tpu_hist_layout": ns.layout}
    t0 = time.time()
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    t_construct = time.time() - t0
    inner = ds._handle
    g = inner.bins.shape[1]
    code_bits = None

    t0 = time.time()
    bst = lgb.train(dict(params), ds, num_boost_round=ITERS,
                    verbose_eval=False, keep_training_booster=True)
    jax.block_until_ready(bst._gbdt.device_score_state())
    t_train = time.time() - t0
    # steady-state per-iteration rate (compile already paid above)
    t0 = time.time()
    steady_n = max(3, ITERS // 2)
    for _ in range(steady_n):
        bst.update()
    jax.block_until_ready(bst._gbdt.device_score_state())
    s_iter = (time.time() - t0) / steady_n
    fused = bst._gbdt._fused
    layout = fused.layout if fused is not None else None
    code_bits = layout.code_bits if layout else None
    from lightgbm_tpu.ops import histogram as H
    hist_layout = H.hist_layout(bst._gbdt.config, inner)
    occ = getattr(inner, "occupancy", None)
    row_nnz = float(occ.row_nnz_mean) if occ is not None else None

    # quality sanity vs a dense-subsample model
    sub = np.random.RandomState(1).choice(ROWS, 200_000, replace=False)
    p = bst.predict(X[sub])
    ys = y[sub]
    order = np.argsort(-p)
    yy = ys[order] > 0
    pos, neg = yy.sum(), len(yy) - yy.sum()
    auc = 1.0 - (np.sum(np.arange(1, len(yy) + 1)[yy])
                 - pos * (pos + 1) / 2) / (pos * neg)

    # deterministic device-footprint accounting of the TRAINING loop,
    # cross-checked below against the obs layer's live-array sampler
    # (obs.live_array_bytes — the shared portable HBM estimator).
    # The row-major traverse bins stay HOST-side: the grower's lazy
    # property (round-5 fix) never uploads them on the persistent path,
    # and prediction uses the raw-feature path forest
    from lightgbm_tpu.obs import live_array_bytes
    live_measured = live_array_bytes()
    acct = {}
    if layout is not None:
        acct["planar state [P,R] i32"] = layout.num_planes * layout.num_lanes * 4
        wl = (fused._caps[-1] // layout.tile + 1) * layout.tile
        acct["partition window buffer"] = layout.num_planes * (
            wl + layout.tile + 256) * 4
        if fused._use_hist_pool:
            acct["histogram pool [L,F,B,2]"] = (fused.num_leaves *
                                                fused.num_features *
                                                fused.max_num_bin * 2 * 4)
        dev_bins = bst._gbdt.train_data._device_bins
        if dev_bins is not None:
            acct["row-major bins (resident!)"] = int(
                np.prod(dev_bins.shape)) * dev_bins.dtype.itemsize
    total = sum(acct.values())

    lines = [
        "# Wide-sparse scale proof (Allstate shape)",
        "",
        f"Config: {ROWS:,} rows x {VARS * CATS} one-hot columns "
        f"(density {X.nnz / (ROWS * VARS * CATS):.2%}), num_leaves=255, "
        f"max_bin=255, {ITERS} measured iterations on one TPU v5e chip.",
        "",
        f"- EFB bundled {VARS * CATS} columns into **{g} bundle columns**",
        f"- histogram layout: **{hist_layout}** (requested "
        f"`--layout {ns.layout}`"
        + (f"; measured mean present codes/row {row_nnz:.2f}"
           if row_nnz is not None else "") + ")",
        f"- planar code packing: **{code_bits}-bit** "
        "(group bins <= 16 -> dense_bin.hpp IS_4BIT analogue)",
        f"- dataset construct (binning + EFB + packing): {t_construct:.0f}s",
        f"- train ({ITERS} iters incl. compile): {t_train:.0f}s",
        f"- steady-state: **{s_iter:.2f} s/iter** -> extrapolated "
        f"{s_iter * 500:.0f}s for 500 iterations (reference Allstate "
        "baseline: 148.2s/500 iters on the 28-core CPU box, "
        "docs/Experiments.rst:121; its sparse-optimized row-wise "
        "histograms make Allstate CHEAPER per row than HIGGS for the "
        "reference, while the planar TPU path pays for every bundle "
        "column — the honest comparison is below, not hidden)",
        f"- sampled train AUC: **{auc:.4f}** (sanity floor 0.70)",
        "",
        "Device-footprint accounting (deterministic, from array shapes):",
        "",
    ]
    for k, v in acct.items():
        lines.append(f"- {k}: {v / 1e9:.2f} GB")
    lines += [
        f"- **total: {total / 1e9:.2f} GB** of 16 GB HBM "
        "(naive dense u8 would be "
        f"{ROWS * VARS * CATS / 1e9:.1f} GB — does not fit)",
        (f"- measured live-array bytes (obs.live_array_bytes): "
         f"{live_measured / 1e9:.2f} GB" if live_measured >= 0 else
         "- measured live-array bytes: unavailable (no jax)"),
        "",
        f"Generated by scripts/sparse_scale.py; total wall "
        f"{time.time() - T0:.0f}s.",
    ]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = os.path.join(repo, "docs", "SPARSE_SCALE.md")
    # preserve hand-authored analysis across regeneration: everything
    # from the FIRST second-level heading onward (the generated part
    # above never emits one)
    manual = ""
    if os.path.exists(out):
        prev_lines = open(out).read().splitlines(keepends=True)
        for i, ln in enumerate(prev_lines):
            if ln.startswith("## "):
                manual = "\n" + "".join(prev_lines[i:])
                break
    with open(out, "w") as fh:
        fh.write("\n".join(lines) + "\n" + manual)
    print("\n".join(lines))
    assert auc > 0.70, "quality sanity failed"


if __name__ == "__main__":
    main()
