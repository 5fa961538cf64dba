"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call, at
the full width of the one model the repo is measured on (the HIGGS shape:
10,485,760 x 28 dense f32, binary objective, 255 leaves, 255 bins):

    lgb.Dataset -> lgb.train (compile-paying iteration + 10 steady ones)
    -> Booster.predict on 500,000 held-out rows -> save_model / reload /
    predict again

and proves which path it was (persistent fused tier, radix_pallas_bf16 +
pallas2 kernels, PathForest predict), that the result is right (held-out
AUC against a floor and against the scatter/ref oracle; reloaded model ==
in-memory model; the Pallas kernels equal their XLA oracles on windows cut
from the trained planar state), that nothing fell back silently (compile
manager counters), that every other Pallas kernel the dispatcher can select
compiles through Mosaic, and that a fresh train() finds the compile cache.
With four chips visible it also runs the same shape data-parallel.

Exit code 0 and a last stdout line `{"ok": true, "device": {...}}` only
when every stage passed on a TPU. No accelerator -> non-zero, no result.
One process touches JAX. Wall times and peak memory are printed as
*smoke timings*: they are not benchmark metrics.
"""
import json
import os
import sys
import tempfile
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

ROWS = 10_485_760
TEST_ROWS = 500_000
COLS = 28
STEADY_ITERS = 10
ORACLE_ROWS = 65_536        # row slice the scatter/ref oracle can afford
PARITY_ROWS = 65_536        # rows cut from the trained state for parity
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
          "learning_rate": 0.1, "min_data_in_leaf": 20, "verbose": 1}

# held-out AUC after 11 iterations: above the floor, and within the band
# of the oracle trained on ORACLE_ROWS rows of the same draw. Eleven
# trees at learning_rate 0.1 reach ~0.79 whatever the row count (the
# generator's Bayes ceiling, ~0.875, needs hundreds of iterations)
AUC_FLOOR = 0.75
AUC_ORACLE_BAND = 0.02
# the kernels and the oracle on the SAME rows differ only by bf16
# histogram inputs and accumulation order
AUC_SAME_ROWS_BAND = 0.005
# four chips: per-device peak bytes within this factor of each other
PEAK_BYTES_FACTOR = 2.0


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- data

def make_higgs_like(n: int, f: int = COLS, seed: int = 0, scale=2.4):
    """Synthetic stand-in calibrated to real HIGGS difficulty: labels are
    DRAWN from p = sigmoid(s(x)) with s standardized to `scale` (Bayes
    AUC ~0.875), so a broken split search visibly loses. Rows are drawn
    in 64 fixed blocks, each from its own spawned stream, so the draw is
    the same on any number of cores."""
    assert f >= 12
    X = np.empty((n, f), np.float32)
    u = np.empty(n, np.float32)
    blocks = np.linspace(0, n, 65).astype(np.int64)
    seeds = np.random.SeedSequence(seed).spawn(64)

    def fill(i):
        rng = np.random.default_rng(seeds[i])
        lo, hi = blocks[i], blocks[i + 1]
        rng.standard_normal(out=X[lo:hi], dtype=np.float32)
        u[lo:hi] = rng.random(hi - lo, dtype=np.float32)

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        list(pool.map(fill, range(64)))
    s = (0.9 * X[:, 0] - 0.8 * X[:, 1] + 1.1 * X[:, 2] * X[:, 3]
         + 0.8 * np.sin(2 * X[:, 4]) * X[:, 5] + 0.6 * (X[:, 6] ** 2 - 1)
         + 0.7 * X[:, 7] * X[:, 8] * X[:, 9]
         + 0.5 * np.tanh(X[:, 10]) * X[:, 11])
    s = (s - s.mean()) / s.std() * scale
    y = (u < 1.0 / (1.0 + np.exp(-s))).astype(np.float32)
    return X, y


def auc(y, p) -> float:
    order = np.argsort(-p, kind="stable")
    yy = y[order] > 0
    pos, neg = yy.sum(), len(yy) - yy.sum()
    ranks = np.arange(1, len(yy) + 1)
    return float(1.0 - (np.sum(ranks[yy]) - pos * (pos + 1) / 2)
                 / (pos * neg))


# -------------------------------------------------------------- stages

def stage_generate(rows: int, test_rows: int, seed: int = 0):
    """ONE draw; the last `test_rows` are held out."""
    X, y = make_higgs_like(rows + test_rows, seed=seed)
    return X[:rows], y[:rows], X[rows:], y[rows:]


def stage_construct(lgb, X, y, params):
    ds = lgb.Dataset(X, label=y, params=dict(params)).construct()
    from lightgbm_tpu import native
    say(f"host binning: {native.implementation()}")
    return ds


def stage_train(lgb, ds, params, steady_iters: int):
    """The compile-paying iteration, then `steady_iters` more, each span
    closed by block_until_ready. Returns (booster, cold_s, steady_s)."""
    import jax
    t0 = time.perf_counter()
    bst = lgb.train(dict(params), ds, num_boost_round=1, verbose_eval=False,
                    keep_training_booster=True)
    jax.block_until_ready(bst._gbdt.device_score_state())
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steady_iters):
        bst.update()
    jax.block_until_ready(bst._gbdt.device_score_state())
    return bst, cold_s, time.perf_counter() - t0


def check_main_path(bst, params, iters: int) -> None:
    """The run must prove which path it was."""
    g = bst._gbdt
    plan = g.execution_plan()
    say(f"execution plan: {plan}")
    check(g._fused_persist, "not on the persistent fused tier")
    check(plan["hist"] == "radix_pallas_bf16",
          f"histogram kernel is {plan['hist']}, not radix_pallas_bf16")
    check(plan["partition"] == "pallas2",
          f"partition kernel is {plan['partition']}, not pallas2")
    check(bst.num_trees() == iters,
          f"{bst.num_trees()} trees after {iters} iterations")
    g._materialize_models()
    leaves = [int(t.num_leaves) for t in g.models]
    check(all(n == params["num_leaves"] for n in leaves),
          f"tree leaf counts {leaves}, want {params['num_leaves']} each")


def root_split(bst):
    """(feature, threshold bin) of the first tree's root."""
    t = bst._gbdt.models[0]
    return int(t.split_feature[0]), int(t.threshold_in_bin[0])


def stage_predict(bst, Xte, yte):
    p = bst.predict(Xte)
    check(p.shape == (len(Xte),) and bool(np.all(np.isfinite(p))),
          "predictions are not finite [n] values")
    check(bst._gbdt.predict_path == "pathforest",
          f"predict took '{bst._gbdt.predict_path}', not pathforest")
    return p, auc(yte, p)


def stage_save_load(lgb, bst, Xte, p_mem) -> None:
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.txt")
        bst.save_model(path)
        back = lgb.Booster(model_file=path)
    p_back = back.predict(Xte)
    check(back._gbdt.predict_path == "pathforest",
          "reloaded model did not predict through pathforest")
    check(np.array_equal(p_back, p_mem),
          f"reloaded-model predictions differ from in-memory ones "
          f"(max |diff| {np.max(np.abs(p_back - p_mem)):.3g})")


def stage_oracle(lgb, X, y, Xte, yte, params, rows: int, iters: int):
    """A row slice of the same draw trained twice from the same bins and
    seed: by the portable XLA paths (device_type=cpu: scatter histogram,
    argsort partition — the oracle) and by the kernels under test.
    Returns (auc_oracle, auc_kernels) on the held-out rows."""
    ds = lgb.Dataset(X[:rows], label=y[:rows])
    oracle = lgb.train(dict(params, device_type="cpu"), ds,
                       num_boost_round=iters, verbose_eval=False)
    plan = oracle._gbdt.execution_plan()
    check(plan["hist"] == "scatter" and plan["partition"] == "ref",
          f"oracle ran {plan}")
    kernels = lgb.train(dict(params), ds, num_boost_round=iters,
                        verbose_eval=False)
    plan = kernels._gbdt.execution_plan()
    check(plan["hist"] == "radix_pallas_bf16"
          and plan["partition"] == "pallas2", f"slice run took {plan}")
    return auc(yte, oracle.predict(Xte)), auc(yte, kernels.predict(Xte))


def _kernel_check():
    """scripts/kernel_check.py: the on-device kernel checks."""
    scripts = os.path.join(HERE, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import kernel_check
    return kernel_check


def stage_kernel_parity(bst, rows: int, interpret: bool = False) -> None:
    """partition_pallas2(cap=None) / histogram_planar_pallas(cap=None)
    against partition_ref / the scatter-add oracle on windows cut from
    the smoke's own trained planar state."""
    K = _kernel_check()
    from lightgbm_tpu.ops import plane
    fused = bst._gbdt._fused
    data, Ly = K.window_state(bst._gbdt._fused_state, fused.layout, rows)
    tile = min(fused._dyn_tile, Ly.max_tile)
    rb = min(fused._dyn_hist_rb, Ly.max_tile)
    nbins = fused.max_num_bin
    # the cut is the trained tree's leftmost leaves, which the tree's own
    # splits no longer divide: route on the column whose median bin
    # halves these rows most evenly
    codes = np.asarray(plane.window_rowmajor(data, Ly, 0, cap=rows)[0])
    med = np.median(codes, axis=0)
    feat = int(np.argmin(np.abs((codes <= med).mean(axis=0) - 0.5)))
    rscal = plane.route_scalars(Ly, feat, int(med[feat]), 1,
                                int(fused.feature_miss_bin[feat]))
    results = []
    for start, count in ((17, rows - 40), (rows - 1000, 999)):
        r = K.check_partition(
            data, Ly, start, count, rscal, kernel=fused._part_method,
            dynamic=True, tile=tile, interpret=interpret)
        if count > rows // 2:      # the big window must really split
            r["ok"] = r["ok"] and 0 < r["nleft"] < count
        results.append(r)
        results.append(K.check_histogram(
            data, Ly, start, count, nbins, rows_per_block=rb,
            interpret=interpret))
        results.append(K.check_histogram_quant(
            data, Ly, start, count, nbins, rows_per_block=rb,
            interpret=interpret))
    for r in results:
        say(f"parity: {r}")
    check(all(r["ok"] for r in results), "a Pallas kernel disagrees with "
          "its XLA oracle on the trained state")


def stage_kernel_compile() -> None:
    """Every Pallas kernel the dispatcher can select off the HIGGS main
    path lowers and compiles through Mosaic at one real geometry."""
    K = _kernel_check()
    results = [K.compile_only(*k) for k in K.off_main_path_kernels()]
    for r in results:
        say(f"mosaic: {r}")
    check(all(r["status"] == "compiled" for r in results),
          "Mosaic refused a kernel the dispatcher can select")


def stage_cache(lgb, ds, params, cold_s: float) -> float:
    """A fresh train() with every in-memory compile dropped must find
    the on-disk cache the cold one wrote."""
    import jax
    from lightgbm_tpu.compile import (compile_cache_dir, get_manager,
                                      reset_manager)
    say(f"compile cache dir: {compile_cache_dir()} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    jax.clear_caches()
    reset_manager()
    bst, warm_s, _ = stage_train(lgb, ds, params, 0)
    stats = get_manager().snapshot()
    say(f"warmed train(): compile-paying iteration {warm_s:.1f}s vs "
        f"{cold_s:.1f}s cold; manager {stats}")
    served = sum(stats.get(k, 0) for k in
                 ("store_loads", "store_preloads", "jax_cache_hits"))
    check(served >= 1, "the warmed train() found nothing in the cache")
    check(stats.get("cache_misses", 0) == stats.get("jax_cache_hits", 0),
          "the warmed train() compiled a program afresh")
    check(stats.get("store_load_errors", 0) == 0,
          "the executable store dropped a blob it wrote itself")
    check(warm_s < cold_s, "the warmed compile was not faster than cold")
    del bst
    return warm_s


def check_no_fallbacks() -> None:
    """A failed compile or executable call raises out of train() (there
    is no plain-jit fallback left to count); what can still be tolerated
    silently-ish is a stored blob that would not load."""
    from lightgbm_tpu.compile import get_manager
    stats = get_manager().snapshot()
    say(f"compile manager: {stats}")
    check(stats.get("store_load_errors", 0) == 0,
          f"compile.store_load_errors = {stats.get('store_load_errors')}")


def peak_bytes():
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in jax.devices()]


def stage_four_chip(lgb, ds, Xte, yte, params, steady_iters: int,
                    peak_factor=PEAK_BYTES_FACTOR):
    """The same shape with tree_learner=data over every visible chip."""
    import jax
    from lightgbm_tpu.treelearner.parallel import FusedDataParallelGrower
    n_dev = len(jax.devices())
    bst, cold_s, steady_s = stage_train(
        lgb, ds, dict(params, tree_learner="data"), steady_iters)
    g = bst._gbdt
    check(isinstance(g._fused, FusedDataParallelGrower),
          f"data learner is {type(g._fused).__name__}")
    check_main_path(bst, params, 1 + steady_iters)
    state, Ly = g._fused_state, g._fused.layout
    shards = state.addressable_shards
    say(f"four-chip state: {state.shape} sharding {state.sharding}; "
        f"shards {[(str(s.device), s.data.shape) for s in shards]}")
    check(len({s.device for s in shards}) == n_dev,
          "the planar state does not span every device")
    check(all(s.data.shape == (Ly.num_planes, state.shape[1] // n_dev)
              for s in shards), "a device holds more than 1/n of the lanes")
    peaks = peak_bytes()
    say(f"four-chip smoke timings: cold_compile_s={cold_s:.1f} "
        f"steady_iters_s={steady_s:.2f} ({steady_iters} iterations) "
        f"per-device peak_bytes_in_use={peaks}")
    if peak_factor is not None:
        check(min(peaks) > 0 and max(peaks) <= peak_factor * min(peaks),
              f"per-device peak bytes {peaks} differ by more than "
              f"{peak_factor}x")
    _, auc4 = stage_predict(bst, Xte, yte)
    return root_split(bst), auc4


# ---------------------------------------------------------------- main

def main() -> int:
    import jax
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    say(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__}")
    if device["platform"] != "tpu":
        print("chip_smoke needs a TPU; JAX found none", file=sys.stderr)
        return 2

    import lightgbm_tpu as lgb
    timings = {}

    def timed(name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        timings[name] = round(time.perf_counter() - t0, 2)
        say(f"stage {name}: ok ({timings[name]:.1f}s)")
        return out

    X, y, Xte, yte = timed("generate", stage_generate, ROWS, TEST_ROWS)
    ds = timed("construct", stage_construct, lgb, X, y, PARAMS)

    four = None
    if len(dev) >= 4:
        # first, so each device's peak bytes are this stage's own
        four = timed("four_chip", stage_four_chip, lgb, ds, Xte, yte,
                     PARAMS, STEADY_ITERS)
    else:
        say(f"stage four_chip: did not run ({len(dev)} chip visible)")

    bst, cold_s, steady_s = timed("train", stage_train, lgb, ds, PARAMS,
                                  STEADY_ITERS)
    timings["cold_compile"], timings["steady_iters"] = \
        round(cold_s, 2), round(steady_s, 2)
    check_main_path(bst, PARAMS, 1 + STEADY_ITERS)
    timings["peak_bytes_in_use"] = peak_bytes()
    from lightgbm_tpu.compile import get_manager
    say(f"compile manager after the cold train: {get_manager().snapshot()}")
    p, auc_main = timed("predict", stage_predict, bst, Xte, yte)
    timed("save_load", stage_save_load, lgb, bst, Xte, p)
    timed("kernel_parity", stage_kernel_parity, bst, PARITY_ROWS)
    root1 = root_split(bst)
    del bst

    auc_oracle, auc_slice = timed(
        "oracle", stage_oracle, lgb, X, y, Xte, yte, PARAMS, ORACLE_ROWS,
        1 + STEADY_ITERS)
    say(f"held-out AUC: main={auc_main:.5f} (floor {AUC_FLOOR}); on "
        f"{ORACLE_ROWS} rows oracle={auc_oracle:.5f} kernels="
        f"{auc_slice:.5f} (band {AUC_SAME_ROWS_BAND}); main vs oracle "
        f"band {AUC_ORACLE_BAND}")
    check(auc_main >= AUC_FLOOR, f"held-out AUC {auc_main:.5f} < floor")
    check(abs(auc_main - auc_oracle) <= AUC_ORACLE_BAND,
          f"held-out AUC {auc_main:.5f} vs oracle {auc_oracle:.5f}")
    check(abs(auc_slice - auc_oracle) <= AUC_SAME_ROWS_BAND,
          f"same rows: kernels {auc_slice:.5f} vs oracle {auc_oracle:.5f}")
    if four is not None:
        root4, auc4 = four
        say(f"four-chip: root split {root4} vs single-chip {root1}; "
            f"held-out AUC {auc4:.5f}")
        check(root4 == root1, "four-chip root split differs")
        check(abs(auc4 - auc_oracle) <= AUC_ORACLE_BAND,
              f"four-chip AUC {auc4:.5f} vs oracle {auc_oracle:.5f}")

    timings["warm_compile"] = round(
        timed("cache", stage_cache, lgb, ds, PARAMS, cold_s), 2)
    timed("kernel_compile", stage_kernel_compile)
    check_no_fallbacks()

    say("smoke timings (not benchmark metrics): "
        + " ".join(f"{k}={v}" for k, v in timings.items()))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except Exception:
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.exit(rc)
