"""Training traffic: `lgb.Dataset` -> `lgb.train` -> `Booster.update()`.

No validation set and no callbacks: the dispatch-ahead loop as
`bst.update()` drives it. The clock is read only where the device has
caught up: `chunk_iters` updates, then one `block_until_ready`.

What this file reads of the program beyond its public API, all of it
here: `bst._gbdt.device_score_state()` (the array an iteration updates,
to block on), `bst._gbdt.execution_plan()` (tier and kernels),
`ds._handle.bins` (the binned matrix, for the reference's root
histogram), and `lightgbm_tpu.obs`' sync tracing (a count).
"""
from __future__ import annotations

import dataclasses
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.harness.clock import now
from benchmarks.reference import gbdt_numpy as ref

SAMPLE_ROWS = 262_144       # training rows the reference walker scores
AUC_ITERS = 10              # held-out AUC is read at this many trees


@dataclasses.dataclass
class State:
    bst: object
    ds: object
    X: np.ndarray               # train rows, then the held-out rows
    y: np.ndarray
    rows: int
    artifacts: dict


def _block(bst) -> None:
    import jax
    jax.block_until_ready(bst._gbdt.device_score_state())


def setup(ctx) -> State:
    import lightgbm_tpu as lgb
    shape, params = ctx.config["shape"], dict(ctx.config["params"])
    rows = int(shape["rows"])
    gen = ctx.config["generator"]
    with ctx.stage("generate"):
        X, y = ctx.load("generators", gen["name"]).make(
            rows + int(shape["heldout_rows"]), seed=ctx.seed,
            cols=int(shape["cols"]), **gen["args"])
    with ctx.stage("construct"):
        ds = lgb.Dataset(X[:rows], label=y[:rows],
                         params=dict(params)).construct()
    with ctx.stage("first_call"):
        bst = lgb.train(dict(params), ds, num_boost_round=1,
                        verbose_eval=False, keep_training_booster=True)
        _block(bst)
    with ctx.stage("warmup"):
        for _ in range(int(ctx.traffic["warmup_iters"])):
            bst.update()
        _block(bst)
    ctx.say(f"execution plan: {bst._gbdt.execution_plan()}")
    return State(bst, ds, X, y, rows, {})


def window(ctx, st: State, seconds: float) -> dict:
    chunk = int(ctx.traffic["chunk_iters"])
    iters = attempted = failed = 0
    t0 = now()
    while True:
        attempted += 1
        try:
            stopped = any([st.bst.update() for _ in range(chunk)])
            _block(st.bst)
        except Exception:
            traceback.print_exc()
            stopped = True
        elapsed = now() - t0
        if stopped:                 # raised, or found nothing left to split
            failed += 1
            break
        iters += chunk
        if elapsed >= seconds:
            break
    return {"seconds": elapsed, "attempted": attempted, "failed": failed,
            "units": {"iters": iters}}


def traced(ctx, st: State) -> dict:
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import trace as obs_trace
    n = int(ctx.traffic["traced_iters"])
    first = st.bst.num_trees()
    tracer = obs_trace.activate_tracer(obs.Tracer())
    obs_trace.install_sync_tracing()
    try:
        for _ in range(n):
            with ctx.span("update"):
                st.bst.update()
        # every sync so far was the program's own; the one below is ours
        syncs = sum(1 for ev in tracer.buf if ev[2] == "sync")
        with ctx.span("sync"):
            _block(st.bst)
    finally:
        obs_trace.uninstall_sync_tracing()
        obs_trace.deactivate_tracer(tracer)
    st.artifacts["traced_trees"] = (first, first + n)
    return {"units": {"iters": n}, "counters": {"blocking_syncs": syncs}}


# ------------------------------------------------------------- the check

def _in_blocks(fn, n: int, blocks: int = 64) -> list:
    edges = np.linspace(0, n, blocks + 1).astype(np.int64)
    with ThreadPoolExecutor() as pool:
        return list(pool.map(lambda i: fn(edges[i], edges[i + 1]),
                             range(blocks)))


def _tree0(st: State, tree: ref.Tree, bands: dict, params: dict) -> list:
    """Tree 0 against the reference on every training row: leaf counts
    exactly, leaf values from float64 sums, and the root's gain against
    the best gain of the reference's own histograms."""
    X, y, n = st.X[:st.rows], st.y[:st.rows], st.rows
    L = tree.num_leaves
    init = ref.binary_init_score(y)
    p0 = float(ref.sigmoid(init))

    def count(lo, hi):
        leaf = ref.leaf_of(tree, X[lo:hi])
        return np.stack([np.bincount(leaf, None, L),
                         np.bincount(leaf, y[lo:hi], L)])
    rows_in, pos_in = np.sum(_in_blocks(count, n), axis=0)
    counts_ok = np.array_equal(rows_in.astype(np.int64), tree.leaf_count)
    # binary log-loss at the constant score: g = p0 - y, h = p0 (1 - p0)
    value = init + float(params["learning_rate"]) * ref.leaf_output(
        rows_in * p0 - pos_in, rows_in * p0 * (1.0 - p0),
        float(params.get("lambda_l2", 0.0)))
    err = np.abs(tree.leaf_value - value)
    tol = bands["leaf_value_atol"] + bands["leaf_value_rtol"] * np.abs(value)

    bins = st.ds._handle.bins
    g, h = ref.binary_grad_hess(y, np.full(n, init))
    limits = dict(min_data_in_leaf=int(params["min_data_in_leaf"]),
                  min_sum_hessian=float(
                      params.get("min_sum_hessian_in_leaf", 1e-3)),
                  lambda_l2=float(params.get("lambda_l2", 0.0)))
    with ThreadPoolExecutor() as pool:
        found = list(pool.map(
            lambda f: ref.best_threshold(
                ref.histogram(bins[:, f], g, h, int(bins[:, f].max()) + 1),
                **limits), range(bins.shape[1])))
    best_gain, best_f = max((gain, f) for f, (gain, _) in enumerate(found))
    gain_err = abs(tree.split_gain[0] - best_gain) / best_gain
    return [
        ("tree0_leaf_counts", counts_ok,
         f"{L} leaves, {int(np.sum(rows_in != tree.leaf_count))} counts "
         "differ from numpy's routing of every training row"),
        ("tree0_leaf_values", bool(np.all(err <= tol)),
         f"max |value - reference| {err.max():.3g} (worst in units of its "
         f"tolerance {np.max(err / tol):.3g})"),
        ("tree0_root_gain", gain_err <= bands["root_gain_rtol"],
         f"model {tree.split_gain[0]:.6g} on feature "
         f"{int(tree.split_feature[0])}, reference best {best_gain:.6g} on "
         f"feature {best_f}: off by {gain_err:.3g} of it "
         f"(allowed {bands['root_gain_rtol']})")]


def check(ctx, st: State) -> list:
    bands, params = ctx.config["correct"], ctx.config["params"]
    plan = st.bst._gbdt.execution_plan()
    out = [("fused_tier", plan["tier"] != "host-loop",
            f"tier {plan['tier']}, hist {plan['hist']}, partition "
            f"{plan['partition']}, learner {plan['learner']}")]

    trees = st.artifacts["trees"] = ref.parse_model(st.bst.model_to_string())
    out += _tree0(st, trees[0], bands, params)

    # the trainer's running scores against its own model, walked by the
    # reference over a seeded sample of the training rows
    (_, _, loss, _), = st.bst.eval_train()
    take = np.sort(np.random.default_rng(ctx.seed).choice(
        st.rows, min(SAMPLE_ROWS, st.rows), replace=False))
    Xs, ys = st.X[take], st.y[take]
    raw = np.concatenate(_in_blocks(
        lambda lo, hi: ref.predict_raw(trees, Xs[lo:hi]), len(take)))
    walked = ref.binary_logloss(ys, raw)
    p = float(np.mean(st.y[:st.rows]))
    constant = -(p * np.log(p) + (1 - p) * np.log(1 - p))
    out.append(("train_logloss",
                abs(loss - walked) <= bands["logloss_atol"]
                and loss < constant,
                f"eval_train {loss:.6f} over {len(trees)} trees, reference "
                f"walker {walked:.6f} on {len(take)} rows, constant score "
                f"{constant:.6f}"))

    heldout = st.bst.predict(st.X[st.rows:], num_iteration=AUC_ITERS)
    auc = ref.auc(st.y[st.rows:], heldout)
    lo, hi = bands["auc10_floor"], bands["auc10"] + bands["auc10_band"]
    lo = max(lo, bands["auc10"] - bands["auc10_band"])
    out.append(("heldout_auc", lo <= auc <= hi,
                f"{auc:.5f} at {AUC_ITERS} trees on {len(heldout)} rows "
                f"(want {lo:.5f}..{hi:.5f}); predict path "
                f"{st.bst._gbdt.predict_path}"))
    return out
