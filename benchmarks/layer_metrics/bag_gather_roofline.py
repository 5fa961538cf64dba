"""kernels (treelearner/fused.py `_grow_tree`, the bag's gather; a scope,
no Pallas kernel yet): share of its roofline the per-tree gather of the
bag reaches. Bound by memory: the least it moves is the bag rows' bundle
codes, gradients and hessians in and their planes out
(harness/work_sampled.py), that over the chip's HBM bandwidth, over the
own time of the ops under `lgbm.bag_gather` in the traced sub-window. One
tree per traced iteration; the bundle count is what the run's dataset
made (`bundle_groups`, kept by the mode)."""
from benchmarks.harness import scope_shares, work_sampled


def read(ev):
    spent = scope_shares.seconds(ev, "lgbm.bag_gather")
    if not spent or not ev.traced or "bundle_groups" not in ev.artifacts:
        return None
    params = ev.config["params"]
    top_k, other_k = work_sampled.goss_counts(
        int(ev.config["shape"]["rows"]), float(params["top_rate"]),
        float(params["other_rate"]))
    moved = work_sampled.bag_gather_bytes(
        top_k + other_k, int(ev.artifacts["bundle_groups"]),
        int(params["max_bin"]), ev.traced["units"]["iters"])
    return 100.0 * moved / ev.peaks["hbm_bytes_per_s"] / spent
