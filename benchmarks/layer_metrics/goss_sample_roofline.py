"""kernels (boosting/gbdt.py `_goss_sample_device`; a scope, no Pallas
kernel yet): share of its roofline a GOSS sampling round reaches. Bound by
memory: the least it moves is every row's gradient and hessian in and out
and one permutation out (harness/work_sampled.py), that over the chip's
HBM bandwidth, over the own time of the ops under `lgbm.goss_sample` in
the traced sub-window. One round per traced iteration."""
from benchmarks.harness import scope_shares, work_sampled


def read(ev):
    spent = scope_shares.seconds(ev, "lgbm.goss_sample")
    if not spent or not ev.traced:
        return None
    moved = work_sampled.goss_sample_bytes(
        int(ev.config["shape"]["rows"]), ev.traced["units"]["iters"])
    return 100.0 * moved / ev.peaks["hbm_bytes_per_s"] / spent
