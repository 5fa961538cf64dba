"""kernels (objective/rank.py; a scope, no Pallas kernel yet): share of
its memory roofline a ranking gradient reaches: the least it moves is
every row's score and label in and gradient and hessian out and two
numbers a query (harness/work_rank.py), that over the chip's HBM
bandwidth, over the own time of the ops under `lgbm.rank_grad` in the
traced sub-window. The pair terms are compute, so this reads far below
1 %: how far from free the pair work is. One gradient a traced
iteration."""
from benchmarks.harness import scope_shares, work_rank


def read(ev):
    spent = scope_shares.seconds(ev, "lgbm.rank_grad")
    queries = ev.artifacts.get("rank_queries")
    if not spent or not queries or not ev.traced or not ev.peaks:
        return None
    moved = work_rank.rank_grad_bytes(
        int(ev.config["shape"]["rows"]), queries,
        ev.traced["units"]["iters"])
    return 100.0 * moved / ev.peaks["hbm_bytes_per_s"] / spent
