"""kernels (objective/rank.py; a scope, no Pallas kernel yet): device
nanoseconds under `lgbm.rank_grad` per pair of documents with different
labels, the pairs counted from the run's query sizes and labels alone
(harness/work_rank.py: the mode keeps the count as an artifact), so it
reads the same work whatever implements it. One gradient a traced
iteration. None where the scope or the count is missing."""
from benchmarks.harness import scope_shares


def read(ev):
    spent = scope_shares.seconds(ev, "lgbm.rank_grad")
    pairs = ev.artifacts.get("rank_pairs")
    if not spent or not pairs or not ev.traced:
        return None
    return 1e9 * spent / (pairs * ev.traced["units"]["iters"])
