"""boosting_loop (objective/rank.py): share of the device's busy time
under the program's `lgbm.rank_grad` scope — one ranking gradient program
an iteration: the window gather into the padded query slabs, the per-query
sorts, the pair blocks and the way back to rows (its nested
`lgbm.rank_sort` / `lgbm.rank_pairs` / `lgbm.rank_to_rows` are booked to
it: the reader asks by the outermost scope);
None on a program or a trace that has no such scope
(harness/scope_shares.py); summed over the chips."""
from benchmarks.harness import scope_shares


def read(ev):
    return scope_shares.share(ev, "lgbm.rank_grad")
