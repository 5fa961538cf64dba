"""The work DART's replay of its dropped trees needs, whatever implements
it, from the drop sets the run recorded and the model it produced. Kept
with the benchmark, beside harness/work.py, so that no PR that claims a
gain can change what the replay kernel is held against.

Replaying a tree is compute: every row steps through the tree's splits,
so the kernel's yardstick is time a row and a split (`lane_splits`); its
memory roofline (`replay_bytes`) is the least a round's kernel must move,
and reads low by design: how far from free the per-split work is. Both
are held against the kernel's own time, read by `KERNEL`, the name its
Pallas call gives its custom call in the trace.
"""
from __future__ import annotations

from .work import PLANE_BYTES, code_bits

F32 = 4
KERNEL = "replay_forest_pallas"


def lane_splits(rows: int, drops, tree_splits) -> int:
    """Rows x the splits of every tree the rounds in `drops` replayed:
    `drops` one tuple of dropped tree indices a round (one tree an
    iteration), `tree_splits[t]` tree t's internal nodes in the model."""
    return int(rows) * sum(int(tree_splits[t]) for d in drops for t in d)


def replay_bytes(rows: int, cols: int, max_bin: int, rounds: int) -> int:
    """A round that drops any tree reads the bin codes' planes once
    (whatever the trees) and writes the dropped trees' sum, 4 B a row.
    The score's pass beside it is not the kernel's."""
    planes = -(-cols * code_bits(max_bin) // 32)
    return int(rounds) * int(rows) * (PLANE_BYTES * planes + F32)
