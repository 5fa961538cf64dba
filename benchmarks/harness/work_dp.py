"""The least bytes a chip's links have to carry for the allreduces of
data-parallel tree growth, computed from shapes and from the model the run
produced. Kept with the benchmark, as harness/work.py is.

Data parallel (LightGBM's Parallel Learning Guide, `tree_learner=data`):
every chip histograms its own rows and the chips sum their histograms, so
that each finds the same split. With histogram subtraction the sum is
needed once for the root and once per split, for the smaller child; the
rows that went left are counted the same way. A ring allreduce over D
chips sends and receives 2 (D - 1) / D of the payload over each chip's
links, and no allreduce carries less.
"""
from __future__ import annotations

HIST_BYTES = 4          # float32 sums of gradients and of hessians
COUNT_BYTES = 4         # one int32 of rows


def hist_payload_bytes(num_cols: int, max_bin: int) -> int:
    """One `[cols, max_bin + 1, 2]` float32 histogram (bins 0..max_bin)
    and the int32 row count that goes with it."""
    return num_cols * (max_bin + 1) * 2 * HIST_BYTES + COUNT_BYTES


def ring_factor(chips: int) -> float:
    """Share of a payload that a ring allreduce moves over each chip's
    links: 2 (D - 1) / D; nothing on one chip."""
    return 2.0 * (chips - 1) / chips


def allreduce_bytes(splits_per_tree, num_cols: int, max_bin: int,
                    chips: int) -> float:
    """Bytes over one chip's links for these trees: per tree the root's
    histogram, and per split the smaller child's, each with its count.
    `splits_per_tree`: the number of splits of each tree in question (the
    length of the model's internal_count)."""
    sums = sum(1 + int(s) for s in splits_per_tree)
    return sums * hist_payload_bytes(num_cols, max_bin) * ring_factor(chips)
