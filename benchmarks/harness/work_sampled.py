"""The least bytes row sampling needs, whatever implements it, computed
from shapes. Kept with the benchmark, beside harness/work.py, so that no
PR that claims a gain can change what a scope is held against.

Both steps are bound by memory (they compute next to nothing), so their
rooflines are bytes over the chip's HBM bandwidth over the scope's time.
"""
from __future__ import annotations

from .work import PLANE_BYTES, code_bits

F32 = 4


def goss_counts(n: int, top_rate: float, other_rate: float) -> tuple:
    """(top_k, other_k): rows kept for their weight, rows drawn from the
    rest (goss.hpp:111-147)."""
    top_k = max(1, int(n * top_rate))
    return top_k, max(1, min(int(n * other_rate), n - top_k))


def goss_sample_bytes(n: int, rounds: int, classes: int = 1) -> int:
    """One sampling round reads every row's gradient and hessian and
    writes them back (the drawn rows come back weighted) with the
    permutation that puts the bag first: 2 reads and 2 writes per class
    and one int32 of permutation, 20 B a row for one class."""
    return rounds * n * (4 * classes * F32 + 4)


def bag_gather_bytes(bag_rows: int, groups: int, max_bin: int,
                     trees: int) -> int:
    """One gather per tree reads the bag rows' bundle codes, gradients and
    hessians and writes them as lanes of the planar state: the codes once
    in at their packed width and once out as whole int32 planes, gradient
    and hessian once in and once out."""
    bits = code_bits(max_bin)
    code_bytes_in = groups * bits / 8.0
    code_planes_out = -(-groups * bits // 32)
    per_row = code_bytes_in + PLANE_BYTES * code_planes_out + 4 * F32
    return int(trees * bag_rows * per_row)
