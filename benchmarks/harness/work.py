"""The least work the algorithm needs, computed from shapes and from the
model the run produced. Kept with the benchmark so that no PR that claims
a gain can change what a kernel is held against.
"""
from __future__ import annotations

PLANE_BYTES = 4         # the planar training state is int32, one lane a row
AUX_PLANES = 5          # gradient, hessian, row id, label, score


def code_bits(max_bin: int) -> int:
    """Bits of one bin code in the planar state: the dense-bin packing of
    the reference (4 bits up to 16 bins, then one byte, then two)."""
    return 4 if max_bin <= 16 else 8 if max_bin <= 256 else 16


def planar_planes(num_cols: int, code_bits: int) -> int:
    """Planes of the [P, rows] training state every partition moves: the
    bin codes packed into 32-bit words, then the five per-row planes,
    rounded up to the 8-sublane tile."""
    code_planes = -(-num_cols * code_bits // 32)
    return -(-(code_planes + AUX_PLANES) // 8) * 8


def partition_bytes(internal_counts, num_planes: int) -> int:
    """Bytes a stable partition has to move for these splits: each split
    reads and writes every plane of every row of the leaf it splits.
    `internal_counts`: rows of each split leaf (the model's
    internal_count, over the trees in question)."""
    return 2 * PLANE_BYTES * num_planes * int(sum(internal_counts))

