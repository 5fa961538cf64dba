"""harness/work.py against cases worked by hand."""
from benchmarks.harness import work


def test_code_bits():
    assert [work.code_bits(b) for b in (15, 16, 17, 63, 255, 256, 257)] == \
        [4, 4, 8, 8, 8, 8, 16]


def test_planar_planes():
    # HIGGS: 28 one-byte codes = 7 words, + 5 per-row planes = 12 -> 16
    assert work.planar_planes(28, 8) == 16
    # Epsilon: 2,000 one-byte codes = 500 words, + 5 = 505 -> 512
    assert work.planar_planes(2000, 8) == 512
    # Allstate's 581 bundles at 4 bits: 73 words, + 5 = 78 -> 80
    assert work.planar_planes(581, 4) == 80


def test_partition_bytes():
    # a root of 1,000 rows and a child of 400, 16 planes of 4 bytes, each
    # row read once and written once
    assert work.partition_bytes([1000, 400], 16) == 2 * 4 * 16 * 1400
    assert work.partition_bytes([], 16) == 0

