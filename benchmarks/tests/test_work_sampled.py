"""harness/work_sampled.py on shapes small enough to count by hand."""
from benchmarks.harness import work_sampled as ws


def test_goss_counts_follow_the_reference():
    assert ws.goss_counts(22_000_000, 0.2, 0.1) == (4_400_000, 2_200_000)
    assert ws.goss_counts(10, 0.01, 0.01) == (1, 1)
    assert ws.goss_counts(10, 0.9, 0.5) == (9, 1)       # never past n


def test_goss_sample_bytes():
    # g and h in and out (16 B) and an int32 of permutation: 20 B a row
    assert ws.goss_sample_bytes(1000, rounds=1) == 20_000
    assert ws.goss_sample_bytes(1000, rounds=5) == 100_000
    assert ws.goss_sample_bytes(1000, rounds=1, classes=3) == 52_000


def test_bag_gather_bytes():
    # 13 byte codes in, 4 int32 planes out, g and h in and out: 13+16+16
    assert ws.bag_gather_bytes(1000, groups=13, max_bin=255, trees=1) == 45_000
    assert ws.bag_gather_bytes(1000, groups=13, max_bin=255, trees=5) == 225_000
    # 4-bit codes: 8 columns in 4 bytes, one plane
    assert ws.bag_gather_bytes(10, groups=8, max_bin=15, trees=1) == 10 * 24
