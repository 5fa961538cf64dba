"""harness/work_dart.py on cases small enough to count by hand."""
from benchmarks.harness import work_dart as wd


def test_lane_splits_count_every_replayed_tree_once_a_round():
    splits = [254, 10, 3, 0]
    # round one drops trees 0 and 2, round two skips, round three tree 0
    assert wd.lane_splits(1000, [(0, 2), (), (0,)], splits) == \
        1000 * (254 + 3 + 254)
    assert wd.lane_splits(1000, [(), ()], splits) == 0
    assert wd.lane_splits(7, [(3,)], splits) == 0


def test_replay_bytes():
    # 28 columns at 8 bits: 7 code planes of 4 B, then 4 B a row of
    # the dropped trees' sum out
    assert wd.replay_bytes(1000, 28, 255, rounds=1) == 1000 * (7 * 4 + 4)
    assert wd.replay_bytes(1000, 28, 255, rounds=3) == 3 * 32_000
    # 16 bins or fewer: 4-bit codes, 14 columns in 2 planes
    assert wd.replay_bytes(10, 14, 15, rounds=1) == 10 * (2 * 4 + 4)
    assert wd.replay_bytes(10, 14, 15, rounds=0) == 0
