"""harness/work_rank.py: the pair count against a brute-force count over
every pair of every query, and the bytes on a shape small enough to count
by hand."""
import numpy as np

from benchmarks.harness import work_rank as wr
from benchmarks.reference import lambdarank_numpy as rank


def test_label_pairs_equal_a_brute_force_count():
    rng = np.random.default_rng(3)
    group = np.array([1, 2, 7, 8, 9, 130, 33, 1, 5])
    label = rng.integers(0, 5, group.sum())
    want = rank.count_pairs(label, group)
    assert wr.label_pairs(group, label) == want > 0
    # and by the plainest count there is
    bounds = np.concatenate([[0], np.cumsum(group)])
    plain = sum(1 for q in range(len(group))
                for a in range(bounds[q], bounds[q + 1])
                for b in range(a + 1, bounds[q + 1]) if label[a] != label[b])
    assert plain == want


def test_label_pairs_of_one_label_queries_and_of_one_document():
    assert wr.label_pairs([4, 1, 3], [2, 2, 2, 2, 0, 1, 1, 1]) == 0
    assert wr.label_pairs([3], [0, 1, 2]) == 3
    assert wr.label_pairs([2, 2], [0, 1, 1, 0]) == 2


def test_rank_grad_bytes():
    # score, label, gradient, hessian: 16 B a row; place and inverse max
    # DCG: 8 B a query
    assert wr.rank_grad_bytes(1000, 10, rounds=1) == 16_080
    assert wr.rank_grad_bytes(1000, 10, rounds=5) == 80_400
