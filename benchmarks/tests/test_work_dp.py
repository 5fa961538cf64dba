"""harness/work_dp.py against cases worked by hand."""
import pytest

from benchmarks.harness import work_dp


def test_hist_payload_bytes():
    # HIGGS: 28 columns x 256 bins x (gradient, hessian) x 4 bytes, and
    # the int32 count of the rows that went left
    assert work_dp.hist_payload_bytes(28, 255) == 28 * 256 * 2 * 4 + 4 \
        == 57_348
    assert work_dp.hist_payload_bytes(2000, 63) == 2000 * 64 * 8 + 4


def test_ring_factor():
    assert work_dp.ring_factor(1) == 0.0        # nothing leaves the chip
    assert work_dp.ring_factor(2) == 1.0
    assert work_dp.ring_factor(4) == 1.5
    assert work_dp.ring_factor(8) == 1.75


def test_allreduce_bytes():
    # one full 255-leaf tree on four chips: the root and 254 splits
    assert work_dp.allreduce_bytes([254], 28, 255, 4) == 255 * 57_348 * 1.5
    # five traced trees, one of them cut short; a stump still sums its root
    assert work_dp.allreduce_bytes([254, 254, 254, 100, 0], 28, 255, 4) \
        == pytest.approx((4 * 255 - 154 + 1) * 57_348 * 1.5)
    assert work_dp.allreduce_bytes([], 28, 255, 4) == 0.0
    assert work_dp.allreduce_bytes([254], 28, 255, 1) == 0.0
