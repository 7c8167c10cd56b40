"""Roofline arithmetic against the peak table."""
import pytest

from bench import roofline


def test_share_against_v5e_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["flops_per_s"] == 197e12
    # 819 kB takes 1 us at 819 GB/s: half of a 2 us kernel
    share, bound = roofline.share_pct(1e3, 819e3, 2e-6, "TPU v5 lite")
    assert bound == "memory"
    assert share == pytest.approx(50.0)
    share, bound = roofline.share_pct(197e9, 1.0, 2e-3, "TPU v5 lite")
    assert bound == "compute"
    assert share == pytest.approx(50.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.share_pct(1.0, 1.0, 1.0, "TPU v99")


def test_work_counts_grow_with_inputs():
    o1, b1 = roofline.slowdown_work(8, 7)
    o2, b2 = roofline.slowdown_work(16, 7)
    assert o2 == 2 * o1
    assert b2 > b1
    assert roofline.slowdown_work(100, 7) == (100 * (9 * 7 + 3),
                                              4 * (700 + 7 + 300))
    assert roofline.walk_work(6, 1) == (5 * 6 + 12, 5 * 6 + 24 + 16)
