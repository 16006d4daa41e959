"""Roofline byte counts against hand-counted shapes, and the peak table."""
import numpy as np
import pytest

from lsmbench import roofline


def test_probe_bytes_counts_only_tables_whose_range_holds_the_key():
    keys = np.array([5, 50, 500], np.uint32)
    lo = [0, 40, 0]
    hi = [10, 60, 1000]
    k = [7, 5, 3]
    # key 5: tables 0 and 2 (7 + 3 words); 50: tables 1 and 2 (5 + 3);
    # 500: table 2 (3); three keys of 4 bytes
    want = 3 * 4 + (7 + 3 + 5 + 3 + 3) * 4
    assert roofline.probe_bytes(keys, lo, hi, k) == want


def test_probe_bytes_range_ends_are_inclusive():
    assert roofline.probe_bytes(np.array([9], np.uint32), [9], [9], [2]) \
        == 4 + 2 * 4


def test_share_is_percent_of_the_least_time():
    # 819 MB in 2 ms at 819 GB/s: the least time is 1 ms, so 50%
    assert roofline.share(819_000_000, 2e-3, 819e9) == pytest.approx(50.0)
    assert roofline.share(100, 0.0, 819e9) is None
    assert roofline.share(0, 1.0, 819e9) is None


def test_peaks_table():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
