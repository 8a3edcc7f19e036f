"""The peaks table and the bytes a sampled walk step needs."""

from __future__ import annotations

import pytest

from benchtest_util import BENCH  # noqa: F401  (puts the benchmark on the path)

import roofline


def test_bytes_per_step_counts_the_walks_work():
    # prev, cur, hop read and written (24), two CSR offsets (8), one
    # neighbour (4), and the corpus write when recording (4)
    assert roofline.advance_bytes_per_step(record=True) == 40
    assert roofline.advance_bytes_per_step(record=False) == 36


def test_v5e_peaks_and_unknown_device():
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flop_per_s"] == 197e12
    assert peaks["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")


def test_roofline_share():
    # 819e9 B/s moves 40 B x 20.475e9 steps in one second
    assert roofline.advance_roofline_pct(20_475_000_000, 1.0, record=True, hbm_bytes_per_s=819e9) == pytest.approx(100.0)
    assert roofline.advance_roofline_pct(1000, 0.0, record=True, hbm_bytes_per_s=819e9) is None


def test_roofline_share_over_four_chips():
    """The same work on four chips has four times the bandwidth to meet it."""
    args = (1_000_000_000, 0.5)
    one = roofline.advance_roofline_pct(*args, record=True, hbm_bytes_per_s=819e9)
    assert roofline.advance_roofline_pct(*args, record=True, hbm_bytes_per_s=819e9, chips=1) == one
    four = roofline.advance_roofline_pct(*args, record=True, hbm_bytes_per_s=819e9, chips=4)
    assert four == pytest.approx(one / 4)
