"""stats.py's percentiles and the table of peaks."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import stats  # noqa: E402


@pytest.mark.parametrize("p,want", [(50, 50), (95, 95), (99, 99),
                                    (100, 100), (1, 1), (0.5, 1)])
def test_percentile_is_nearest_rank(p, want):
    assert stats.percentile(range(1, 101), p) == want


def test_percentile_small_and_empty():
    assert stats.percentile([7.0], 99) == 7.0
    assert stats.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_peaks_known_and_unknown_device():
    assert stats.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            stats.peaks_for(kind)
