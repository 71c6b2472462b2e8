"""``roofline.py`` on hand-worked shapes."""

import pytest

import roofline


def test_bytes_per_topic_by_hand():
    # telemetry-1m: 4 patterns (exact and one '+' at each of 3 levels),
    # tokenized to 8 levels. Gathers 4 * 64 = 256 B; tokens up
    # (2*8 + 2) * 4 = 72 B; ranges down (2*4 + 2) * 4 = 40 B: 368 B a topic.
    assert roofline.match_bytes(1, 4, 8) == 368
    assert roofline.match_bytes(4096, 4, 8) == 4096 * 368
    # one pattern, one level: 64 + 16 + 16
    assert roofline.match_bytes(10, 1, 1) == 960


def test_least_seconds_is_bytes_over_hbm_bandwidth():
    # 819 GB/s: 4096 topics * 368 B = 1,507,328 B -> 1.8404 us
    s = roofline.least_seconds(4096, 4, 8, "TPU v5 lite")
    assert s == pytest.approx(1507328 / 819e9)
    assert 1.83e-6 < s < 1.85e-6


def test_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        roofline.peak("cpu")
    with pytest.raises(KeyError):
        roofline.peak("source")
