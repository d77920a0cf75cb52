"""The percentile and sample-count rule, and the histogram arithmetic."""

import numpy as np
import pytest

from benchmark.harness import stats


@pytest.mark.parametrize("n,p,reported", [
    (1000, 99.0, True), (999, 99.0, False), (100, 90.0, True),
    (99, 90.0, False), (20, 50.0, True), (19, 50.0, False), (0, 50.0, False)])
def test_a_percentile_needs_ten_samples_beyond_it(n, p, reported):
    value = stats.percentile(np.arange(n, dtype=float), p)
    assert (value is not None) == reported


def test_percentile_value():
    assert stats.percentile(np.arange(1001.0), 99.0) == pytest.approx(990.0)
    assert stats.percentile(np.arange(101.0), 50.0) == pytest.approx(50.0)


def test_histogram_difference_and_percentile():
    from fmda_tpu.obs.registry import LatencyHistogram

    h = LatencyHistogram("x")
    for _ in range(100):
        h.observe(0.5)  # before the window
    before = h.snapshot()
    for v in np.linspace(1e-3, 3e-3, 1000):
        h.observe(float(v))
    d = stats.hist_diff(h.snapshot(), before)
    assert d["n"] == 1000
    assert stats.hist_mean_s(d) == pytest.approx(2e-3, rel=1e-6)
    # accurate to a bin (26 %)
    assert stats.hist_percentile_s(d, 50.0) == pytest.approx(2e-3, rel=0.26)
    assert stats.hist_percentile_s({"counts": [0] * 80, "n": 0,
                                    "total_s": 0.0}, 50.0) is None


def test_spread_is_interquartile_over_median():
    assert stats.spread([9.0, 10.0, 10.0, 10.0, 11.0]) == pytest.approx(0.0)
    assert stats.spread([8.0, 9.0, 10.0, 11.0, 12.0]) == pytest.approx(0.2)
