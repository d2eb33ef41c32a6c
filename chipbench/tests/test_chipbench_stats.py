"""Rates and percentiles run over every sample, so a stall moves them."""
import numpy as np
import pytest

from stats import percentile, rate, token_gaps


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy(q):
    xs = np.random.default_rng(0).lognormal(size=1001)
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_a_stall_moves_the_tail_and_the_rate():
    # 8 lanes, one token each per 0.1 s tick, for 100 ticks
    events = [(0.1 * (t + 1), lane) for t in range(100) for lane in range(8)]
    gaps = token_gaps(events)
    assert len(gaps) == 8 * 99
    assert percentile(gaps, 95) == pytest.approx(0.1)
    base_rate = rate(len(events), 10.0)
    # a 0.5 s stall before every 10th tick
    stalled = [(0.1 * (t + 1) + 0.5 * ((t + 9) // 10), lane)
               for t in range(100) for lane in range(8)]
    sgaps = token_gaps(stalled)
    assert percentile(sgaps, 95) == pytest.approx(0.6)
    assert rate(len(stalled), stalled[-1][0]) < 0.7 * base_rate


def test_token_gaps_are_per_request():
    assert token_gaps([(1.0, "a"), (1.5, "b"), (2.0, "a"), (4.0, "b")]) \
        == [1.0, 2.5]


def test_empty_samples_and_windows_are_errors():
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        rate(10, 0.0)
