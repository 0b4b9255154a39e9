"""Percentiles and rates from due times, and the spread of runs."""
import statistics

import numpy as np
import pytest

from benchlib import stats as ST


def test_latency_counts_from_due_time_and_unanswered_is_infinite():
    due = np.array([0.0, 1.0, 2.0])
    done = np.array([0.25, 1.5, np.nan])
    lat = ST.latencies_ms(due, done)
    assert lat[0] == pytest.approx(250) and lat[1] == pytest.approx(500)
    assert np.isinf(lat[2])


@pytest.mark.parametrize("q", [0, 50, 95, 99, 100])
def test_percentile_matches_numpy(q):
    v = np.random.default_rng(3).exponential(100, 1357)
    assert ST.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_percentile_keeps_unanswered_in_the_tail():
    v = np.concatenate([np.arange(95.0), [np.inf] * 5])
    assert ST.percentile(v, 50) == pytest.approx(49.5)
    assert np.isinf(ST.percentile(v, 99))


def test_rate_counts_only_completions_inside_the_window():
    done = np.array([0.5, 1.0, 9.9, 10.0, 10.1, np.nan])
    assert ST.rate(done, 0.0, 10.0) == pytest.approx(0.4)


def test_spread_uses_python_quartiles():
    v = [100.0, 101.0, 99.0, 104.0, 98.0, 100.5]
    q1, med, q3 = statistics.quantiles(v, n=4)
    assert ST.spread(v) == pytest.approx((q3 - q1) / med)
    assert ST.spread([1.0]) is None
