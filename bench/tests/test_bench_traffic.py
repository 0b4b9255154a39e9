"""Every seed sends the same work: same queries, same due times, in
another order."""
import numpy as np
import pytest

from benchlib import traffic as T

MIX = {"loop": "open", "rate_qps": 45.0, "schedule_seed": 20261018}


@pytest.mark.parametrize("seeds", [(1, 2), (3000000001, 4294967311),
                                   (2**31 + 5, 7)])
def test_open_schedule_same_work_for_every_seed(seeds):
    a = T.open_schedule(MIX, 30.0, seeds[0], 10_000)
    b = T.open_schedule(MIX, 30.0, seeds[1], 10_000)
    assert len(a.due_s) == len(b.due_s) == 1350
    np.testing.assert_array_equal(a.due_s, b.due_s)
    np.testing.assert_array_equal(np.sort(a.query_idx),
                                  np.arange(1350))
    np.testing.assert_array_equal(np.sort(a.query_idx),
                                  np.sort(b.query_idx))
    assert not np.array_equal(a.query_idx, b.query_idx)


def test_open_schedule_is_poisson_inside_the_window():
    s = T.open_schedule(MIX, 30.0, 9, 10_000)
    assert np.all(np.diff(s.due_s) > 0)
    assert s.due_s[0] >= 0 and s.due_s[-1] < 30.0
    gaps = np.diff(s.due_s) * MIX["rate_qps"]
    # unit-rate exponential gaps: mean and spread both near 1
    assert abs(gaps.mean() - 1) < 0.1 and abs(gaps.std() - 1) < 0.15


def test_same_seed_same_schedule():
    a = T.open_schedule(MIX, 30.0, 11, 10_000)
    b = T.open_schedule(MIX, 30.0, 11, 10_000)
    np.testing.assert_array_equal(a.query_idx, b.query_idx)


def test_bursts_keep_count_and_window():
    t = T.arrival_offsets(1000, 30.0, 5, {"on_s": 1.0, "off_s": 3.0})
    assert len(t) == 1000 and t.max() < 30.0
    phase = np.mod(t, 4.0)
    assert np.all(phase < 1.0 + 1e-9)


def test_open_schedule_refuses_more_requests_than_queries():
    with pytest.raises(ValueError):
        T.open_schedule(MIX, 30.0, 1, 1000)


def test_closed_order_permutes_the_whole_set():
    a, b = T.closed_order(1, 500), T.closed_order(2, 500)
    np.testing.assert_array_equal(np.sort(a), np.arange(500))
    assert not np.array_equal(a, b)
