"""Arithmetic of the end-to-end metrics (benchmark/stats.py)."""

import numpy as np
import pytest

from benchmark import stats


@pytest.mark.parametrize("q,want", [(50, 5.0), (90, 9.0), (99, 10.0),
                                    (100, 10.0), (1, 1.0)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(np.arange(1, 11), q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_closed_loop_counts_requests_completed_inside_the_window():
    t_send = np.array([9.0, 9.9, 10.5, 19.7, 19.95])
    t_done = np.array([9.5, 10.1, 10.9, 19.9, 20.2])
    ok = np.array([True, True, False, True, True])
    mask, lat = stats.closed_loop_window(t_send, t_done, ok, 10.0, 10.0)
    assert mask.tolist() == [False, True, False, True, False]
    np.testing.assert_allclose(lat, [200.0, 200.0])


def test_open_loop_latency_runs_from_the_due_time():
    # the generator sent the second request 50 ms late: that wait counts
    t_due = np.array([10.0, 10.1, 25.0])
    t_done = np.array([10.02, 10.2, 25.1])
    due, mask, lat = stats.open_loop_window(
        t_due, t_done, np.array([True, True, True]), 10.0, 10.0)
    assert due.tolist() == [True, True, False]
    np.testing.assert_allclose(lat, [20.0, 100.0])
    np.testing.assert_allclose(
        stats.lateness_ms(t_due[:2], np.array([10.0, 10.15])), [0.0, 50.0])


def test_a_stalled_window_shows_in_rate_and_tail():
    """A 2 s stall in a 10 s open-loop window at 10/s: the rate is over
    the whole window, and every request due in the stall waits it out."""
    t_due = 100.0 + np.arange(100) * 0.1
    service = 0.01
    t_done = np.where((t_due >= 103.0) & (t_due < 105.0), 105.0 + service,
                      t_due + service)
    due, mask, lat = stats.open_loop_window(
        t_due, t_done, np.ones(100, bool), 100.0, 10.0)
    assert due.sum() == 100 and mask.sum() == 100
    assert stats.rate(mask.sum(), 10.0) == 10.0
    assert stats.percentile(lat, 50) == pytest.approx(10.0)
    assert stats.percentile(lat, 99) == pytest.approx(1910.0)


def test_failed_requests_have_no_latency_and_count_as_failed():
    t_due = np.array([1.0, 2.0, 3.0])
    ok = np.array([True, False, True])
    due, mask, lat = stats.open_loop_window(t_due, t_due + 0.5, ok, 0.0, 10.0)
    assert int(due.sum()) - int(mask.sum()) == 1 and lat.size == 2


def test_rate_needs_a_window():
    with pytest.raises(ValueError):
        stats.rate(10, 0.0)


def test_open_loop_schedule_same_gaps_for_every_seed_in_another_order():
    a = stats.open_loop_schedule(200.0, 10.0, gaps_seed=7, order_seed=1)
    b = stats.open_loop_schedule(200.0, 10.0, gaps_seed=7, order_seed=2)
    assert a.size == b.size == 2000 and a[0] == b[0] == 0.0
    ga, gb = np.diff(a), np.diff(b)
    assert not np.allclose(ga, gb) and np.all(ga >= 0) and a[-1] < 10.0
    # the same multiset of gaps: each schedule only hides its own first gap
    assert len(set(np.round(ga, 12)) ^ set(np.round(gb, 12))) <= 2
    again = stats.open_loop_schedule(200.0, 10.0, gaps_seed=7, order_seed=1)
    np.testing.assert_array_equal(a, again)
