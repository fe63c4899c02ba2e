"""Latency, percentile and schedule arithmetic."""

from collections import Counter

import numpy as np
import pytest

from benchlib import stats, traffic


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy_linear(q):
    xs = np.random.default_rng(1).exponential(size=37).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_refuses_no_samples_and_bad_q():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_latency_runs_from_the_due_time_in_an_open_loop():
    from benchlib.harness import Sent

    held_up = Sent(params={}, request=None, due=1.5, sent=1.5, done=2.1)
    assert held_up.latency_ms == pytest.approx(600.0)
    late_sender = Sent(params={}, request=None, due=1.0, sent=1.3, done=1.4)
    assert late_sender.latency_ms == pytest.approx(400.0)     # the sender's delay counts
    closed = Sent(params={}, request=None, sent=3.0, done=3.25)
    assert closed.latency_ms == pytest.approx(250.0)           # closed loop: from the call


def test_apportion_largest_remainder():
    assert traffic.apportion([1, 1, 1], 10) == [4, 3, 3]
    assert sum(traffic.apportion([1.0, 0.5, 0.33], 7)) == 7
    assert traffic.apportion([3, 1], 0) == [0, 0]


MIX = {"loop": "open", "rate_per_s": 4.0, "arrivals_seed": 8, "query": {"name": "q"},
       "pick": {"month": {"values": ["c", "b", "a"], "zipf": 1.0}}}


def test_open_schedule_same_work_for_every_seed():
    a = traffic.make_schedule(MIX, 1, 30)
    b = traffic.make_schedule(MIX, 2**31 + 77, 30)
    assert len(a.params) == len(b.params) == 120
    count = lambda s: sorted(Counter(p["month"] for p in s.params).items())
    assert count(a) == count(b) == [("a", 22), ("b", 33), ("c", 65)]
    assert a.params != b.params
    assert a.due_s == b.due_s               # the same arrivals, another order of requests
    for s in (a, b):
        assert s.due_s == sorted(s.due_s) and 0 <= s.due_s[0] and s.due_s[-1] < 30
    assert [p["month"] for p in a.distinct] == ["a", "b", "c"]
    # the same seed gives the same schedule
    again = traffic.make_schedule(MIX, 1, 30)
    assert again.params == a.params and again.due_s == a.due_s


def test_closed_schedule_has_no_due_times():
    s = traffic.make_schedule({"loop": "closed", "query": {"pattern": "triangle"}}, 5, 10)
    assert s.due_s is None and s.distinct == [{}] and len(s.params) == traffic.CLOSED_CYCLE
    with pytest.raises(ValueError):
        traffic.make_schedule({"loop": "sideways", "query": {}}, 5, 10)


def test_rows_off_counts_the_multiset_difference():
    from benchlib.harness import rows_off

    want = np.array([[1, 2, 3], [1, 2, 4], [2, 3, 4]])
    assert rows_off(want.copy(), want) == 0
    assert rows_off(want[:2], want) == 1
    assert rows_off(np.concatenate([want, want[:1]]), want) == 1      # a duplicate
    assert rows_off(np.array([[1, 2, 3], [1, 2, 4], [2, 3, 5]]), want) == 2
    assert rows_off(np.zeros((0, 3)), want) == 3
    assert rows_off(7, 9) == 2 and rows_off(9, 9) == 0
