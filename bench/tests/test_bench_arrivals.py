"""Arrival schedules are a pure function of (seed, rate, window)."""
import numpy as np
import pytest

from bench import arrivals


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40 + 3])
def test_poisson_is_a_function_of_seed_and_rate(seed):
    a = arrivals.poisson(seed, 250.0, 4.0, 100)
    b = arrivals.poisson(seed, 250.0, 4.0, 100)
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.pool_idx, b.pool_idx)
    assert len(a.due) == 1000                    # round(rate * seconds)
    assert np.all(np.diff(a.due) >= 0) and a.due[0] >= 0 and a.due[-1] < 4
    assert a.pool_idx.min() >= 0 and a.pool_idx.max() < 100
    c = arrivals.poisson(seed + 1, 250.0, 4.0, 100)
    assert not np.array_equal(a.due, c.due)


def test_every_seed_offers_the_same_work_in_another_order():
    counts = {len(arrivals.poisson(s, 1234.5, 3.0, 10).due)
              for s in range(20)}
    assert counts == {round(1234.5 * 3.0)}


def test_gaps_look_exponential():
    due = arrivals.poisson(3, 1000.0, 20.0, 10).due
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1e-3, rel=0.05)
    assert gaps.std() == pytest.approx(1e-3, rel=0.1)


def test_rate_is_fixed_or_a_share_of_the_knee():
    cfg = {"knee_qps": 5000.0, "data": {"pool": 10}}
    assert arrivals.rate_qps({"rate": {"knee_share": 0.8}}, cfg) == 4000.0
    assert arrivals.rate_qps({"rate": {"qps": 100}}, cfg) == 100.0
    sched = arrivals.open_schedule(
        {"arrivals": "poisson", "rate": {"qps": 100}}, cfg, 1, 2.0)
    assert len(sched.due) == 200


def test_closed_stream_is_seeded():
    a = arrivals.query_stream(5, 100, 7)
    np.testing.assert_array_equal(a, arrivals.query_stream(5, 100, 7))
    assert a.max() < 7
