"""The client's timing on a scripted clock: latency runs from the due
time, a stalled drain delays the requests behind it, answers are matched
by request id, and a request that never comes back is unanswered."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import loop


class Clock:
    """Scripted time: advances only by sleeps, drains and a tiny tick per
    read (so a spinning loop moves on)."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-6
        return self.t

    def sleep(self, dt):
        self.t += max(0.0, dt)


class FakeScheduler:
    """Answers every queued request per drain, taking ``cost(n)`` seconds;
    ``hold`` request ids never come back."""

    def __init__(self, clock, cost, hold=(), reverse=False):
        self.clock, self.cost = clock, cost
        self.queue, self.next_id, self.n_drains = [], 0, 0
        self.hold, self.reverse = set(hold), reverse

    @property
    def pending(self):
        return len(self.queue)

    def submit(self, x):
        rid = self.next_id
        self.next_id += 1
        self.queue.append((rid, np.asarray(x)))
        return rid

    def drain(self):
        taken, self.queue = self.queue, []
        self.clock.t += self.cost(self.n_drains, len(taken))
        self.n_drains += 1
        out = [SimpleNamespace(request_id=rid, aux=np.full(2, int(x[0])),
                               batch_time=0.01, bucket=len(taken),
                               shed=False)
               for rid, x in taken if rid not in self.hold]
        return out[::-1] if self.reverse else out

    def flush(self):
        return self.drain() if self.queue else []


ROWS = np.arange(10, dtype=np.float32)[:, None] * np.ones((1, 3), np.float32)


def test_latency_runs_from_due_time_through_a_stalled_drain():
    clock = Clock()
    # the second drain stalls for half a second
    sched = FakeScheduler(clock, lambda i, n: 0.5 if i == 1 else 0.05)
    run = loop.run_open(sched, ROWS, np.array([0.0, 0.1, 0.2]),
                        np.array([3, 4, 5]), 1.0, 2, clock=clock,
                        sleep=clock.sleep)
    assert run.answered.all()
    np.testing.assert_allclose(run.latency, [0.05, 0.5, 0.45], atol=2e-3)
    # the third request was due at 0.2 but submitted after the stall
    np.testing.assert_allclose(run.submit - run.due, [0, 0, 0.4], atol=2e-3)
    np.testing.assert_array_equal(run.answers[:, 0], [3, 4, 5])


def test_answers_are_matched_by_request_id():
    clock = Clock()
    sched = FakeScheduler(clock, lambda i, n: 0.01, reverse=True)
    due = np.zeros(4)
    run = loop.run_open(sched, ROWS, due, np.array([1, 2, 3, 4]), 0.5, 2,
                        clock=clock, sleep=clock.sleep)
    np.testing.assert_array_equal(run.answers[:, 0], [1, 2, 3, 4])
    assert len(run.drains) == 1 and sorted(run.drains[0].requests) == \
        [0, 1, 2, 3]


def test_requests_due_at_the_close_are_served_late_not_dropped():
    clock = Clock()
    sched = FakeScheduler(clock, lambda i, n: 0.3)
    run = loop.run_open(sched, ROWS, np.array([0.0, 0.1, 0.25]),
                        np.array([0, 1, 2]), 0.3, 2, clock=clock,
                        sleep=clock.sleep)
    assert run.answered.all()
    assert run.recv[2] > run.seconds          # answered after the close
    assert run.latency[2] == pytest.approx(run.recv[2] - 0.25)


def test_a_request_that_never_returns_is_unanswered():
    clock = Clock()
    sched = FakeScheduler(clock, lambda i, n: 0.01, hold={1})
    run = loop.run_open(sched, ROWS, np.array([0.0, 0.0, 0.0]),
                        np.array([0, 1, 2]), 0.2, 2, clock=clock,
                        sleep=clock.sleep, grace=1.0)
    np.testing.assert_array_equal(run.answered, [True, False, True])
    assert run.answers[1, 0] == -1


def test_closed_loop_keeps_its_clients_busy():
    clock = Clock()
    sched = FakeScheduler(clock, lambda i, n: 0.1)
    run = loop.run_closed(sched, ROWS, np.arange(10), 4, 0.95, 2,
                          clock=clock)
    assert run.answered.all()
    # 4 answers per 0.1 s drain: 9 drains end inside the window, the
    # tenth after it, and no client sends again after the close
    assert np.sum(run.recv <= 0.95) == 36 and len(run.due) == 40
    np.testing.assert_allclose(run.latency, 0.1, atol=1e-3)
    launches = list(run.launches())
    assert all(b == 4 and len(req) == 4 for b, req in launches)
