"""The client: drives a scheduler's ``submit``/``drain`` on the wall clock.

One thread.  Each turn of the open loop submits every request whose due
time has passed, then calls ``drain()`` while the scheduler holds work.
A request's latency runs from its due time to the moment its result
comes back from ``drain()`` or ``flush()``.  Results are matched by
``request_id`` and nothing is assumed about which call returns which
request, so a scheduler that returns results later than the launch that
computed them is timed by the same code.

After the window closes the loop submits what was due inside it and
waits, at most ``grace`` seconds, for every answer: a late answer is
late, one that never comes is failed.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, List

import numpy as np

GRACE_S = 60.0
_SPIN_S = 1e-3      # sleep until this close to a due time, then spin
# per-request arrays and their value before the request is issued/answered
_FILL = {"due": 0.0, "pool_idx": 0, "submit_t": np.nan, "recv": np.inf,
         "batch_time": np.nan, "bucket": 0, "shed": False}


def no_span(name: str):
    return contextlib.nullcontext()


@dataclass
class Drain:
    start: float             # seconds after the window opened
    end: float
    requests: List[int]      # request indices answered by this call


@dataclass
class Run:
    """What a window produced, per request and per drain."""
    seconds: float
    due: np.ndarray
    pool_idx: np.ndarray
    submit: np.ndarray
    recv: np.ndarray
    batch_time: np.ndarray
    bucket: np.ndarray       # bucket of the launch that answered it
    answers: np.ndarray      # (n, k) neighbour ids, -1 where unanswered
    shed: np.ndarray
    drains: List[Drain] = field(default_factory=list)

    @property
    def answered(self) -> np.ndarray:
        return np.isfinite(self.recv) & ~self.shed

    @property
    def latency(self) -> np.ndarray:
        return (self.recv - self.due)[self.answered]

    def launches(self):
        """(bucket, request indices) per launch: a drain's answers grouped
        by the bucket that computed them."""
        for dr in self.drains:
            req = np.asarray(dr.requests)
            req = req[~self.shed[req]]
            for b in np.unique(self.bucket[req]):
                yield int(b), req[self.bucket[req] == b]


class _Client:
    def __init__(self, sched, rows, k: int, clock: Callable[[], float],
                 span=no_span, capacity: int = 1024):
        self.sched, self.rows, self.k = sched, rows, k
        self.clock, self.span = clock, span
        self.t0 = 0.0
        self.rid_to_req = {}
        self.drains: List[Drain] = []
        for name, fill in _FILL.items():
            setattr(self, name, np.full(capacity, fill))
        self.answers = np.full((capacity, k), -1, np.int64)
        self.n = 0

    def now(self) -> float:
        return self.clock() - self.t0

    def _grow(self) -> None:
        for name, fill in _FILL.items():
            a = getattr(self, name)
            setattr(self, name, np.concatenate([a, np.full_like(a, fill)]))
        self.answers = np.concatenate(
            [self.answers, np.full_like(self.answers, -1)])

    def issue(self, due: float, pool: int) -> None:
        if self.n == len(self.due):
            self._grow()
        i = self.n
        self.n += 1
        self.due[i], self.pool_idx[i] = due, pool
        rid = self.sched.submit(self.rows[pool])
        self.submit_t[i] = self.now()
        self.rid_to_req[rid] = i

    def collect(self, call: Callable, start: float) -> List[int]:
        with self.span("drain"):
            out = call()
        end = self.now()
        got = []
        with self.span("result"):
            for r in out:
                i = self.rid_to_req.pop(r.request_id)
                self.recv[i] = end
                self.batch_time[i] = r.batch_time
                if r.shed:
                    self.shed[i] = True
                else:
                    self.answers[i] = np.asarray(r.aux)[:self.k]
                    self.bucket[i] = r.bucket
                got.append(i)
        if got:
            self.drains.append(Drain(start, end, got))
        return got

    def settle(self, limit: float) -> None:
        """Drain until every issued request has an answer, or until
        ``limit`` seconds after the window opened."""
        while self.rid_to_req and self.now() < limit:
            start = self.now()
            if self.sched.pending:
                self.collect(self.sched.drain, start)
            else:
                self.collect(self.sched.flush, start)
                if self.rid_to_req and not self.sched.pending:
                    break       # nothing queued and nothing returned

    def result(self, seconds: float) -> Run:
        n = self.n
        return Run(seconds=seconds, due=self.due[:n].copy(),
                   pool_idx=self.pool_idx[:n].copy(),
                   submit=self.submit_t[:n].copy(), recv=self.recv[:n].copy(),
                   batch_time=self.batch_time[:n].copy(),
                   bucket=self.bucket[:n].copy(),
                   answers=self.answers[:n].copy(), shed=self.shed[:n].copy(),
                   drains=self.drains)


def run_open(sched, rows, due, pool_idx, seconds: float, k: int, *,
             clock: Callable[[], float] = time.perf_counter,
             sleep: Callable[[float], None] = time.sleep, span=no_span,
             grace: float = GRACE_S) -> Run:
    """Open loop: request ``i`` is due ``due[i]`` seconds after the window
    opens and asks for ``rows[pool_idx[i]]``."""
    c = _Client(sched, rows, k, clock, span, capacity=max(1, len(due)))
    n = len(due)
    i = 0
    c.t0 = clock()
    with span("window"):
        while True:
            now = c.now()
            if now >= seconds:
                break
            if i < n and due[i] <= now:
                with span("submit"):
                    while i < n and due[i] <= now:
                        c.issue(float(due[i]), int(pool_idx[i]))
                        i += 1
            if sched.pending:
                c.collect(sched.drain, now)
                continue
            nxt = min(float(due[i]) if i < n else seconds, seconds)
            wait = nxt - c.now()
            if wait > _SPIN_S:
                with span("wait"):
                    sleep(wait - _SPIN_S)
    while i < n and due[i] < seconds:       # due inside the window
        c.issue(float(due[i]), int(pool_idx[i]))
        i += 1
    c.settle(seconds + grace)
    return c.result(seconds)


def run_closed(sched, rows, stream, outstanding: int, seconds: float,
               k: int, *, clock: Callable[[], float] = time.perf_counter,
               span=no_span, grace: float = GRACE_S) -> Run:
    """Closed loop: ``outstanding`` clients each send their next request
    (the next entry of ``stream``) as soon as their last one returns."""
    c = _Client(sched, rows, k, clock, span, capacity=4 * outstanding)
    j = 0

    def next_query() -> int:
        nonlocal j
        q = int(stream[j % len(stream)])
        j += 1
        return q

    c.t0 = clock()
    with span("window"):
        with span("submit"):
            for _ in range(outstanding):
                c.issue(0.0, next_query())
        while c.now() < seconds:
            got = c.collect(sched.drain, c.now())
            with span("submit"):
                for i in got:
                    if c.recv[i] < seconds:
                        c.issue(float(c.recv[i]), next_query())
    c.settle(seconds + grace)
    return c.result(seconds)
