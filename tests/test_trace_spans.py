"""Program spans and the scheduler's wall-clock queue counter: each
launching drain opens its phases once, nested and in order (seen through
a stand-in for ``TraceAnnotation`` and in a CPU profiler trace of a small
scheduler run); under the chaos virtual clock ``RequestResult.queue_s``
is hand-computable and replays bit for bit."""
import glob
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from conftest import synth_blobs
from repro.core import estimator as E
from repro.runtime.chaos import ChaosInjector, ChaosPlan
from repro.serving import (
    ModelStore,
    NonNeuralServeEngine,
    RequestScheduler,
    poisson_trace,
    replay_trace,
)
from repro.serving import engine as engine_mod
from repro.serving import scheduler as scheduler_mod

SCHEDULER_SPANS = ("scheduler.drain", "scheduler.coalesce",
                   "scheduler.ready", "scheduler.fetch", "scheduler.scatter")
ENGINE_SPANS = ("engine.classify", "engine.put", "engine.dispatch",
                "engine.slice")


@pytest.fixture(scope="module")
def blobs():
    return synth_blobs(n=160, d=8, n_class=3)


def _engine(X, y, max_batch=8):
    eng = NonNeuralServeEngine(E.make_fitted("gnb", X, y, n_groups=3),
                               max_batch=max_batch)
    eng.warmup_buckets(X.shape[1])
    return eng


def _serve(sched, X, n=11):
    """n single-query submits, drained to the end: buckets 8 then 4."""
    ids = [sched.submit(X[i]) for i in range(n)]
    sched.flush()
    return ids


# ------------------------------------------------- spans, no profiler

@pytest.fixture
def opened(monkeypatch):
    """Every span the scheduler and engine open, as ``("+", name, ids)``
    on entry and ``("-", name)`` on exit, through a stand-in for
    ``TraceAnnotation``."""
    log = []

    class Recorded:
        def __init__(self, name, **ids):
            self.name = name
            log.append(("+", name, ids))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            log.append(("-", self.name))

    monkeypatch.setattr(scheduler_mod, "TraceAnnotation", Recorded)
    monkeypatch.setattr(engine_mod, "TraceAnnotation", Recorded)
    return log


def test_launch_opens_each_phase_once_in_order(blobs, opened):
    X, y = blobs
    sched = RequestScheduler(_engine(X, y), max_wait=1)
    _serve(sched, X)
    assert sched.stats.launches == 2
    first = opened[:len(opened) // 2]
    assert [ev[:2] for ev in first] == [
        ("+", "scheduler.drain"), ("+", "scheduler.coalesce"),
        ("-", "scheduler.coalesce"),
        ("+", "engine.classify"),
        ("+", "engine.put"), ("-", "engine.put"),
        ("+", "engine.dispatch"), ("-", "engine.dispatch"),
        ("+", "engine.slice"), ("-", "engine.slice"),
        ("-", "engine.classify"),
        ("+", "scheduler.ready"), ("-", "scheduler.ready"),
        ("+", "scheduler.fetch"), ("-", "scheduler.fetch"),
        ("+", "scheduler.scatter"), ("-", "scheduler.scatter"),
        ("-", "scheduler.drain")]
    assert [ev[:2] for ev in opened[len(first):]] == \
        [ev[:2] for ev in first]
    ids = [ev[2] for ev in opened if ev[0] == "+"
           and ev[1] in ("scheduler.drain", "engine.classify")]
    assert ids == [dict(launch=0, bucket=8, rows=8, tier="full"),
                   dict(bucket=8, rows=8),
                   dict(launch=1, bucket=4, rows=3, tier="full"),
                   dict(bucket=4, rows=4)]


def test_ticks_without_a_launch_open_no_span(blobs, opened):
    """An empty tick, a tick inside the coalescing window and a cache
    hit launch nothing and open nothing."""
    X, y = blobs
    sched = RequestScheduler(_engine(X, y), max_wait=3, cache_size=8)
    sched.drain()
    sched.submit(X[0])
    sched.drain()
    assert opened == [] and sched.stats.launches == 0
    sched.flush()
    n = len(opened)
    assert sched.stats.launches == 1 and n > 0
    assert sched.results[sched.submit(X[0])].cache_hit
    sched.drain()
    assert len(opened) == n and sched.stats.launches == 1


# ------------------------------------------------- in a profiler trace

def _record(tmp_path, run):
    """Host events named like a program span, from a CPU profiler trace
    of ``run()``: (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SCHEDULER_SPANS + ENGINE_SPANS:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda ev: ev[1])


def _inside(inner, outer, events):
    """Every ``inner`` span lies within some ``outer`` span."""
    outs = [(s, e) for n, s, e, _ in events if n == outer]
    return all(any(os <= s and e <= oe for os, oe in outs)
               for n, s, e, _ in events if n == inner)


def test_spans_once_per_launch_and_nested(blobs, tmp_path):
    X, y = blobs
    sched = RequestScheduler(_engine(X, y), max_wait=1)
    ev = _record(tmp_path, lambda: _serve(sched, X))
    launches = sched.stats.launches
    assert launches == 2
    for name in SCHEDULER_SPANS + ENGINE_SPANS:
        assert sum(n == name for n, *_ in ev) == launches, name
    drains = [st for n, _, _, st in ev if n == "scheduler.drain"]
    assert [(st["launch"], st["bucket"], st["rows"], st["tier"])
            for st in drains] == [(0, 8, 8, "full"), (1, 4, 3, "full")]
    classifies = [st for n, _, _, st in ev if n == "engine.classify"]
    assert [(st["bucket"], st["rows"]) for st in classifies] == \
        [(8, 8), (4, 4)]
    for inner in ("scheduler.coalesce", "scheduler.ready",
                  "scheduler.fetch", "scheduler.scatter",
                  "engine.classify"):
        assert _inside(inner, "scheduler.drain", ev), inner
    for inner in ("engine.put", "engine.dispatch", "engine.slice"):
        assert _inside(inner, "engine.classify", ev), inner
    # the phases run in the order the drain takes them
    order = [n for n, *_ in ev if n in SCHEDULER_SPANS[1:]
             or n == "engine.classify"][:5]
    assert order == ["scheduler.coalesce", "engine.classify",
                     "scheduler.ready", "scheduler.fetch",
                     "scheduler.scatter"]


def test_grouped_drain_spans(blobs, tmp_path):
    """The multi-tenant drain opens the same phases around one
    (model-group x bucket) launch."""
    X, y = blobs
    store = ModelStore()
    for t in range(2):
        store.register(t, E.make_fitted("gnb", X, y, n_groups=3))
    eng = store.make_engine(max_batch=8, max_group=2)
    ids = store.model_ids
    stacked, _ = store.group(ids)
    eng.warmup_groups(stacked, X.shape[1])
    sched = RequestScheduler(eng, store=store, max_wait=1)

    def run():
        for i in range(6):
            sched.submit(X[i], model_id=ids[i % 2])
        sched.flush()

    ev = _record(tmp_path, run)
    assert sched.stats.launches == 1
    for name in SCHEDULER_SPANS + ENGINE_SPANS:
        assert sum(n == name for n, *_ in ev) == 1, name
    (st,) = [st for n, _, _, st in ev if n == "scheduler.drain"]
    assert (st["launch"], st["tier"]) == (0, "full")
    (st,) = [st for n, _, _, st in ev if n == "engine.classify"]
    assert (st["bucket"], st["rows"], st["group"]) == (4, 4, 2)
    assert _inside("scheduler.ready", "scheduler.drain", ev)
    assert _inside("engine.dispatch", "engine.classify", ev)


# ------------------------------------------------- queue_s under chaos

def test_queue_s_hand_computed_under_virtual_clock(blobs):
    """Virtual time passes only inside launches: 0.25 s a launch, 2 s on
    the straggler tick 1.  Submit stamps read the clock between launches.

    t=0:    submit q0..q9 (scheduler max_batch 4)
    tick 1: launch q0..q3, t0 0    -> queue_s 0;    straggler, t = 2
    tick 2: launch q4..q7, t0 2    -> queue_s 2;    t = 2.25
    t=2.25: submit q10; resubmit q0 -> cache hit, queue_s 0
    tick 3: launch q8, q9, q10, t0 2.25 -> 2.25, 2.25, 0;  t = 2.5
    """
    X, y = blobs
    sched = RequestScheduler(_engine(X, y), max_wait=1, max_batch=4,
                             cache_size=16)
    plan = ChaosPlan(straggler_ticks=(1,), straggler_factor=8.0)
    ChaosInjector(plan, base_batch_time=0.25).attach(sched)
    first = sched.submit(X[:10])
    sched.drain()
    sched.drain()
    late = sched.submit(X[10])
    hit = sched.results[sched.submit(X[0])]
    sched.drain()
    got = [sched.results[i].queue_s for i in first + [late]]
    assert got == [0.0] * 4 + [2.0] * 4 + [2.25, 2.25, 0.0]
    assert [sched.results[i].batch_time for i in (0, 4, 8)] == \
        [2.0, 0.25, 0.25]
    assert hit.cache_hit and hit.queue_s == 0.0
    assert sched.stats.queue_s == got
    s = sched.stats.summary()   # nearest rank of 11: the 6th and 11th
    assert s["queue_p50_s"] == 2.0 and s["queue_p95_s"] == 2.25
    assert sched.stats.batch_time_s == 2.5


def test_shed_queue_s_is_zero(blobs):
    X, y = blobs
    sched = RequestScheduler(_engine(X, y), max_wait=1, max_queue=1)
    ok, shed = sched.submit(X[:2])
    sched.drain()
    assert sched.results[shed].shed and sched.results[shed].queue_s == 0.0
    assert sched.results[ok].queue_s >= 0.0
    assert sched.stats.queue_s == [sched.results[ok].queue_s]


def _replay(X, y):
    sched = RequestScheduler(_engine(X, y), max_wait=2, max_queue=64,
                             shed_expired=True)
    plan = ChaosPlan.preset("mixed", seed=0, ticks=24)
    ids = replay_trace(sched, X[:40], poisson_trace(4.0, 24, seed=1),
                       deadline=4, chaos=ChaosInjector(plan))
    return sched, ids


def _key(r):
    return (r.request_id, r.shed, r.reason,
            None if r.prediction is None else int(r.prediction),
            None if r.aux is None else np.asarray(r.aux).tolist(),
            r.tier, r.bucket, r.queue_time, r.deadline_missed,
            r.batch_time, r.queue_s)


def test_chaos_replay_with_queue_s_is_bit_identical(blobs):
    X, y = blobs
    s1, ids1 = _replay(X, y)
    s2, ids2 = _replay(X, y)
    assert ids1 == ids2
    k1 = [_key(s1.results[i]) for i in ids1]
    assert k1 == [_key(s2.results[i]) for i in ids2]
    assert s1.events == s2.events
    assert s1.stats.queue_s == s2.stats.queue_s
    # the wait is real: a straggler tick holds later requests back
    assert any(r.queue_s > 0 for r in s1.results.values())
