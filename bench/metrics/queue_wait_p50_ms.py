"""Median of latency minus the launch time the scheduler reports
(RequestResult.batch_time): time spent waiting, not computing."""
from bench.readings import percentile_ms


def read(rec):
    run = rec.run
    ok = run.answered
    return percentile_ms((run.recv - run.due - run.batch_time)[ok], 50)
