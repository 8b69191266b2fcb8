"""Serving-load sweep: arrival rate x algorithm x bucket policy through
the micro-batching ``RequestScheduler`` (serving/scheduler.py).

Replays a seeded Poisson-ish arrival trace per cell and records the SLO
accounting — tail latency in drain ticks (deterministic for a seed),
throughput, bucket occupancy (the paper-§5.3 core-utilization analogue:
a half-empty bucket wastes silicon the way a stalled PULP core does),
cache hit-rate, and deadline-miss rate.  The bucket-policy axis is
``max_wait``: a short coalescing window trades occupancy (smaller,
emptier buckets) for tail latency, exactly the latency/energy knob the
paper's near-sensor framing cares about.

Results accumulate in BENCH_serving.json via benchmarks/report.py
(schema-checked on load and append like the other BENCH files).

  PYTHONPATH=src python -m benchmarks.serving_load [--quick]
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

ALGORITHMS = ("knn", "kmeans", "gnb", "gmm", "rf")
ALGORITHMS_QUICK = ("knn", "gnb")
RATES = (1.0, 4.0, 16.0)
RATES_QUICK = (2.0, 8.0)
MAX_WAITS = (1, 4)            # bucket policy: latency- vs occupancy-leaning
MAX_WAITS_QUICK = (2,)
TICKS, TICKS_QUICK = 96, 32
DEADLINE_FACTOR = 2           # SLO = 2x the coalescing window


def run(csv_rows: list, quick: bool = False):
    from repro.core.estimator import make_fitted
    from repro.data.datasets import class_blobs
    from repro.serving import (NonNeuralServeEngine, RequestScheduler,
                               poisson_trace, replay_trace)

    algos = ALGORITHMS_QUICK if quick else ALGORITHMS
    rates = RATES_QUICK if quick else RATES
    waits = MAX_WAITS_QUICK if quick else MAX_WAITS
    ticks = TICKS_QUICK if quick else TICKS
    n, d = (160, 12) if quick else (320, 21)
    max_batch = 32

    X, y = class_blobs(n=n, d=d)
    # repeated-query traffic: cycle a pool smaller than the LRU so the
    # cache axis actually shows up in hit_rate
    Q = X[:48]
    results = []
    print("\n== Serving load sweep (rate x algorithm x bucket policy) ==")
    print(f"{'algo':7s} {'rate':>5s} {'wait':>4s} {'p50':>4s} {'p95':>4s} "
          f"{'p99':>4s} {'req/tick':>8s} {'occ':>5s} {'hit':>5s} "
          f"{'miss':>5s}")
    for algo in algos:
        est = make_fitted(algo, X, y, n_groups=int(y.max()) + 1)
        # one engine per algorithm: buckets compile once, every
        # (rate, max_wait) cell reuses them (a fresh scheduler per cell
        # resets the stats; bucket_launches accumulates across cells)
        engine = NonNeuralServeEngine(est, max_batch=max_batch)
        engine.warmup_buckets(d)
        for max_wait in waits:
            for rate in rates:
                sched = RequestScheduler(engine, max_wait=max_wait,
                                         cache_size=64)
                counts = poisson_trace(rate, ticks, seed=0)
                replay_trace(sched, Q, counts,
                             deadline=DEADLINE_FACTOR * max_wait)
                assert set(engine.bucket_launches) <= sched.warmed, \
                    (algo, rate, max_wait)   # no mid-stream compiles
                s = sched.stats.summary()
                rec = {"algorithm": algo, "rate": rate,
                       "max_wait": max_wait, "ticks": ticks,
                       "completed": s["completed"],
                       "p50": s["p50"], "p95": s["p95"], "p99": s["p99"],
                       "throughput": s["throughput"],
                       "occupancy": s["occupancy"],
                       "hit_rate": s["hit_rate"],
                       "deadline_miss_rate": s["deadline_miss_rate"]}
                results.append(rec)
                print(f"{algo:7s} {rate:5.1f} {max_wait:4d} {s['p50']:4.0f} "
                      f"{s['p95']:4.0f} {s['p99']:4.0f} "
                      f"{s['throughput']:8.2f} {s['occupancy']:5.2f} "
                      f"{s['hit_rate']:5.2f} "
                      f"{s['deadline_miss_rate']:5.2f}")
                mean_batch_us = 1e6 * sched.stats.batch_time_s \
                    / sched.stats.launches if sched.stats.launches else 0.0
                csv_rows.append(
                    (f"serving_load/{algo}/r{rate:g}/w{max_wait}",
                     mean_batch_us,
                     f"p95_ticks={s['p95']:.0f};occ={s['occupancy']:.2f};"
                     f"hit={s['hit_rate']:.2f}"))
    return results


if __name__ == "__main__":
    import argparse

    from benchmarks import report

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    report.write_serving_entry(run([], quick=args.quick))
    print("\n### Serving load\n")
    print(report.serving_table())
