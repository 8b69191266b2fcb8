#!/usr/bin/env python3
"""Find a configuration's knee once: the highest Poisson rate it serves
with no growing backlog.

    python bench/sweep.py --config sift1m-flat --seed 11 --seconds 5 \\
        --rates 4000,6000,8000,10000,12000 --repeats 3

One process builds the system as a run does, then offers each rate for
``--seconds`` through the same client.  A rate is sustained when, at the
window's close, at most two full buckets of due requests are unanswered
(one launch in flight, one queued behind it) and the median latency of
the window's last third is at most twice that of its first third.  The
knee is the highest sustained rate below the lowest unsustained one.
``--repeats`` runs the whole list again on other arrival seeds, in the
same process.  Each rate prints one JSON line; the last line of each
repeat names its knee.  The fixed rate of a ``knee_share`` traffic mix is that
share of the ``knee_qps`` written into the configuration.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def sustained(run, max_batch: int) -> dict:
    due_in = run.due < run.seconds
    late = int(np.sum(due_in & (run.recv > run.seconds)))
    lat = run.recv - run.due
    thirds = np.array_split(np.arange(len(run.due)), 3)
    first = float(np.median(lat[thirds[0]]))
    last = float(np.median(lat[thirds[-1]]))
    return {"backlog_at_close": late, "p50_first_third_ms": first * 1e3,
            "p50_last_third_ms": last * 1e3,
            "sustained": bool(late <= 2 * max_batch and last <= 2 * first)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    ap.add_argument("--repeats", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import spec
    from bench.system import build
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("bench/sweep.py: JAX finds no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = spec.benchmark(ROOT)
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    config = spec.load_json(ROOT / entry["file"])
    traffic = spec.load_json(ROOT / "bench" / "traffic" / "poisson.json")
    k = int(traffic["k"])
    config = dict(config, k=k)
    gen = spec.module("data", config["data"]["generator"])
    t0 = time.perf_counter()
    base, labels, pool = gen.generate(args.seed, config["data"])
    pool_host = np.asarray(pool)
    system = build(config, traffic, base, labels)
    print(json.dumps({"config": args.config,
                      "setup_s": time.perf_counter() - t0}), flush=True)
    for rep in range(args.repeats):
        sweep(system, pool_host, config, args, k,
              seed=args.seed + 1000 * rep)
    return 0


def sweep(system, pool_host, config, args, k: int, seed: int) -> None:
    from bench import arrivals, loop

    knee, stop = None, False
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        sched = arrivals.poisson(seed + i, rate, args.seconds,
                                 len(pool_host))
        run = loop.run_open(system.scheduler, pool_host, sched.due,
                            sched.pool_idx, args.seconds, k, grace=10.0)
        ok = run.answered
        lat = (run.recv - run.due)[ok]
        row = {"rate": rate, "offered": len(run.due),
               "answered_in_window": int(np.sum(ok & (run.recv
                                                      <= run.seconds))),
               "p50_ms": float(np.percentile(lat, 50) * 1e3),
               "p95_ms": float(np.percentile(lat, 95) * 1e3),
               "mean_bucket": float(np.mean(run.bucket[ok])),
               **sustained(run, int(config["max_batch"]))}
        print(json.dumps(row), flush=True)
        if row["sustained"] and not stop:
            knee = rate
        else:
            stop = True
    print(json.dumps({"config": args.config, "arrival_seed": seed,
                      "knee_qps": knee}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
