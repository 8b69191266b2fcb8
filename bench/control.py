#!/usr/bin/env python3
"""Readings of a cell's control: what the comparison reads when the timed
path computes one precision step below what the configuration states.
The limits in each configuration's ``checks`` sit between these readings
and those of the program's own runs.  The benchmark's runs never run it.

    python bench/control.py --workload sift1m-flat.poisson --seeds 1,2,3

- ``reference_high`` (exact search, whose distance matmul runs at
  ``highest``): the plain reference put in the program's place with its
  matmul in three bfloat16 passes (``high``), and no exact re-rank.
- ``program_bf16`` (the refine computes plain fp32 differences): the
  program's own bfloat16 path switched on, served through the whole
  timed path for a short window at the cell's load.

Each seed prints one JSON line with the numbers compared and limits.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def reference_high(root, workload: str, seed: int, **kw):
    """Checks of the reference at ``high`` over every pool query."""
    import numpy as np

    from bench import correct, loop, spec
    from bench.reference import knn as ref

    bench = spec.benchmark(root)
    cell = spec.cell(bench, workload)
    config, traffic = spec.config(bench, cell, root), spec.traffic(cell, root)
    if kw.get("shrink"):
        config, traffic = kw["shrink"](config, traffic)
    k = int(traffic["k"])
    gen = spec.module("data", config["data"]["generator"], root)
    base, _, pool = gen.generate(seed, config["data"])
    A_host, Q_host = np.asarray(base), np.asarray(pool)
    gt = ref.GroundTruth(base, pool, A_host, Q_host, k)
    ids = ref.expansion_topk(base, pool, k, ref.cross_bf16x3)
    P = len(Q_host)
    run = loop.Run(seconds=0.0, due=np.zeros(P), pool_idx=np.arange(P),
                   submit=np.zeros(P), recv=np.zeros(P),
                   batch_time=np.zeros(P), bucket=np.zeros(P, int),
                   answers=ids, shed=np.zeros(P, bool))
    checks, ok, _, detail = correct.compare(config, gt, A_host, Q_host, run)
    return {"correct": ok, "checks": checks, "detail": detail}


def program_bf16(root, workload: str, seed: int, seconds: float = 3.0,
                 **kw):
    """Checks of a short window served with the program's bf16 policy."""
    import jax.numpy as jnp

    from bench import harness
    from repro.kernels.dispatch import PrecisionPolicy

    shrink0 = kw.get("shrink")

    def shrink(config, traffic):
        if shrink0:
            config, traffic = shrink0(config, traffic)
        fitted = dict(config["fitted"],
                      policy=PrecisionPolicy("bf16", jnp.bfloat16))
        return dict(config, fitted=fitted), traffic

    r = harness.run(root, workload, seed, seconds, False,
                    t_start=time.perf_counter(), shrink=shrink,
                    **{k: v for k, v in kw.items() if k != "shrink"})
    return {"correct": r["correct"], "checks": r["checks"]}


CONTROLS = {"reference_high": reference_high, "program_bf16": program_bf16}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import spec
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: JAX finds no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    bench = spec.benchmark(ROOT)
    config = spec.config(bench, spec.cell(bench, args.workload))
    control = CONTROLS[config["control"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control(ROOT, args.workload, seed, seconds=args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": config["control"], **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
