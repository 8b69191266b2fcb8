#!/usr/bin/env python3
"""Faults planted in the program's IVF-PQ path, to read what the
comparison sees when the candidate set is wrong.  The limit on
``missed_pct`` sits between these readings and the program's own.  The
benchmark's runs never plant them.

    python bench/faults.py --workload sift1m-ivfpq.poisson \\
        --fault probe_skips_nearest --seeds 1,2,3 --seconds 3

- ``nprobe_1``: the probe reads one list instead of the configured
  ``nprobe``;
- ``probe_skips_nearest``: the probe drops each query's nearest list and
  reads the next one in its place (an off-by-one in the probe);
- ``adc_lut_scrambled``: the ADC scores come from each query's lookup
  table reversed, so the refine re-ranks the wrong survivors;
- ``none``: the program as configured, for its own readings.

Each seed runs the whole harness (the same window, load and comparison
as a run) and prints one JSON line with the checks and the recall.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class _Dispatch:
    """The program's kernel dispatch with some entries replaced."""

    def __init__(self, base, **replaced):
        self._base = base
        self.__dict__.update(replaced)

    def __getattr__(self, name):
        return getattr(self._base, name)


@contextlib.contextmanager
def _patched_dispatch(**make):
    """Replace ``repro.core.ann.dispatch`` entries while open; ``make``
    maps an entry's name to a function of the original entry."""
    from repro.core import ann

    base = ann.dispatch
    ann.dispatch = _Dispatch(base, **{name: f(getattr(base, name))
                                      for name, f in make.items()})
    try:
        yield
    finally:
        ann.dispatch = base


def _skip_nearest(distance_topk):
    def probe(A, X, p, **kw):
        dist, idx = distance_topk(A, X, p + 1, **kw)
        return dist[:, 1:], idx[:, 1:]
    return probe


def _scramble_lut(adc_topk):
    def scored(qlut, *args, **kw):
        return adc_topk(qlut[:, ::-1], *args, **kw)
    return scored


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` while open.  Yields a ``shrink`` hook for
    ``harness.run`` that applies the fault's configuration change."""
    def same(config, traffic):
        return config, traffic

    if fault == "none":
        yield same
    elif fault == "nprobe_1":
        def one(config, traffic):
            return dict(config, fitted=dict(config["fitted"],
                                            nprobe=1)), traffic
        yield one
    elif fault == "probe_skips_nearest":
        with _patched_dispatch(distance_topk=_skip_nearest):
            yield same
    elif fault == "adc_lut_scrambled":
        with _patched_dispatch(adc_topk=_scramble_lut):
            yield same
    else:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")


FAULTS = ("none", "nprobe_1", "probe_skips_nearest", "adc_lut_scrambled")


def reading(root, workload: str, seed: int, fault: str,
            seconds: float = 3.0, shrink=None, **kw) -> dict:
    """One run of ``workload`` with ``fault`` planted: its checks and
    recall.  ``shrink`` (tests) is applied before the fault's own."""
    from bench import harness

    with planted(fault) as plant:
        def both(config, traffic):
            if shrink is not None:
                config, traffic = shrink(config, traffic)
            return plant(config, traffic)

        r = harness.run(root, workload, seed, seconds, False,
                        t_start=time.perf_counter(), shrink=both, **kw)
    recall = r["metrics"].get("recall_at_10", {}).get("value")
    return {"correct": r["correct"], "checks": r["checks"],
            "recall_at_10": recall}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=FAULTS)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = reading(ROOT, args.workload, seed, args.fault,
                      seconds=args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "fault": args.fault, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
