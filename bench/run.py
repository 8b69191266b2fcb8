#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python bench/run.py --workload sift1m-flat.poisson --seed 7 \\
        --seconds 10 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, ``breakdown`` when
traced, and last ``checks``: each number compared with the reference,
beside its limit.  The same checks are the last lines of standard error.

Without a TPU, with fewer chips than the cell asks for, or without the
system under test (``src/repro``) beside it, the run exits non-zero and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# program settings a run must not inherit from its environment
REFUSED_ENV = ("REPRO_BACKEND", "REPRO_SHARD_STRATEGY", "REPRO_CALIBRATION")


def fail(msg: str, code: int = 2) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    set_env = [v for v in REFUSED_ENV if os.environ.get(v)]
    if set_env:
        return fail(f"{', '.join(set_env)} set: the benchmark runs the "
                    f"program's own selectors, unset them")
    if not (ROOT / "src" / "repro").is_dir():
        return fail("the system under test (src/repro) is not in this "
                    "checkout")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        return fail(str(e), 3)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
