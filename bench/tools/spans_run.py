#!/usr/bin/env python3
"""One traced benchmark run that keeps the program's own spans, and what
they read.

The harness keeps only the benchmark's host spans (``bench/trace.py``
``HOST_SPANS``) and none of the scheduler's counters, so the scheduler's
and engine's spans (``scheduler.*``, ``engine.*``) and
``RequestResult.queue_s`` have no reader among the cell's metrics yet.
This tool runs one cell through the same harness with those spans added
to the ones it keeps (innermost first, so the idle-gap breakdown names
the program's phase) and reduces the window to:

* ``fetch_ms``: mean ``scheduler.fetch`` per launch;
* ``ready_idle_ms``: chip 0 idle inside ``scheduler.ready`` per launch,
  and how much of it comes after the launch's last device op;
* the split by phase of the idle inside the benchmark's ``drain`` and
  outside its ``classify`` (the breakdown's ``drain`` entry without the
  program's spans);
* the three longest ``scheduler.ready`` spans: start, length, launch,
  bucket and rows, device busy time inside, top device op;
* ``sched_queue_p50_ms`` / ``_p95_ms`` (``ServingStats.queue_s``) and
  the scheduler's events by kind.

    python3 bench/tools/spans_run.py --workload sift1m-flat.trickle \\
        --seed 7 --seconds 30 --out chiprun_out/spans/trickle
    python3 bench/tools/spans_run.py --read chiprun_out/spans/trickle.json

``--out P`` writes ``P.json``: the harness's result line, the readings,
the counters and the trace itself (``--read`` reduces that file again).
It changes no cell: ``bench/run.py`` runs without it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as tracing  # noqa: E402

PROGRAM_SPANS = ("engine.put", "engine.dispatch", "engine.slice",
                 "engine.classify", "scheduler.coalesce", "scheduler.ready",
                 "scheduler.fetch", "scheduler.scatter", "scheduler.drain")
# the benchmark's ``classify`` wraps ``engine.classify``
KEPT_SPANS = PROGRAM_SPANS[:3] + ("classify",) + PROGRAM_SPANS[3:] + (
    "submit", "result", "drain", "wait")


def _spans(trace, name):
    lo, hi = trace.window
    return sorted((s, e) for n, s, e in trace.host
                  if n == name and lo <= s and e <= hi)


def _clip(busy, s, e):
    """The merged, sorted intervals ``busy`` cut to [s, e]."""
    i = max(bisect.bisect_left(busy, (s, s)) - 1, 0)
    out = []
    while i < len(busy) and busy[i][0] < e:
        a, b = max(busy[i][0], s), min(busy[i][1], e)
        if b > a:
            out.append((a, b))
        i += 1
    return out


def _intersect(a, b):
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _minus(a, b):
    """``a`` without ``b``, both merged and sorted."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def readings(trace, drains=()) -> dict:
    """The program spans' readings of one traced window.  ``drains``:
    ``[start_s, end_s, launch, bucket, rows]`` per ``scheduler.drain``."""
    lo, hi = trace.window
    busy = tracing.union([(s, e) for _, s, e in trace.device[0]], lo, hi) \
        if trace.device else []
    idle = tracing.gaps(busy, lo, hi)

    def covered(*names):
        return tracing.union([se for n in names
                              for se in _spans(trace, n)], lo, hi)

    out = {"window_s": hi - lo,
           "busy_s": sum(e - s for s, e in busy)}
    n = {name: len(_spans(trace, name)) for name in PROGRAM_SPANS}
    out["spans"] = {name: [n[name], 1e3 * sum(
        e - s for s, e in _spans(trace, name)) / n[name]]
        for name in PROGRAM_SPANS if n[name]}
    launches = n["scheduler.ready"]
    fetch = out["spans"].get("scheduler.fetch")
    out["fetch_ms"] = fetch[1] if fetch else None

    # idle inside scheduler.ready: the part after the device's last op
    ready_idle = after = 0.0
    for s, e in _spans(trace, "scheduler.ready"):
        inside = _clip(busy, s, e)
        ready_idle += (e - s) - sum(b - a for a, b in inside)
        after += e - (inside[-1][1] if inside else s)
    out["ready_idle_ms"] = 1e3 * ready_idle / launches if launches else None
    out["ready_idle_after_last_op_ms"] = \
        1e3 * after / launches if launches else None

    # the benchmark's ``drain`` idle, outside its ``classify``, by phase
    left = _minus(_intersect(idle, covered("drain")), covered("classify"))
    out["drain_idle_s"] = sum(e - s for s, e in left)
    split = {}
    for name in PROGRAM_SPANS:
        spans = covered(name)
        t = sum(e - s for s, e in _intersect(left, spans))
        if t > 0:
            split[name] = t
        left = _minus(left, spans)
    split["none"] = sum(e - s for s, e in left)
    out["drain_idle_split_s"] = split

    longest = []
    for s, e in sorted(_spans(trace, "scheduler.ready"),
                       key=lambda se: se[0] - se[1])[:3]:
        ops = {}
        for name, a, b in (trace.device[0] if trace.device else []):
            if min(b, e) > max(a, s):
                ops[name] = ops.get(name, 0.0) + min(b, e) - max(a, s)
        ids = next((d[2:] for d in drains if d[0] <= s and e <= d[1]), None)
        longest.append({
            "start_s": s - lo, "len_s": e - s, "launch_bucket_rows": ids,
            "device_busy_s": sum(b - a for a, b in _clip(busy, s, e)),
            "top_op": max(ops, key=ops.get) if ops else None})
    out["longest_ready"] = longest
    return out


def _record(args) -> dict:
    """Run the cell through ``bench/harness.py`` with the program's spans
    kept; return the result, the readings and the scheduler's counters."""
    from jax.profiler import ProfileData

    from bench import harness

    tracing.HOST_SPANS = KEPT_SPANS
    kept = {"drains": []}
    read_xplane, stop, build = (tracing.from_xplane, tracing.Recorder.stop,
                                harness.build)

    def from_xplane(path, chips):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == "scheduler.drain":
                            ids = dict(e.stats)
                            kept["drains"].append(
                                [e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9,
                                 ids.get("launch"), ids.get("bucket"),
                                 ids.get("rows")])
        return read_xplane(path, chips)

    def keep_trace(recorder):
        kept["trace"] = stop(recorder)
        return kept["trace"]

    def keep_system(*a, **kw):
        kept["system"] = build(*a, **kw)
        return kept["system"]

    tracing.from_xplane = from_xplane
    tracing.Recorder.stop = keep_trace
    harness.build = keep_system
    result = harness.run(ROOT, args.workload, args.seed, args.seconds, True,
                         t_start=T_START)
    sched = kept["system"].scheduler
    events = {}
    for ev in sched.events:
        events[ev.kind] = events.get(ev.kind, 0) + 1
    summary = sched.stats.summary()
    return {"result": result, "trace": json.loads(kept["trace"].to_json()),
            "drains": kept["drains"],
            "counters": {
                "sched_queue_p50_ms": 1e3 * summary["queue_p50_s"],
                "sched_queue_p95_ms": 1e3 * summary["queue_p95_s"],
                "served": len(sched.stats.queue_s),
                "launches": sched.stats.launches,
                "batch_time_s": sched.stats.batch_time_s,
                "events": events}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", help="write <out>.json")
    ap.add_argument("--read", help="reduce a file --out wrote, or a Trace "
                    "JSON, again")
    args = ap.parse_args(argv)
    if args.read:
        run = json.loads(Path(args.read).read_text())
        if "trace" not in run:      # a Trace JSON, perhaps with "drains"
            run = {"trace": run, "drains": run.pop("drains", [])}
    else:
        if args.workload is None or args.seed is None:
            ap.error("--workload and --seed are needed to record a run")
        run = _record(args)
    trace = tracing.Trace.from_json(json.dumps(run["trace"]))
    run["readings"] = readings(trace, run.get("drains", ()))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out + ".json").write_text(json.dumps(run))
    shown = {k: v for k, v in run.items() if k not in ("trace", "drains")}
    print(json.dumps(shown, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
