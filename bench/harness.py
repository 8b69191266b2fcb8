"""One run of one cell: set up, measure a window, check the answers.

1. Set-up (``setup_s``, process start to the first due request): data
   from the seed on the device, fit, warm-up of every bucket the traffic
   uses.
2. Window: the client drives the scheduler for ``--seconds``; with
   ``--trace 1`` under the profiler.
3. After the window: peak device memory, counters read from the fitted
   index, then the program's state is freed and the exact reference runs
   over the query pool.  Every answer is compared with it.

The result is one dict; ``run.py`` prints it.
"""
from __future__ import annotations

import gc
import sys
import time
from types import SimpleNamespace

import numpy as np

from bench import arrivals, correct, loop, spec
from bench import trace as tracing
from bench.readings import percentile_ms
from bench.reference import knn as ref
from bench.system import build


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def _compile_counter():
    """Counts programs compiled or loaded from the cache while ``on``."""
    import jax

    state = {"on": False, "n": 0}

    def listener(event, duration, **kw):
        if state["on"] and event in (
                "/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_retrieval_time_sec"):
            state["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return state


def _gc_pauses():
    """Collector pauses (generation, seconds) while ``on``."""
    state = {"on": False, "t0": 0.0, "pauses": []}

    def callback(phase, info):
        if not state["on"]:
            return
        if phase == "start":
            state["t0"] = time.perf_counter()
        else:
            state["pauses"].append((info["generation"],
                                    time.perf_counter() - state["t0"]))

    gc.callbacks.append(callback)
    return state


def _host_notes(run_, seconds: float, pauses, classify_s) -> list:
    """Where the window's tail came from: p95 latency per fifth of the
    window, the collector's pauses, and the longest drain split into its
    launch (``RequestResult.batch_time``: classify until the answers are
    ready on the device) and the host work around it."""
    fifth = np.minimum((run_.due * 5 // seconds).astype(int), 4)
    lat = run_.recv - run_.due
    p95 = [percentile_ms(lat[fifth == i], 95) for i in range(5)]
    full = [t for g, t in pauses["pauses"] if g == 2]
    notes = [f"latency p95 ms by fifth of the window (due time): {p95}",
             f"host: {len(pauses['pauses'])} collector pauses, "
             f"{sum(t for _, t in pauses['pauses']):.3f} s, {len(full)} "
             f"full, longest {max(full, default=0):.3f} s; longest "
             f"classify call {max(classify_s, default=0):.3f} s"]
    if run_.drains:
        d = max(run_.drains, key=lambda d: d.end - d.start)
        launch = float(np.nanmax(run_.batch_time[d.requests]))
        notes.append(f"longest drain {d.end - d.start:.3f} s at "
                     f"{d.start:.3f} s into the window: launch {launch:.3f} "
                     f"s, host {d.end - d.start - launch:.3f} s")
    return notes


def run(root, workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, peaks_kind=None,
        shrink=None, tamper=None) -> dict:
    """``shrink`` (config, traffic) -> (config, traffic) and ``tamper``
    (system) are for tests: a small size on the CPU, a broken timed
    path."""
    bench = spec.benchmark(root)
    cell = spec.cell(bench, workload)
    config, traffic = spec.config(bench, cell, root), spec.traffic(cell, root)
    if shrink is not None:
        config, traffic = shrink(config, traffic)
    k = int(traffic["k"])
    config = dict(config, k=k)
    wanted = spec.metrics(bench, workload, trace)

    import jax

    devices = jax.devices()
    chips = int(cell["chips"])
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r}); "
                     f"this benchmark measures the chip and has no CPU mode")
    if len(devices) < chips:
        raise NoChip(f"{workload} needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    peaks = spec.peaks(peaks_kind or devices[0].device_kind, root)
    notes = []
    if require_chip:
        from repro.launch.compile_cache import enable_compile_cache

        notes.append(f"compile cache {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    gen = spec.module("data", config["data"]["generator"], root)
    base, labels, pool = gen.generate(seed, config["data"])
    pool_host = np.asarray(pool)
    recorder = tracing.Recorder(chips) if trace else None
    span = recorder.span if trace else loop.no_span
    system = build(config, traffic, base, labels,
                   span=span if trace else None)
    if tamper is not None:
        tamper(system)
    counter, pauses = _compile_counter(), _gc_pauses()

    # ---- window
    setup_s = time.perf_counter() - t_start
    if recorder:
        recorder.start()
    counter["on"] = pauses["on"] = True
    if traffic["loop"] == "open":
        sched = arrivals.open_schedule(traffic, config, seed, seconds)
        run_ = loop.run_open(system.scheduler, pool_host, sched.due,
                             sched.pool_idx, seconds, k, span=span)
    else:
        stream = arrivals.query_stream(seed, 1 << 20, len(pool_host))
        run_ = loop.run_closed(system.scheduler, pool_host, stream,
                               int(traffic["outstanding"]), seconds, k,
                               span=span)
    counter["on"] = pauses["on"] = False
    if recorder:
        t_trace = time.perf_counter()
        trace_ = tracing.Reduced(recorder.stop())
        notes.append(f"trace stopped and read in "
                     f"{time.perf_counter() - t_trace:.3f} s")
    else:
        trace_ = None

    # ---- after the window
    stats = devices[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    work = spec.module("work", config["estimator"], root)
    counters = work.counters(system.estimator, pool_host, config)
    outside = sorted(set(system.engine.bucket_launches) - system.warmed)
    classify_s = list(system.classify_s)
    notes.extend(_host_notes(run_, seconds, pauses, classify_s))
    notes.append(f"programs compiled or loaded in the window: "
                 f"{counter['n']}; launches outside the warmed buckets: "
                 f"{outside}; launches {dict(system.engine.bucket_launches)}")
    del system
    gc.collect()

    t_ref = time.perf_counter()
    A_host = np.asarray(base)
    gt = ref.GroundTruth(base, pool, A_host, pool_host, k)
    checks, ok, recall, detail = correct.compare(config, gt, A_host,
                                                 pool_host, run_)
    notes.append(detail)
    notes.append(f"reference and comparison "
                 f"{time.perf_counter() - t_ref:.3f} s over "
                 f"{len(pool_host)} pool queries")

    rec = SimpleNamespace(config=config, traffic=traffic, cell=cell,
                          seconds=seconds, setup_s=setup_s, run=run_,
                          classify_s=classify_s, recall=recall,
                          trace=trace_, peaks=peaks, chips=chips,
                          counters=counters, note=notes.append)
    metrics = {}
    for m in wanted:
        value = spec.module("metrics", m["name"], root).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    result = {"correct": bool(ok), "attempted": int(len(run_.due)),
              "failed": int(checks["unanswered"]["value"]),
              "metrics": metrics, "device": device}
    if trace_ is not None:
        device["busy_s"] = trace_.busy_s
        device["window_s"] = trace_.window_s
        result["breakdown"] = {"device_ops": trace_.device_ops(),
                               "idle_gaps": trace_.idle_gaps()}
    result["checks"] = checks
    for n in notes:
        print(f"[bench] {n}", file=sys.stderr, flush=True)
    return result
