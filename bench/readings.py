"""Shared arithmetic of the metric readers in ``bench/metrics/``.

A reader takes the run's record and returns a number, or None when the
run holds nothing to read: a traced metric in an untraced run, a kernel
that did not run.  A share of a roofline or a peak is never reported as
0 for lack of data.
"""
from __future__ import annotations

import numpy as np

from bench import spec


def percentile_ms(values, q: float):
    values = np.asarray(values, np.float64)
    if values.size == 0:
        return None
    return float(np.percentile(values, q) * 1e3)


def roofline_pct(rec, op: str):
    """Least time the chip could take for this op's launches (the larger
    of operations over peak and bytes over HBM bandwidth), over the op's
    device time in the trace, in percent."""
    if rec.trace is None:
        return None
    w = spec.module("work", op)
    seconds = rec.trace.op_seconds(w.TRACE)
    if seconds <= 0:
        return None
    est = spec.module("work", rec.config["estimator"])
    t_ops = t_bytes = t_min = 0.0
    for bucket, req in rec.run.launches():
        shapes = est.launch_ops(bucket, rec.run.pool_idx[req], rec.config,
                                rec.counters)
        if op not in shapes:
            continue
        ops, nbytes = w.work(shapes[op])
        a = ops / rec.peaks[w.PEAK]
        b = nbytes / rec.peaks["hbm_bytes_per_s"]
        t_ops, t_bytes, t_min = t_ops + a, t_bytes + b, t_min + max(a, b)
    if t_min == 0.0:
        return None
    rec.note(f"{op}: device {seconds:.6f} s, bound {t_min:.6f} s "
             f"({'bytes' if t_bytes >= t_ops else 'operations'} bind: "
             f"{t_bytes:.6f} s of bytes, {t_ops:.6f} s of operations)")
    return 100.0 * t_min / seconds


def step_mfu_pct(rec):
    """FLOPs the answered queries need, over the summed wall time of the
    drains that answered them, over the chips' bf16 peak, in percent."""
    run = rec.run
    est = spec.module("work", rec.config["estimator"])
    ok = run.answered
    flops = float(np.sum(est.query_flops(run.pool_idx[ok], rec.config,
                                         rec.counters)))
    busy = sum(d.end - d.start for d in run.drains)
    if busy <= 0 or flops <= 0:
        return None
    return 100.0 * flops / busy / (rec.chips * rec.peaks["bf16_flops_per_s"])


def idle_pct(rec):
    if rec.trace is None or not rec.trace.trace.device \
            or rec.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)


def engine_host_ms(rec):
    if not rec.classify_s:
        return None
    return float(np.mean(rec.classify_s) * 1e3)
