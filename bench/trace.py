"""From a profiler trace to the device numbers: busy and idle time, time
per kernel, the top device ops and what the host did in the idle gaps.

``Recorder`` runs the profiler around the measured window (Python tracer,
the runtime's own host events and HLO protos off) and ``from_xplane``
keeps only what the reduction reads: the device ops of the chips in use,
and the benchmark's own host spans (``HOST_SPANS``), all on the
profiler's one clock.  ``Trace`` is
plain data, so a trace recorded on the chip can be kept as JSON and the
reduction checked on it without one.
"""
from __future__ import annotations

import glob
import json
import re
import shutil
import tempfile
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

# the benchmark's host spans, innermost first: an idle gap is put down to
# the innermost span the host was in
HOST_SPANS = ("classify", "submit", "result", "drain", "wait")
WINDOW = "window"
TOP = 10

Interval = Tuple[float, float]


@dataclass
class Trace:
    """Device ops per chip and host spans, as (name, start_s, end_s)."""
    device: List[List[Tuple[str, float, float]]]
    host: List[Tuple[str, float, float]]
    window: Interval

    def to_json(self) -> str:
        return json.dumps({"device": self.device, "host": self.host,
                           "window": list(self.window)})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls([[tuple(e) for e in dev] for dev in d["device"]],
                   [tuple(e) for e in d["host"]], tuple(d["window"]))


def union(intervals, lo: float, hi: float) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


class Reduced:
    """What the metric readers take from a trace."""

    def __init__(self, trace: Trace):
        self.trace = trace
        lo, hi = trace.window
        self.window_s = hi - lo
        busy = [union([(s, e) for _, s, e in dev], lo, hi)
                for dev in trace.device]
        self.busy = busy
        self.busy_s = float(np.mean([sum(e - s for s, e in b)
                                     for b in busy])) if busy else 0.0

    def op_seconds(self, pattern: str) -> float:
        """Device time of the ops whose name matches ``pattern``, summed
        over the whole trace and averaged over the chips."""
        rx = re.compile(pattern)
        if not self.trace.device:
            return 0.0
        return float(np.mean([sum(e - s for n, s, e in dev if rx.search(n))
                              for dev in self.trace.device]))

    def device_ops(self, top: int = TOP):
        """[name, seconds] of the ops that took most device time, chip 0."""
        tot: Dict[str, float] = {}
        for n, s, e in (self.trace.device[0] if self.trace.device else []):
            tot[n] = tot.get(n, 0.0) + (e - s)
        return [[n, t] for n, t in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = TOP):
        """[host span, seconds]: idle device time on chip 0 within the
        window, put down to the innermost host span open at the time;
        ``none`` where the host was in no span."""
        lo, hi = self.trace.window
        if not self.busy:
            return []
        idle = gaps(self.busy[0], lo, hi)
        left = idle
        out = []
        for name in HOST_SPANS:
            spans = union([(s, e) for n, s, e in self.trace.host
                           if n == name], lo, hi)
            t = overlap(left, spans)
            if t > 0:
                out.append([name, t])
            left = _subtract(left, spans)
        rest = sum(e - s for s, e in left)
        if rest > 0:
            out.append(["none", rest])
        return sorted(out, key=lambda kv: -kv[1])[:top]


def _subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    out = []
    for s, e in a:
        t = s
        for bs, be in b:
            if be <= t or bs >= e:
                continue
            if bs > t:
                out.append((t, bs))
            t = max(t, be)
        if t < e:
            out.append((t, e))
    return out


def op_name(event_name: str) -> str:
    """``%distance_topk.1`` from ``%distance_topk.1 = (f32[8,10]...) ...``:
    the HLO instruction's name, without its text."""
    return event_name.split(" = ", 1)[0]


def from_xplane(path: str, chips: int) -> Trace:
    """Device ops of the first ``chips`` TPU planes (their ``XLA Ops``
    line: asynchronous copies, on a line of their own, overlap the ops and
    are not counted) and the benchmark's host spans, from an
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host, window = {}, [], None
    wanted = set(HOST_SPANS) | {WINDOW}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device[int(m.group(1))] = [
                        (op_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        ev = (e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                        if e.name == WINDOW:
                            window = ev[1:]
                        else:
                            host.append(ev)
    if window is None:
        raise ValueError("the trace holds no 'window' span")
    return Trace([device[i] for i in sorted(device)], host, window)


class Recorder:
    """Profiler around the window; ``span`` marks host spans in it."""

    def __init__(self, chips: int):
        self.chips = chips
        self.dir = None

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # user annotations, not the runtime's
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> Trace:
        import jax

        jax.profiler.stop_trace()
        try:
            files = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            return from_xplane(files[0], self.chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
