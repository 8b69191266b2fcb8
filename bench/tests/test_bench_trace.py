"""The trace reduction: busy union, idle share, per-kernel time and the
idle gaps put down to host spans, on a hand-made trace and on a trace
recorded on a TPU v5e chip (``data/trickle_trace.json``, from a traced
``sift1m-flat.trickle`` window)."""
import re
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tracing

DATA = Path(__file__).resolve().parent / "data"


def test_union_gaps_overlap_by_hand():
    u = tracing.union([(1, 3), (2, 4), (6, 7), (0.5, 0.6), (8, 12)], 0, 10)
    assert u == [(0.5, 0.6), (1, 4), (6, 7), (8, 10)]
    assert tracing.gaps(u, 0, 10) == [(0, 0.5), (0.6, 1), (4, 6), (7, 8)]
    assert tracing.overlap([(0, 2), (3, 5)], [(1, 4)]) == 2


def hand_trace():
    # chip 0 busy [1,2] and [2.5,3] (overlapping ops merge); window [0,4]
    dev = [("_fused_kernel", 1.0, 1.8), ("copy.1", 1.5, 2.0),
           ("_fused_kernel", 2.5, 3.0), ("fusion.3", 4.5, 5.0)]
    host = [("drain", 0.8, 3.2), ("classify", 0.9, 1.1), ("submit", 0.1, 0.3),
            ("wait", 3.3, 3.9)]
    return tracing.Trace([dev], host, (0.0, 4.0))


def test_reduction_by_hand():
    r = tracing.Reduced(hand_trace())
    assert r.window_s == 4.0
    assert r.busy_s == pytest.approx(1.5)
    assert r.op_seconds(r"_fused_kernel") == pytest.approx(1.3)
    assert r.device_ops()[0] == ["_fused_kernel", pytest.approx(1.3)]
    gaps = dict((n, t) for n, t in r.idle_gaps())
    # idle: [0,1] [2,2.5] [3,4]; drain covers [0.8,1]+[2,2.5]+[3,3.2],
    # classify [0.9,1] is inside drain and wins; submit [0.1,0.3];
    # wait [3.3,3.9]; the rest is "none"
    assert gaps["classify"] == pytest.approx(0.1)
    assert gaps["drain"] == pytest.approx(0.1 + 0.5 + 0.2)
    assert gaps["submit"] == pytest.approx(0.2)
    assert gaps["wait"] == pytest.approx(0.6)
    assert gaps["none"] == pytest.approx(2.5 - 0.1 - 0.8 - 0.2 - 0.6)
    assert sum(gaps.values()) == pytest.approx(4.0 - 1.5)


def test_json_round_trip():
    t = hand_trace()
    assert tracing.Trace.from_json(t.to_json()) == t


@pytest.fixture(scope="module")
def chip_trace():
    return tracing.Trace.from_json((DATA / "trickle_trace.json").read_text())


def test_chip_trace_busy_matches_a_brute_force_count(chip_trace):
    r = tracing.Reduced(chip_trace)
    lo, hi = chip_trace.window
    # busy time on a 1 us grid, counted independently of the reduction
    grid = np.zeros(int(np.ceil((hi - lo) * 1e6)) + 1, bool)
    for _, s, e in chip_trace.device[0]:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int(round((a - lo) * 1e6)):int(round((b - lo) * 1e6))] = 1
    assert r.busy_s == pytest.approx(grid.sum() * 1e-6, abs=2e-6 *
                                     len(chip_trace.device[0]) + 1e-6)
    assert 0.0 < r.busy_s < r.window_s
    idle = sum(t for _, t in r.idle_gaps())
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-9)


def test_chip_trace_kernel_time(chip_trace):
    from bench import spec

    r = tracing.Reduced(chip_trace)
    pattern = spec.module("work", "distance_topk").TRACE
    t = r.op_seconds(pattern)
    want = sum(e - s for n, s, e in chip_trace.device[0]
               if re.search(pattern, n))
    assert t == pytest.approx(want) and t > 0
    names = [n for n, _ in r.device_ops()]
    assert re.search(pattern, names[0])      # the kernel leads the device


def test_xplane_reduces_to_the_kept_trace(chip_trace):
    """The kept JSON is what ``from_xplane`` reads from the recorded
    ``.xplane.pb``: TPU ops by HLO name, the benchmark's host spans."""
    t = tracing.from_xplane(str(DATA / "trickle.xplane.pb"), chips=1)
    assert t == chip_trace
    names = {n for n, _, _ in t.host}
    assert names <= set(tracing.HOST_SPANS) and "classify" in names
    assert all(" = " not in n for n, _, _ in t.device[0])
