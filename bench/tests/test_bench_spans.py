"""The program's own spans on a trace recorded on a TPU v5e chip
(``data/trickle_spans_trace.json``: 0.5 s of a traced
``sift1m-flat.trickle`` window, program spans kept), and the readings
``bench/tools/spans_run.py`` takes from them.  The device idle that the
benchmark's spans can only file under ``drain`` splits into the
scheduler's phases: most of it is ``scheduler.ready`` (the device has
finished, the host still waits) and ``scheduler.fetch`` (the two
device-to-host copies)."""
from pathlib import Path

import pytest

from bench import trace as tracing
from bench.tools import spans_run

DATA = Path(__file__).resolve().parent / "data"
PHASES = ("scheduler.coalesce", "engine.classify", "scheduler.ready",
          "scheduler.fetch", "scheduler.scatter")


@pytest.fixture(scope="module")
def spans_trace():
    return tracing.Trace.from_json(
        (DATA / "trickle_spans_trace.json").read_text())


def _spans(trace, name):
    return sorted((s, e) for n, s, e in trace.host if n == name)


def test_each_launch_opens_every_phase_in_order(spans_trace):
    drains = _spans(spans_trace, "scheduler.drain")
    assert len(drains) > 20
    for name in PHASES + ("engine.put", "engine.dispatch", "engine.slice"):
        assert len(_spans(spans_trace, name)) == len(drains), name
    for k, (ds, de) in enumerate(drains):
        starts = []
        for name in PHASES:
            s, e = _spans(spans_trace, name)[k]
            assert ds <= s <= e <= de, (name, k)
            starts.append(s)
        assert starts == sorted(starts), k
    # the program's drain lies inside the benchmark's own drain span
    outer = _spans(spans_trace, "drain")
    assert all(any(os <= s and e <= oe for os, oe in outer)
               for s, e in drains)


def test_former_drain_idle_is_ready_and_fetch(spans_trace):
    """Idle inside the benchmark's ``drain`` and outside its
    ``classify``: at least nine tenths of it lies in ``scheduler.ready``
    or ``scheduler.fetch``, and the rest in no other single phase."""
    lo, hi = spans_trace.window
    red = tracing.Reduced(spans_trace)
    idle = tracing.gaps(red.busy[0], lo, hi)

    def idle_in(*names):
        spans = [se for n in names for se in _spans(spans_trace, n)]
        return tracing.overlap(idle, tracing.union(spans, lo, hi))

    former = idle_in("drain") - idle_in("classify")
    ready, fetch = idle_in("scheduler.ready"), idle_in("scheduler.fetch")
    assert former > 0.1 * red.window_s
    assert ready + fetch > 0.9 * former
    assert ready > fetch > idle_in("scheduler.scatter")
    assert idle_in("scheduler.coalesce") < 0.01 * former


def test_ready_idle_comes_after_the_device_work(spans_trace):
    """Inside ``scheduler.ready`` the device runs first and then sits
    idle until the host leaves ``block_until_ready``: no ready span
    starts on an idle device."""
    lo, hi = spans_trace.window
    busy = tracing.Reduced(spans_trace).busy[0]
    for s, e in _spans(spans_trace, "scheduler.ready"):
        if not lo <= s < e <= hi:
            continue
        inside = tracing.union(busy, s, e)
        assert inside and inside[0][0] == s
        assert e - inside[-1][1] > 0


def test_tool_files_idle_under_the_program_phase():
    """One hand-made launch: the device runs 0.2-1.0 s; the idle inside
    the benchmark's ``drain`` and outside its ``classify`` (1.0-3.0 s)
    goes to the innermost program span."""
    trace = tracing.Trace(
        [[("%k", 0.2, 1.0)]],
        [("drain", 0.1, 3.0), ("classify", 0.1, 0.3),
         ("scheduler.drain", 0.1, 2.9), ("engine.classify", 0.12, 0.28),
         ("scheduler.ready", 0.3, 1.6), ("scheduler.fetch", 1.6, 2.4),
         ("scheduler.scatter", 2.4, 2.8)],
        (0.0, 10.0))
    r = spans_run.readings(trace, drains=[[0.1, 2.9, 0, 64, 60]])
    assert r["drain_idle_s"] == pytest.approx(2.0)
    assert r["drain_idle_split_s"] == pytest.approx({
        "scheduler.ready": 0.6, "scheduler.fetch": 0.8,
        "scheduler.scatter": 0.4, "scheduler.drain": 0.1, "none": 0.1})
    assert r["fetch_ms"] == pytest.approx(800.0)
    assert r["ready_idle_ms"] == pytest.approx(600.0)
    assert r["ready_idle_after_last_op_ms"] == pytest.approx(600.0)
    (longest,) = r["longest_ready"]
    assert longest["launch_bucket_rows"] == [0, 64, 60]
    assert longest["top_op"] == "%k"
    assert (longest["start_s"], longest["len_s"],
            longest["device_busy_s"]) == pytest.approx((0.3, 1.3, 0.7))


def test_tool_reads_the_recorded_trace(spans_trace):
    r = spans_run.readings(spans_trace)
    split = r["drain_idle_split_s"]
    assert split["scheduler.ready"] + split["scheduler.fetch"] > \
        0.9 * r["drain_idle_s"]
    assert sum(split.values()) == pytest.approx(r["drain_idle_s"])
    assert 0.5 < r["fetch_ms"] < 1.5
    # the idle inside ``ready`` all comes after the launch's device work
    assert r["ready_idle_after_last_op_ms"] == \
        pytest.approx(r["ready_idle_ms"], rel=1e-3)
    assert len(r["longest_ready"]) == 3
