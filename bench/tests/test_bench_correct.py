"""The comparison that decides ``correct``: the reference's own numbers,
the controls that must fail it, and a whole run on the CPU at a small
size, with the timed path broken underneath, that must come out not
correct."""
import dataclasses
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, faults, harness, spec
from bench.reference import knn as ref

FLAT = spec.load_json(spec.ROOT / "bench/configs/sift1m-flat.json")
IVF = spec.load_json(spec.ROOT / "bench/configs/sift1m-ivfpq.json")


def shrink(config, traffic):
    """The same mixture and index shapes, scaled to a CPU test."""
    c = json.loads(json.dumps(config))
    c["data"].update(n_base=4096, pool=64, components=64, super_clusters=8)
    c["max_batch"] = 8
    c["knee_qps"] = 150.0
    if c["estimator"] == "ann":
        c["fitted"].update(n_cells=16, nprobe=4, refine=20)
    return c, dict(traffic)


def run_cell(workload, tamper=None, sh=shrink, seconds=1.0):
    return harness.run(spec.ROOT, workload, 2 ** 33 + 17, seconds, False,
                       t_start=time.perf_counter(), require_chip=False,
                       peaks_kind="TPU v5 lite", shrink=sh, tamper=tamper)


# ------------------------------------------------------------ reference

def near_ties(seed=1, n_query=32, n_near=60, n_far=2000, d=128, step=0.5):
    """Queries each with ``n_near`` rows at distances ``step`` units
    apart: finer than a ``high`` matmul resolves."""
    g = np.random.default_rng(seed)
    Q = g.uniform(0, 255, (n_query, d)).astype(np.float32)
    far = g.uniform(0, 255, (n_far, d)).astype(np.float32)
    r = np.sqrt((255.0 ** 2) * d)
    rows = []
    for q in Q:
        unit = ref.U * (np.linalg.norm(q) + r) ** 2
        v = g.normal(size=(n_near, d))
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        rad = np.sqrt(2000.0 + step * unit * np.arange(n_near))
        rows.append(q + v * rad[:, None])
    A = np.concatenate([far] + rows).astype(np.float32)
    return A, Q


def test_reference_reads_zero_on_itself_and_catches_a_wrong_row():
    A, Q = near_ties()
    gt = ref.GroundTruth(jnp.asarray(A), jnp.asarray(Q), A, Q, 10)
    idx = np.arange(len(Q))
    ids = gt.ids[:, :10]
    assert ref.excess_units(gt, A, Q, idx, ids) == 0.0
    assert ref.beyond(ref.excess_per_answer(gt, A, Q, idx, ids), 0.0) == 0
    assert ref.order_excess_units(gt, A, Q, idx, ids) == 0.0
    assert ref.recall_at_k(gt, idx, ids) == 1.0
    assert not ref.malformed(ids, len(A)).any()
    wrong = ids.copy()
    wrong[3, 0] = 0                                   # a far row
    assert ref.excess_units(gt, A, Q, idx, wrong) > 1e3
    gap = ref.excess_per_answer(gt, A, Q, idx, wrong)
    assert ref.beyond(gap, 2.0) == 1
    assert ref.order_excess_units(gt, A, Q, idx, wrong) > 1e3
    dup = ids.copy()
    dup[5, 1] = dup[5, 0]
    assert ref.malformed(dup, len(A))[5]


def test_flat_control_fails_and_the_program_passes():
    """The reference at ``high`` in the program's place reads over the
    limit; the served program (fp32 at ``highest``) under it."""
    A, Q = near_ties()
    gt = ref.GroundTruth(jnp.asarray(A), jnp.asarray(Q), A, Q, 10)
    idx = np.arange(len(Q))
    limit = FLAT["checks"]["answers_beyond_tol"]
    tol = FLAT["tolerance_units"]
    ctl = ref.expansion_topk(jnp.asarray(A), jnp.asarray(Q), 10,
                             ref.cross_bf16x3)
    assert ref.beyond(ref.excess_per_answer(gt, A, Q, idx, ctl),
                      tol) > limit

    from repro.core.estimator import make_fitted
    from repro.serving import NonNeuralServeEngine

    est = make_fitted("knn", A, np.zeros(len(A), np.int32), n_groups=1,
                      k=10)
    got = np.asarray(NonNeuralServeEngine(est, max_batch=32)
                     .classify(Q).aux)
    assert ref.beyond(ref.excess_per_answer(gt, A, Q, idx, got),
                      tol) <= limit


# ------------------------------------------------------------ whole runs

def _alter(system):
    """An answer altered where it is produced: each first neighbour id
    moved to the next row."""
    classify = system.engine.classify
    n = int(system.estimator.serve_cost_shape().get("N", 4096))

    def bad(X):
        r = classify(X)
        return dataclasses.replace(r, aux=r.aux.at[:, 0].set(
            (r.aux[:, 0] + 1) % n))
    system.engine.classify = bad


def _misroute(system):
    """Answers handed to the wrong rows of the launch."""
    classify = system.engine.classify

    def bad(X):
        r = classify(X)
        return dataclasses.replace(r, aux=r.aux[::-1])
    system.engine.classify = bad


def _drop(system):
    """One request never comes back."""
    sched = system.scheduler
    drain = sched.drain

    def bad(force=False):
        return [r for r in drain(force) if r.request_id != 0]
    sched.drain = bad
    sched.flush = lambda: []


@pytest.fixture(scope="module")
def flat_clean():
    return run_cell("sift1m-flat.poisson")


def test_flat_run_is_correct(flat_clean):
    r = flat_clean
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 120
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"setup_s", "latency_p50_ms", "qps"}


@pytest.mark.parametrize("fault", [_alter, _misroute, _drop],
                         ids=["altered", "misrouted", "dropped"])
def test_flat_run_with_a_broken_path_is_not_correct(fault):
    r = run_cell("sift1m-flat.poisson", tamper=fault, seconds=0.5)
    assert not r["correct"]


def test_ivfpq_run_is_correct_and_its_faults_are_not():
    r = run_cell("sift1m-ivfpq.poisson")
    assert r["correct"], r["checks"]
    assert r["checks"]["missed_pct"]["value"] == pytest.approx(
        100.0 * (1.0 - r["metrics"]["recall_at_10"]["value"]))
    assert 0.0 < r["metrics"]["recall_at_10"]["value"] <= 1.0
    assert not run_cell("sift1m-ivfpq.poisson", tamper=_alter,
                        seconds=0.5)["correct"]


def test_ivfpq_control_program_bf16_is_not_correct():
    out = control.program_bf16(spec.ROOT, "sift1m-ivfpq.poisson",
                               2 ** 33 + 17, seconds=0.5,
                               require_chip=False, peaks_kind="TPU v5 lite",
                               shrink=shrink)
    assert not out["correct"]
    c = out["checks"]["order_excess_units"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault", ["probe_skips_nearest",
                                   "adc_lut_scrambled"])
def test_ivfpq_run_with_a_wrong_candidate_set_is_not_correct(fault):
    """Answers in exact order but from the wrong candidates: only
    ``missed_pct`` can see them."""
    out = faults.reading(spec.ROOT, "sift1m-ivfpq.poisson", 2 ** 33 + 17,
                         fault, seconds=0.5, shrink=shrink,
                         require_chip=False, peaks_kind="TPU v5 lite")
    c = out["checks"]
    assert not out["correct"]
    assert c["missed_pct"]["value"] > c["missed_pct"]["limit"]
    assert c["order_excess_units"]["value"] <= c["order_excess_units"][
        "limit"] and c["malformed"]["value"] == 0
