"""warmup(autotune=True): the measurement-driven arm picker (paper §5.2
profile-then-optimize at warmup time, DESIGN.md §12).

The engine's ``_measure`` is an overridable seam: these tests script its
timings so the winner flips deterministically, then check the production
invariants — tuned winners come from registered arms, launches route
through them, explicit pins collapse the search axis, and
``bucket_launches ⊆ warmed`` survives autotuned serving.
"""
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from conftest import synth_blobs
from repro.core import estimator as E
from repro.kernels import dispatch
from repro.serving import NonNeuralServeEngine


@pytest.fixture(scope="module")
def blobs():
    return synth_blobs(n=240, d=16, n_class=3)


def _engine(X, y, algo="knn", **kw):
    est = E.make_fitted(algo, X, y, n_groups=3, **kw)
    return NonNeuralServeEngine(est, max_batch=64)


def _script(engine, pick):
    """Replace the timing seam: the arm matching ``pick`` measures fast,
    everything else slow.  Relies on ``_autotune_bucket`` iterating
    ``_autotune_candidates`` in order."""
    state = {"cands": None, "i": 0}

    def fake(fn, params, chunk, iters=3):
        i = state["i"]
        state["i"] += 1
        arm = state["cands"][i]
        return 5.0 if pick(arm) else 50.0

    orig = engine._autotune_candidates

    def candidates(bucket):
        state["cands"] = orig(bucket)
        state["i"] = 0
        return state["cands"]

    engine._autotune_candidates = candidates
    engine._measure = fake


def test_scripted_flip_routes_through_ref(blobs):
    X, y = blobs
    engine = _engine(X, y, "knn")
    _script(engine, lambda arm: arm[1] == "ref")
    engine.warmup(X[:32], autotune=True)
    arm = engine.tuned[32]
    assert arm.path == "ref"
    assert arm.static_path == "fused"      # the shape selector's verdict
    assert arm.differs
    assert arm.us < arm.static_us
    # production launches route through the tuned arm and stay inside
    # the warmed set
    res = engine.classify(X[:32])
    assert set(engine.bucket_launches) <= engine.warmed
    want, _ = engine.estimator.predict_batch(X[:32])
    np.testing.assert_array_equal(np.asarray(res.classes), np.asarray(want))


def test_scripted_static_winner_does_not_differ(blobs):
    X, y = blobs
    engine = _engine(X, y, "knn")
    # candidate 0 is always the static arm
    _script(engine, lambda arm: arm == (engine._route(32), None, None))
    engine.warmup(X[:32], autotune=True)
    arm = engine.tuned[32]
    assert arm.path is None and arm.bn is None
    assert not arm.differs
    assert arm.us == arm.static_us


def test_scripted_bn_winner(blobs):
    X, y = blobs
    engine = _engine(X, y, "knn")
    _script(engine, lambda arm: arm[2] == 64)
    engine.warmup(X[:32], autotune=True)
    arm = engine.tuned[32]
    assert (arm.path, arm.bn) == ("fused", 64)
    assert arm.differs
    res = engine.classify(X[:32])
    want, _ = engine.estimator.predict_batch(X[:32])
    np.testing.assert_array_equal(np.asarray(res.classes), np.asarray(want))


def test_candidates_come_from_registry(blobs):
    X, y = blobs
    engine = _engine(X, y, "knn")
    regd = dispatch.registered()[("knn", "distance_topk")]
    for s, p, bn in engine._autotune_candidates(32):
        assert s == "single"               # no mesh on this engine
        assert p is None or p in regd
        assert p != "quant"                # lossy arm never implicit
        assert bn in (None, 64, 256)


def test_explicit_path_collapses_path_axis(blobs):
    X, y = blobs
    engine = _engine(X, y, "knn", path="ref")
    cands = engine._autotune_candidates(32)
    assert all(p is None for _, p, _ in cands)
    engine.warmup(X[:32], autotune=True)
    # winner keeps the pinned path (choice path None -> estimator.path)
    arm = engine.tuned[32]
    assert arm.path is None
    assert arm.static_path == "ref"


def test_env_override_collapses_path_axis(blobs, monkeypatch):
    X, y = blobs
    engine = _engine(X, y, "knn")
    monkeypatch.setenv(dispatch.ENV_VAR, "ref")
    assert all(p is None
               for _, p, _ in engine._autotune_candidates(32))


def test_quantized_engine_never_explores_paths(blobs):
    X, y = blobs
    engine = _engine(X, y, "knn", policy=dispatch.get_policy("int8"))
    assert engine._quantized
    cands = engine._autotune_candidates(32)
    assert all(p is None for _, p, _ in cands)
    assert engine._static_arm(32)[1] == "quant"


def test_real_autotune_end_to_end(blobs):
    """No scripting: really micro-time the arms, and the tuned winner must
    not lose to the static arm it was measured against (acceptance: never
    slower, and on this substrate some (algo, bucket) usually flips)."""
    X, y = blobs
    engine = _engine(X, y, "knn")
    engine.warmup(X[:32], autotune=True)
    arm = engine.tuned.get(32)
    assert arm is not None
    assert arm.us <= arm.static_us * 1.001
    assert len(arm.candidates) >= 3        # static + real alternatives
    res = engine.classify(X[:40])          # 32 + trailing 8 bucket
    assert set(engine.bucket_launches) <= engine.warmed
    want, _ = engine.estimator.predict_batch(X[:40])
    np.testing.assert_array_equal(np.asarray(res.classes), np.asarray(want))


def test_warmup_without_autotune_leaves_tuned_empty(blobs):
    X, y = blobs
    engine = _engine(X, y, "gnb")
    engine.warmup(X[:32])
    assert engine.tuned == {}
    s, p, bn = engine._choice(32)
    assert (p, bn) == (None, None)


def test_autotune_raises_when_an_arm_fails(blobs):
    """A failure while timing an arm (a kernel the compiler refuses) must
    surface, not silently drop the arm from the table."""
    X, y = blobs
    engine = _engine(X, y, "knn")
    cands = engine._autotune_candidates(32)

    def measure(fn, params, chunk, iters=3):
        if measure.calls == 1:               # the second arm fails
            raise RuntimeError("Mosaic failed to compile TPU kernel")
        measure.calls += 1
        return 5.0

    measure.calls = 0
    engine._measure = measure
    assert len(cands) >= 2
    with pytest.raises(RuntimeError, match="Mosaic"):
        engine.warmup(X[:32], autotune=True)


def test_autotune_skips_only_strategies_without_a_sharded_arm(blobs):
    """The one arm autotune may skip: a mesh strategy the algorithm has no
    sharded executor for (ANN has no reference partition)."""
    from repro.launch.mesh import _mk

    X, y = blobs
    mesh = _mk((1,), ("data",))
    ann = NonNeuralServeEngine(E.make_fitted("ann", X, y, n_groups=3),
                               mesh=mesh, max_batch=64)
    knn = NonNeuralServeEngine(E.make_fitted("knn", X, y, n_groups=3),
                               mesh=mesh, max_batch=64)
    assert not ann._has_executor("reference")
    assert ann._has_executor("query") and ann._has_executor("single")
    assert knn._has_executor("reference") and knn._has_executor("query")
