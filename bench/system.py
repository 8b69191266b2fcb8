"""The system under test, built as a deployment runs it: ``make_fitted``,
a ``NonNeuralServeEngine`` warmed for every bucket the traffic uses, and a
``RequestScheduler`` in front of it.

Settings: no result cache (``cache_size=0``: the pool's repeated queries
are never hits), no shedding, no degrade ladder, the static arm selector
(autotune off), and ``max_wait=1``, so every drain launches what is
queued.  The benchmark times each ``engine.classify`` call, which returns
once the launch is enqueued: that is the engine's host time.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List

import numpy as np


@dataclass
class System:
    estimator: object
    engine: object
    scheduler: object
    warmed: frozenset
    classify_s: List[float] = field(default_factory=list)


def fitted_kwargs(config: dict, traffic: dict) -> dict:
    return dict(config["fitted"], k=int(traffic["k"]))


def warm_buckets(config: dict, traffic: dict) -> List[int]:
    """The buckets the traffic can use: listed in the mix, else every
    power of two up to the engine's ``max_batch``."""
    if "warm_buckets" in traffic:
        return sorted(int(b) for b in traffic["warm_buckets"])
    top = int(config["max_batch"])
    return [1 << i for i in range(top.bit_length()) if 1 << i <= top]


def build(config: dict, traffic: dict, base, labels, *, estimator=None,
          span=None) -> System:
    """Fit (unless ``estimator`` is given), warm and wrap the serve path."""
    import jax
    import jax.numpy as jnp

    from repro.core.estimator import make_fitted
    from repro.serving import NonNeuralServeEngine, RequestScheduler

    if estimator is None:
        estimator = make_fitted(config["estimator"], base, labels,
                                n_groups=int(config["data"]["labels"]),
                                **fitted_kwargs(config, traffic))
    d = int(base.shape[1])
    engine = NonNeuralServeEngine(estimator,
                                  max_batch=int(config["max_batch"]))
    buckets = warm_buckets(config, traffic)
    for b in buckets:
        # the whole classify path, slicing included, compiles here
        engine.warmup(jnp.zeros((b, d), jnp.float32))
        jax.block_until_ready(engine.classify(np.zeros((b, d),
                                                       np.float32)).aux)
    engine.bucket_launches.clear()
    warmed = frozenset(engine.warmed)
    system = System(estimator, engine, None, warmed)
    classify = engine.classify

    def timed_classify(X):
        t = time.perf_counter()
        if span is None:
            out = classify(X)
        else:
            with span("classify"):
                out = classify(X)
        system.classify_s.append(time.perf_counter() - t)
        return out

    engine.classify = timed_classify
    system.scheduler = RequestScheduler(engine, max_wait=1, cache_size=0)
    return system
