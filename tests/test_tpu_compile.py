"""Compile-only checks of the serve path's Pallas kernels for a TPU v5e.

Interpret mode accepts constructs the TPU compiler (Mosaic) refuses: a
store to a column picked by a loop index, a lane gather whose index shape
differs from its table, a broadcast that overflows scoped VMEM.  These
tests lower and compile every main-path kernel with ``interpret=False`` at
the smoke run's widths (``chip_smoke.py``: d=128, 256-query buckets, k=10)
against a described ``v5e:2x2`` topology — no chip is attached — and
assert the kernel is in the compiled program (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compilation cache stays off around these
compiles (an entry written for a described chip cannot be read back
without one).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ann, ops, quantized

D, Q, K = 128, 256, 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


# name -> (kernel call with interpret=False, argument (shape, dtype) list)
KERNELS = {
    "distance_topk": (
        lambda a, c: ops.distance_topk(a, c, K, interpret=False),
        [((1 << 20, D), jnp.float32), ((Q, D), jnp.float32)]),
    # the serve buckets of one query and of the open-loop cells
    "distance_topk-q8rows": (
        lambda a, c: ops.distance_topk(a, c, K, interpret=False),
        [((1 << 20, D), jnp.float32), ((8, D), jnp.float32)]),
    "distance_topk-q64rows": (
        lambda a, c: ops.distance_topk(a, c, K, interpret=False),
        [((1 << 20, D), jnp.float32), ((64, D), jnp.float32)]),
    # the merge-pass counter: one more output, in SMEM
    "distance_topk-stats": (
        lambda a, c: ops.distance_topk(a, c, K, stats=True, interpret=False),
        [((1 << 20, D), jnp.float32), ((Q, D), jnp.float32)]),
    "topk_smallest": (
        lambda x: ops.topk_smallest(x, K, interpret=False),
        [((Q, 4096), jnp.float32)]),
    "distance_argmin": (
        lambda a, c: ops.distance_argmin(a, c, interpret=False),
        [((1 << 20, D), jnp.float32), ((1024, D), jnp.float32)]),
    "distance_topk_q8": (
        lambda a, c: quantized.distance_topk_q8(a, c, K, interpret=False),
        [((1 << 18, D), jnp.int8), ((Q, D), jnp.int8)]),
    "distance_argmin_q8": (
        lambda a, c: quantized.distance_argmin_q8(a, c, interpret=False),
        [((1 << 18, D), jnp.int8), ((256, D), jnp.int8)]),
    "adc_topk": (
        lambda lut, codes, ids: ann.adc_topk(lut, codes, ids, 100,
                                             interpret=False),
        [((Q, 16 * 256), jnp.int32), ((Q, 32768, 16), jnp.int8),
         ((Q, 32768), jnp.int32)]),
    "gnb_scores_batch": (
        lambda x, mu, var, lp: ops.gnb_scores_batch(x, mu, var, lp,
                                                    interpret=False),
        [((Q, D), jnp.float32), ((10, D), jnp.float32),
         ((10, D), jnp.float32), ((10,), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = KERNELS[name]
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in args]
    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


@pytest.mark.parametrize("strategy", ["reference", "query"])
def test_sharded_knn_compiles_for_2x2_mesh(strategy, topo, monkeypatch):
    """The 4-chip exact-kNN serve executor at the smoke's size: the fused
    kernel runs per shard, and the reference partition merges with the
    butterfly (collective-permute)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.estimator import KNNEstimator
    from repro.core.knn import KNNModel

    # the kernel wrappers pick the interpreter from the default backend,
    # which is the CPU here: steer them to the compiled kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data")) if strategy == "reference" \
        else rep
    est = KNNEstimator(K, n_class=10)
    params = KNNModel(
        A=jax.ShapeDtypeStruct((1_000_000, D), jnp.float32, sharding=rows),
        labels=jax.ShapeDtypeStruct((1_000_000,), jnp.int32, sharding=rep),
        n_class=10)
    est._params = params
    text = _compiled_text(est.predict_batch_sharded_fn(mesh, "data",
                                                       strategy),
                          params, jax.ShapeDtypeStruct((Q, D), jnp.float32,
                                                       sharding=rep))
    assert "tpu_custom_call" in text
    assert ("collective-permute" in text) == (strategy == "reference")
