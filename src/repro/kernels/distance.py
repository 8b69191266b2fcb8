"""Pairwise squared-Euclidean distance Pallas kernel (kNN/K-Means OP1).

The paper's scalar subtract-square loop becomes the MXU expansion
||a-c||^2 = ||a||^2 - 2 a.c + ||c||^2: one (bn x d)x(d x K) matmul per tile
plus two cheap row-norm reductions — the TPU-native form of the same math
(DESIGN.md §2). Centroid/query count K is small (k-Means k, kNN batches), so
C stays resident in VMEM while A streams through the grid pipeline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dist_kernel(a_ref, c_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)          # (bn, d)
    c = c_ref[...].astype(jnp.float32)          # (K, d)
    an = jnp.sum(a * a, axis=1, keepdims=True)  # (bn, 1)
    cn = jnp.sum(c * c, axis=1)[None, :]        # (1, K)
    cross = jax.lax.dot_general(
        a, c, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)     # (bn, K) on the MXU
    o_ref[...] = (an - 2.0 * cross + cn).astype(o_ref.dtype)


def pairwise_sq_dist(a, c, *, bn: int = 256, interpret: bool = False):
    """A (N, d), C (K, d) -> (N, K). N must tile by bn (ops.py pads)."""
    N, d = a.shape
    K, d2 = c.shape
    assert d == d2, (a.shape, c.shape)
    assert N % bn == 0, (N, bn)
    return pl.pallas_call(
        _dist_kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((K, d), lambda i: (0, 0)),   # resident in VMEM
        ],
        out_specs=pl.BlockSpec((bn, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, K), jnp.float32),
        interpret=interpret,
    )(a, c)
