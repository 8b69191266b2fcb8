"""Fused distance -> top-k streaming Pallas kernel (kNN OP1+OP2 in one pass).

The paper keeps the distance array ``e`` resident in per-cluster L1 and
consumes it in place with Selection Sort (§4.4, Figs. 6-7).  The two-kernel
TPU port (``distance.py`` -> ``topk_select.py``) loses exactly that reuse:
the full (N, Q) distance matrix round-trips through HBM between the passes.
Here the two stages fuse: each grid step computes one (bn x Q) distance tile
via the MXU expansion and immediately folds it into a running k-smallest
accumulator held in VMEM scratch — the TPU analogue of the paper's
L1-resident ``e`` (DESIGN.md §3).  The (N, Q) matrix never materialises.

Tie semantics match the two-pass reference bit-for-bit: the accumulator
only ever holds global row indices smaller than the incoming tile's, a tile
entry replaces an accumulator entry only when strictly smaller, and ties
inside a tile go to the first lane, so the accumulator is always the k
smallest under (distance, global index) — the set a stable sort keeps.  A
tile's masked-min passes stop once no query's tile minimum beats its k-th
best, which after the first few tiles is almost at once; the set is sorted
after the last tile (``topk_select.merge_topk``/``sort_topk``).

``distance_argmin`` is the K-Means variant (OP1+OP2 with k=1): the reduction
runs along the small centroid axis of each tile, so no cross-step state is
needed — each row block writes its nearest-centroid id directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_select import merge_topk, sort_topk

_INF = float("inf")


def _sq_dist_tile(a, c):
    """(bn, d), (Q, d) -> (bn, Q) with the exact arithmetic of distance.py
    (same operand order, f32 accumulate) so fused values are bit-equal to
    the two-pass kernel's."""
    a = a.astype(jnp.float32)
    c = c.astype(jnp.float32)
    an = jnp.sum(a * a, axis=1, keepdims=True)   # (bn, 1)
    cn = jnp.sum(c * c, axis=1)[None, :]         # (1, Q)
    cross = jax.lax.dot_general(
        a, c, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)      # (bn, Q) on the MXU
    return an - 2.0 * cross + cn


def _fused_kernel(a_ref, c_ref, vals_ref, idx_ref, *refs, k: int, bn: int,
                  n_valid: int, stats: bool):
    if stats:
        stats_ref, acc_v, acc_i, passes = refs
    else:
        acc_v, acc_i = refs
    i = pl.program_id(0)
    last = pl.num_programs(0) - 1

    @pl.when(i == 0)
    def _init():
        acc_v[...] = jnp.full_like(acc_v, _INF)
        acc_i[...] = jnp.zeros_like(acc_i)
        if stats:
            passes[0] = 0

    tile = _sq_dist_tile(a_ref[...], c_ref[...]).T        # (Q, bn)
    q = tile.shape[0]
    gidx = i * bn + jax.lax.broadcasted_iota(jnp.int32, (q, bn), 1)
    tile = jnp.where(gidx < n_valid, tile, _INF)          # mask padded rows

    # merge the tile into the running k-smallest: masked-min passes over
    # the tile, only while one still beats a query's k-th best — the
    # in-VMEM Selection Sort of the paper's OP2
    v, ix, n = merge_topk(acc_v[...], acc_i[...], tile, i * bn, k, fill=_INF)
    acc_v[...] = v
    acc_i[...] = ix
    if stats:
        passes[0] += n

    @pl.when(i == last)
    def _store():
        vals, idx = sort_topk(v, ix, fill=_INF)
        vals_ref[...] = vals.astype(vals_ref.dtype)
        idx_ref[...] = idx
        if stats:
            stats_ref[0] = passes[0]
            stats_ref[1] = last + 1


def distance_topk(a, c, k: int, *, bn: int = 256, n_valid: int | None = None,
                  stats: bool = False, interpret: bool = False):
    """A (N, d) data rows, C (Q, d) queries -> (values (Q, k), idx (Q, k)),
    ascending squared distances with global row indices.  N must tile by bn
    (ops.py pads); rows >= n_valid are masked out of the selection.  With
    ``stats`` a third output, int32 (2,) in SMEM, holds the merge passes
    run over all tiles and the number of tiles."""
    N, d = a.shape
    Q, d2 = c.shape
    assert d == d2, (a.shape, c.shape)
    assert N % bn == 0, (N, bn)
    n_valid = N if n_valid is None else n_valid
    assert 1 <= k <= n_valid, (k, n_valid)
    kernel = functools.partial(_fused_kernel, k=k, bn=bn, n_valid=n_valid,
                               stats=stats)
    out_specs = [pl.BlockSpec((Q, k), lambda i: (0, 0)),
                 pl.BlockSpec((Q, k), lambda i: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((Q, k), jnp.float32),
                 jax.ShapeDtypeStruct((Q, k), jnp.int32)]
    scratch = [pltpu.VMEM((Q, k), jnp.float32), pltpu.VMEM((Q, k), jnp.int32)]
    if stats:
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((2,), jnp.int32))
        scratch.append(pltpu.SMEM((1,), jnp.int32))
    return pl.pallas_call(
        kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),      # streams
            pl.BlockSpec((Q, d), lambda i: (0, 0)),       # resident in VMEM
        ],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        interpret=interpret,
    )(a, c)


def _argmin_kernel(a_ref, c_ref, val_ref, idx_ref):
    tile = _sq_dist_tile(a_ref[...], c_ref[...])          # (bn, K)
    bn, K = tile.shape
    m = jnp.min(tile, axis=1)                             # (bn,)
    kcols = jax.lax.broadcasted_iota(jnp.int32, (bn, K), 1)
    first = jnp.min(jnp.where(tile == m[:, None], kcols, K), axis=1)
    val_ref[...] = m[:, None].astype(val_ref.dtype)
    idx_ref[...] = first[:, None].astype(jnp.int32)


def distance_argmin(a, c, *, bn: int = 256, interpret: bool = False):
    """A (N, d), C (K, d) -> (min sq-dist (N, 1), nearest id (N, 1)).

    K-Means OP1+OP2 fused (Selection Sort with k=1 == argmin): the (N, K)
    distance matrix lives only as per-step (bn, K) tiles in VMEM."""
    N, d = a.shape
    K, d2 = c.shape
    assert d == d2, (a.shape, c.shape)
    assert N % bn == 0, (N, bn)
    return pl.pallas_call(
        _argmin_kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((K, d), lambda i: (0, 0)),
        ],
        out_specs=(pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((N, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, 1), jnp.int32)),
        interpret=interpret,
    )(a, c)
