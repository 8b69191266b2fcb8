"""int8 quantized Pallas kernels — the repo's analogue of the paper's
FP-representation study (§5.2, Figs. 9-11).

The paper's biggest lever is the numeric representation: swapping libgcc
soft-float for a target-optimized library buys 1.61x and an FPU up to
32.09x, and PULP-NN shows int8 is how PULP-class cores reach peak
throughput.  This module is the TPU-side version of that rung: every
batched classify hot path gains a ``quant`` arm that stores features as
int8 on a per-feature symmetric lattice and computes distances/scores in
exact integer arithmetic.

Representation-derived wins (all exact, none algorithmic hand-waving):

  * int8 tiles are 4x smaller, so the streaming row block ``bn`` grows
    under the same VMEM budget (``quant_topk_block_rows``);
  * lattice distances are bounded integers, so a distance and its lane
    index pack into ONE int32 key (``dist * bn + lane``).  Packed keys are
    unique, which deletes the entire first-position tie-break dance from
    the selection loop — a masked min per pass instead of the fp32
    kernel's compare/iota/select chain.  Ties still resolve to the
    smallest global row index, bit-equal to ``ref_distance_topk_q8``;
  * the query-norm term of ``||x-r||^2 = ||x||^2 - 2x.r + ||r||^2`` is
    rank-irrelevant per query, so the hot loop is just the int8 GEMM plus
    the row-norm broadcast; the constant is restored outside the kernel.

Numerics: int8 products are at most 127*127, so a float32 MXU/SGEMM
accumulates them EXACTLY for d <= 1040 (partial sums stay below 2^24).
The kernels therefore feed the int8 operands to the matrix unit as f32 —
int8 storage, dequant-free integer-exact accumulate — and cast the result
back to int32.  The tighter ceiling is the packed key: at the minimum
bn=32 block it requires d <= 832 (``_MAX_D``); beyond that the top-k
kernel raises instead of silently wrapping.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import pad_dim, resolve_interpret
from repro.kernels.topk_select import merge_topk, sort_topk

_IMAX = jnp.iinfo(jnp.int32).max
_QMAX = 127                     # symmetric int8 lattice: values in [-127, 127]
_ROW_MULT = 32                  # int8 sublane tile (see pallas guide)
# Two feature-count ceilings bind the fused top-k kernel: f32 accumulation
# of int8 products is exact only while partial sums stay below 2^24
# (d <= 1040), and the packed key dist*bn+lane must fit int32 even at the
# minimum block bn=_ROW_MULT, i.e. dist_span(d)*32 <= 2^31-1 (d <= 832).
# The packing bound is the tighter one, so it is THE supported limit —
# beyond it the kernel would silently wrap, not degrade.
_MAX_D = 832
_VMEM_BUDGET = 16 * 2 ** 20


# ---------------------------------------------------------------------------
# Lattice helpers
# ---------------------------------------------------------------------------


def feature_scales(absmax, eps: float = 1e-12):
    """Per-feature symmetric scale from a (d,) abs-max calibration vector."""
    absmax = jnp.asarray(absmax, jnp.float32)
    return jnp.maximum(absmax, eps) / float(_QMAX)


def quantize_rows(X, scale):
    """(..., d) float features -> int8 rows on the per-feature lattice."""
    q = jnp.round(jnp.asarray(X, jnp.float32) / scale)
    return jnp.clip(q, -_QMAX, _QMAX).astype(jnp.int8)


def dequantize_rows(q, scale):
    return q.astype(jnp.float32) * scale


def lattice_sq_norms(q):
    """(N, d) int8 -> (N,) int32 exact squared lattice norms."""
    qi = q.astype(jnp.int32)
    return jnp.sum(qi * qi, axis=1)


# ---------------------------------------------------------------------------
# Block autotuning — the int8 analogue of ops.fused_topk_block_rows
# ---------------------------------------------------------------------------


def quant_topk_working_set_bytes(bn: int, d: int, q: int, k: int) -> int:
    """VMEM working set of one quant fused distance->top-k grid step: the
    double-buffered int8 (bn, d) A tile, resident int8 (Q, d) C, the
    (Q, bn) int32 packed-key tile, the (Q, k) selection carries, and the
    (Q, k) x2 accumulator scratch + outputs.  int8 shrinks the two
    feature-carrying terms 4x vs ``ops.fused_topk_working_set_bytes``."""
    return (2 * bn * d) + q * d + bn * q * 4 + 4 * q * k * 4 \
        + 4 * q * 2 * k * 4 + 4 * q * k * 4


def dist_span(d: int) -> int:
    """Exclusive upper bound of the offset partial lattice distance
    ``an - 2*cross + OFF`` with ``OFF = 2*d*127^2`` (see kernel)."""
    return 5 * d * _QMAX * _QMAX + 2


def packed_rows_limit(d: int) -> int:
    """Largest ``bn`` whose packed key ``dist * bn + lane`` fits int32."""
    return (2 ** 31 - 1) // dist_span(d)


def quant_topk_block_rows(N: int, d: int, Q: int, k: int,
                          budget: int = _VMEM_BUDGET) -> int:
    """Largest multiple-of-32 streaming block that fits both the VMEM
    budget and the int32 key-packing bound."""
    if d > _MAX_D:
        raise ValueError(
            f"quant distance kernel supports d <= {_MAX_D} (int32 packed "
            f"selection key at the minimum bn={_ROW_MULT} block), "
            f"got d={d}")
    limit = min(packed_rows_limit(d), max(N, _ROW_MULT))
    best = _ROW_MULT
    bn = _ROW_MULT
    while bn <= limit:
        if quant_topk_working_set_bytes(bn, d, Q, k) <= budget:
            best = bn
        bn *= 2
    return best


# ---------------------------------------------------------------------------
# Fused int8 distance -> top-k (the quant arm of kNN OP1+OP2)
# ---------------------------------------------------------------------------


def _int_cross(a8, b8):
    """(m, d) x (n, d) int8 -> (m, n) int32 exact cross products via the
    f32 matrix unit (products <= 127^2, partial sums < 2^24 for d <= 1040:
    every intermediate is exactly representable)."""
    cross = jax.lax.dot_general(
        a8.astype(jnp.float32), b8.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return cross.astype(jnp.int32)


def _quant_topk_kernel(a_ref, c_ref, vals_ref, idx_ref, acc_v, acc_i,
                       *, k: int, bn: int, n_valid: int, off: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_v[...] = jnp.full_like(acc_v, _IMAX)
        acc_i[...] = jnp.zeros_like(acc_i)

    # int8 GEMM hot loop: partial distance an - 2*cross, offset to >= 0.
    # The query norm ||c||^2 is rank-irrelevant per query and is restored
    # by the wrapper outside the stream.
    # The tile is built row-major (bn, Q) and transposed once, as in the
    # fp32 kernel: broadcasting the (bn,) row norms along lanes instead
    # makes Mosaic spill past the scoped VMEM limit at Q=256.
    aq = a_ref[...]                                     # (bn, d) int8
    cross = _int_cross(aq, c_ref[...])                  # (bn, Q) int32
    an = lattice_sq_norms(aq)                           # (bn,) int32
    dist = (an[:, None] - 2 * cross + off).T            # (Q, bn) >= 0
    q = dist.shape[0]

    # pack (dist, lane) into one int32 key — unique by construction, so
    # each selection pass is a masked min with no tie-break machinery
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, bn), 1)
    key = dist * bn + lane
    key = jnp.where(i * bn + lane < n_valid, key, _IMAX)

    # fold the tile into the running k-smallest: accumulator first on
    # equal distances, then the smallest lane — the smallest global row
    # index, the same stable rule as the fp32 fused kernel and lax.top_k
    v, ix, _ = merge_topk(acc_v[...], acc_i[...], key, i * bn, k,
                          fill=_IMAX, packed_bn=bn)
    acc_v[...] = v
    acc_i[...] = ix

    @pl.when(i == pl.num_programs(0) - 1)
    def _store():
        vals_ref[...], idx_ref[...] = sort_topk(v, ix, fill=_IMAX)


def _quant_topk_call(ap, cp, k: int, *, bn: int, n_valid: int, off: int,
                     interpret: bool):
    N, d = ap.shape
    Q = cp.shape[0]
    kernel = functools.partial(_quant_topk_kernel, k=k, bn=bn,
                               n_valid=n_valid, off=off)
    return pl.pallas_call(
        kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),    # streams, int8
            pl.BlockSpec((Q, d), lambda i: (0, 0)),     # resident, int8
        ],
        out_specs=(pl.BlockSpec((Q, k), lambda i: (0, 0)),
                   pl.BlockSpec((Q, k), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((Q, k), jnp.int32),
                   jax.ShapeDtypeStruct((Q, k), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((Q, k), jnp.int32),
                        pltpu.VMEM((Q, k), jnp.int32)],
        interpret=interpret,
    )(ap, cp)


@functools.partial(jax.jit, static_argnames=("k", "bn", "interpret"))
def distance_topk_q8(aq, cq, k: int, *, bn: int | None = None,
                     interpret: bool | None = None):
    """int8 A (N, d) rows, int8 C (Q, d) queries -> (lattice sq-dist
    (Q, k) int32, global row indices (Q, k)), ascending, smallest-index
    ties — the quant arm of the fused kNN hot path.  Exact integer
    arithmetic end to end (bit-equal to ``ref_distance_topk_q8``)."""
    N, d = aq.shape
    Q = cq.shape[0]
    assert aq.dtype == jnp.int8 and cq.dtype == jnp.int8, (aq.dtype, cq.dtype)
    assert 1 <= k <= N, (k, N)
    if d > _MAX_D:                       # explicit-bn callers too
        raise ValueError(
            f"quant distance kernel supports d <= {_MAX_D} (int32 packed "
            f"selection key at the minimum bn={_ROW_MULT} block), "
            f"got d={d}")
    if bn is None:
        bn = quant_topk_block_rows(N, d, Q, k)
    bn = min(bn, packed_rows_limit(d))
    bn = max(_ROW_MULT, (min(bn, max(N, _ROW_MULT)) // _ROW_MULT) * _ROW_MULT)
    assert dist_span(d) * bn <= 2 ** 31 - 1, (d, bn)   # key cannot wrap
    interpret = resolve_interpret(interpret)
    off = 2 * d * _QMAX * _QMAX
    ap = pad_dim(aq, bn, 0, 0)
    cp = pad_dim(cq, 8, 0, 0)
    vals, idx = _quant_topk_call(ap, cp, k, bn=bn, n_valid=N, off=off,
                                 interpret=interpret)
    cn = lattice_sq_norms(cp)                           # restore ||c||^2
    return (vals[:Q] - off) + cn[:Q, None], idx[:Q]


def ref_distance_topk_q8(aq, cq, k: int):
    """Pure-jnp oracle: exact int32 lattice distances, smallest-index
    ties (``lax.top_k`` on the negated distances)."""
    ai = aq.astype(jnp.int32)
    ci = cq.astype(jnp.int32)
    an = jnp.sum(ai * ai, axis=1)[None, :]              # (1, N)
    cn = jnp.sum(ci * ci, axis=1)[:, None]              # (Q, 1)
    dist = an - 2 * (ci @ ai.T) + cn                    # (Q, N) int32 exact
    nv, ni = jax.lax.top_k(-dist, k)
    return -nv, ni


# ---------------------------------------------------------------------------
# Fused int8 distance -> argmin (the quant arm of K-Means OP1+OP2)
# ---------------------------------------------------------------------------


def _quant_argmin_kernel(a_ref, c_ref, cn_ref, val_ref, idx_ref, *,
                         off: int, kp: int, packed: bool):
    aq = a_ref[...]                                     # (bn, d) int8
    cross = _int_cross(aq, c_ref[...])                  # (bn, K) int32
    # the row norm ||a||^2 is rank-irrelevant per row; restored outside.
    # The centroid norms arrive as a (1, K) row: reducing them in-kernel
    # and broadcasting the result along lanes never finishes compiling.
    dist = cn_ref[...] - 2 * cross + off                # (bn, K) >= 0
    bn, K = dist.shape
    if packed:
        cols = jax.lax.broadcasted_iota(jnp.int32, (bn, K), 1)
        m = jnp.min(dist * kp + cols, axis=1)           # unique packed keys
        val_ref[...] = (m // kp)[:, None]
        idx_ref[...] = (m % kp)[:, None]
    else:
        m = jnp.min(dist, axis=1)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bn, K), 1)
        first = jnp.min(jnp.where(dist == m[:, None], cols, K), axis=1)
        val_ref[...] = m[:, None]
        idx_ref[...] = first[:, None].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def distance_argmin_q8(aq, cq, *, bn: int = 1024,
                       interpret: bool | None = None):
    """int8 A (N, d), int8 centroids (K, d) -> (lattice sq-dist (N,)
    int32, nearest id (N,)).  Packed single-min selection when the key
    fits int32, first-index masked argmin otherwise."""
    N, d = aq.shape
    K = cq.shape[0]
    assert aq.dtype == jnp.int8 and cq.dtype == jnp.int8, (aq.dtype, cq.dtype)
    if d > _MAX_D:
        raise ValueError(f"quant argmin supports d <= {_MAX_D}, got {d}")
    interpret = resolve_interpret(interpret)
    off = 2 * d * _QMAX * _QMAX
    kp = 1
    while kp < K:
        kp *= 2
    packed = dist_span(d) * kp <= 2 ** 31 - 1
    bn = max(_ROW_MULT, (min(bn, max(N, _ROW_MULT)) // _ROW_MULT) * _ROW_MULT)
    ap = pad_dim(aq, bn, 0, 0)
    kernel = functools.partial(_quant_argmin_kernel, off=off, kp=kp,
                               packed=packed)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(ap.shape[0] // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((K, d), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
        ],
        out_specs=(pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((ap.shape[0], 1), jnp.int32),
                   jax.ShapeDtypeStruct((ap.shape[0], 1), jnp.int32)),
        interpret=interpret,
    )(ap, cq, lattice_sq_norms(cq)[None, :])
    an = lattice_sq_norms(aq)                           # restore ||a||^2
    return (vals[:N, 0] - off) + an, idx[:N, 0]


def ref_distance_argmin_q8(aq, cq):
    ai = aq.astype(jnp.int32)
    ci = cq.astype(jnp.int32)
    dist = jnp.sum(ai * ai, 1)[:, None] - 2 * (ai @ ci.T) \
        + jnp.sum(ci * ci, 1)[None, :]
    return jnp.min(dist, axis=1), jnp.argmin(dist, axis=1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# int8 features vs precomputed affine score tables (GNB / GMM quant arms)
# ---------------------------------------------------------------------------


def affine_scores(xq, quad, lin, const):
    """int8 features (B, d) against fp32 per-class affine score tables:
    ``score[b, c] = sum_f quad[c, f]*xq^2 + lin[c, f]*xq + const[c]``.

    This is the GEMM-identity form of the Gaussian log-density — the
    (B, C, d) broadcast diff tensor of the fp32 kernel collapses into two
    (B, d) x (d, C) matmuls over exactly-representable integer features
    (xq^2 <= 127^2), with every divide/log folded into the tables at
    calibration time."""
    xf = xq.astype(jnp.float32)
    return (xf * xf) @ quad.T + xf @ lin.T + const[None, :]
