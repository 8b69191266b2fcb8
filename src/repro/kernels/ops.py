"""Jit'd public wrappers for the Pallas kernels: shape padding, block-size
selection, and the interpret switch (``resolve_interpret``): on the CPU
backend the kernels run in the Pallas interpreter, on a TPU they compile
to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import distance as _distance
from repro.kernels import distance_topk as _dtopk
from repro.kernels import flash_attention as _flash
from repro.kernels import gemm as _gemm
from repro.kernels import gnb_score as _gnb
from repro.kernels import topk_select as _topk

_VMEM_BUDGET = 16 * 2 ** 20   # ~16 MiB/core, matching benchmarks/kernel_blocks


def resolve_interpret(interpret: bool | None) -> bool:
    """The one interpret-mode switch for every kernel wrapper: None means
    "interpret exactly when the default backend is the CPU".  Asking for
    the interpreter on an accelerator is refused — a served kernel must
    never fall back to it there."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError("interpret=True on the "
                         f"{jax.default_backend()!r} backend: kernels "
                         "compile there, the interpreter is CPU-only")
    return bool(interpret)


def pad_dim(x, mult: int, axis: int, value=0.0):
    """Pad ``axis`` of ``x`` up to a multiple of ``mult`` with ``value`` —
    the one padding rule of every kernel wrapper."""
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def clamp_block(b: int, n: int, mult: int = 8) -> int:
    """Shrink block size ``b`` for a small dimension ``n``: round n up to a
    multiple of ``mult`` so the result both respects TPU sublane tiling and
    divides the padded dimension.  (The old ``min(b, max(8, n))`` clamp could
    return a non-multiple-of-8 block for 8 < n < b, which Mosaic rejects.)"""
    if n >= b:
        return b
    return max(mult, ((n + mult - 1) // mult) * mult)


def fused_topk_working_set_bytes(bn: int, d: int, q: int, k: int) -> int:
    """VMEM working set of one fused distance->top-k grid step:
    double-buffered (bn, d) A tile, resident (Q, d) C, (bn, Q) distance
    tile, the masked (Q, bn) tile and (Q, k) selection carries (values +
    indices), and the (Q, k) x2 accumulator scratch + (Q, k) x2 outputs.  Single source of truth —
    benchmarks/kernel_blocks.py reports from this same formula."""
    return (2 * bn * d * 4) + q * d * 4 + bn * q * 4 \
        + 2 * (k + bn) * q * 4 + 4 * q * k * 4


def fused_topk_block_rows(N: int, d: int, Q: int, k: int,
                          budget: int = _VMEM_BUDGET) -> int:
    """Autotuned streaming row-block for the fused distance->top-k kernel:
    the largest bn whose working set fits the VMEM budget."""
    best = 8
    for bn in (8, 16, 32, 64, 128, 256, 512, 1024, 2048):
        if bn > max(N, 8):
            break
        if fused_topk_working_set_bytes(bn, d, Q, k) <= budget:
            best = bn
    return best


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128,
           interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    M, K = a.shape
    N = b.shape[1]
    bm = clamp_block(bm, M)
    ap = pad_dim(pad_dim(a, bm, 0), bk, 1)
    bp = pad_dim(pad_dim(b, bk, 0), bn, 1)
    out = _gemm.matmul(ap, bp, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def pairwise_sq_dist(a, c, *, bn: int = 256, interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    N = a.shape[0]
    bn = clamp_block(bn, N)
    ap = pad_dim(a, bn, 0)
    out = _distance.pairwise_sq_dist(ap, c, bn=bn, interpret=interpret)
    return out[:N]


@functools.partial(jax.jit, static_argnames=("k", "bn", "stats",
                                             "interpret"))
def distance_topk(a, c, k: int, *, bn: int | None = None,
                  stats: bool = False, interpret: bool | None = None):
    """Fused kNN hot path: A (N, d) data, C (Q, d) queries -> k nearest rows
    per query as (values (Q, k), global indices (Q, k)), ascending.  The
    (N, Q) distance matrix never leaves VMEM (DESIGN.md §3); bn=None picks
    the largest streaming block that fits the VMEM budget.  ``stats`` adds
    a third result, int32 (merge passes run, tiles), counted over the
    queries padded to a multiple of 8."""
    interpret = resolve_interpret(interpret)
    N, d = a.shape
    Q = c.shape[0]
    assert 1 <= k <= N, (k, N)
    if bn is None:
        bn = fused_topk_block_rows(N, d, Q, k)
    bn = clamp_block(bn, N)
    ap = pad_dim(a, bn, 0)
    cp = pad_dim(c, 8, 0)
    out = _dtopk.distance_topk(ap, cp, k, bn=bn, n_valid=N, stats=stats,
                               interpret=interpret)
    return (out[0][:Q], out[1][:Q]) + tuple(out[2:])


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def distance_argmin(a, c, *, bn: int = 256, interpret: bool | None = None):
    """Fused K-Means OP1+OP2: A (N, d), C (K, d) -> (min sq-dist (N,),
    nearest-centroid id (N,)) without materialising the (N, K) matrix."""
    interpret = resolve_interpret(interpret)
    N = a.shape[0]
    bn = clamp_block(bn, N)
    ap = pad_dim(a, bn, 0)
    vals, idx = _dtopk.distance_argmin(ap, c, bn=bn, interpret=interpret)
    return vals[:N, 0], idx[:N, 0]


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def gnb_scores(x, mu, var, log_prior, *, bd: int = 128,
               interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    d = x.shape[0]
    bd = clamp_block(bd, d)
    xp = pad_dim(x, bd, 0)
    mup = pad_dim(mu, bd, 1)
    varp = pad_dim(var, bd, 1, value=1.0)
    # padded features: x=0, mu=0, var=1 adds a constant -0.5*log(2*pi) per
    # pad to every class — subtract it back out
    import math
    n_pad = xp.shape[0] - d
    out = _gnb.gnb_scores(xp, mup, varp, log_prior, bd=bd,
                          interpret=interpret)
    return out + 0.5 * math.log(2.0 * math.pi) * n_pad


@functools.partial(jax.jit, static_argnames=("bb", "bd", "interpret"))
def gnb_scores_batch(X, mu, var, log_prior, *, bb: int = 8, bd: int = 128,
                     interpret: bool | None = None):
    """Batched GNB scoring: X (B, d) queries -> (B, C) joint log-likelihood.
    Both the query-block ``bb`` and feature-chunk ``bd`` use the divisor-safe
    multiple-of-8 clamp (``clamp_block``) so small B or ragged d can never
    produce a Mosaic-rejected block shape."""
    interpret = resolve_interpret(interpret)
    B, d = X.shape
    bb = clamp_block(bb, B)
    bd = clamp_block(bd, d)
    Xp = pad_dim(pad_dim(X, bb, 0), bd, 1)
    mup = pad_dim(mu, bd, 1)
    varp = pad_dim(var, bd, 1, value=1.0)
    # padded features (x=0, mu=0, var=1) add a constant -0.5*log(2*pi) per
    # pad to every class — subtract it back out; padded query rows are junk
    # and sliced off
    import math
    n_pad = Xp.shape[1] - d
    out = _gnb.gnb_scores_batch(Xp, mup, varp, log_prior, bb=bb, bd=bd,
                                interpret=interpret)
    return out[:B] + 0.5 * math.log(2.0 * math.pi) * n_pad


@functools.partial(jax.jit, static_argnames=("k", "br", "interpret"))
def topk_smallest(x, k: int, *, br: int = 8, interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    R, n = x.shape
    br = clamp_block(br, R)
    xp = pad_dim(x, br, 0, value=jnp.inf)
    vals, idx = _topk.topk_smallest(xp, k, br=br, interpret=interpret)
    return vals[:R], idx[:R]


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool | None = None):
    """q/k/v: (B, H, S, d). GQA callers expand KV heads beforehand."""
    interpret = resolve_interpret(interpret)
    B, H, S, d = q.shape
    bq = min(bq, S)
    bk = min(bk, S)
    qf = q.reshape(B * H, S, d)
    kf = k.reshape(B * H, S, d)
    vf = v.reshape(B * H, S, d)
    out = _flash.flash_attention(qf, kf, vf, causal=causal, bq=bq, bk=bk,
                                 interpret=interpret)
    return out.reshape(B, H, S, d)
