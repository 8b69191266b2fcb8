"""Plain exact k-nearest-neighbour reference and the comparisons that
decide ``correct`` for vector-search cells.

Independent of the program under test: nothing here imports ``repro``.
The candidate search is the brute-force matmul expansion at
``highest`` precision with a two-stage ``lax.top_k``; the candidates are
then re-ranked on the host by exact float64 distances.

Distances are compared in units of the fp32 rounding scale of the
expansion ``||a||^2 - 2 a.q + ||q||^2``: one unit is
``2**-24 * (|q| + max|a|)**2`` for query ``q``.
"""
from __future__ import annotations

import numpy as np

U = 2.0 ** -24
_EXTRA = 4          # candidates kept beyond k before the exact re-rank
_CHUNK = 256        # queries per reference block


def _bf16(x):
    """Round fp32 to bfloat16 precision, kept in fp32.  An explicit op, so
    no compiler drops the rounding as excess precision."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def cross_highest(q, A):
    """q @ A.T in full fp32."""
    import jax
    import jax.numpy as jnp

    return jnp.dot(q, A.T, precision=jax.lax.Precision.HIGHEST)


def cross_bf16x3(q, A):
    """q @ A.T in three bfloat16 passes (hi*hi + hi*lo + lo*hi, fp32
    accumulate): what ``precision="high"`` does on a TPU, spelled out so
    that it gives the same numbers on every backend."""
    qh, ah = _bf16(q), _bf16(A)
    ql, al = _bf16(q - qh), _bf16(A - ah)
    # products of bfloat16 values are exact in fp32
    return cross_highest(qh, ah) + (cross_highest(qh, al)
                                    + cross_highest(ql, ah))


def expansion_topk(A, Q, m: int, cross=cross_highest, *, chunk=_CHUNK):
    """Top-``m`` row ids per query by the expansion distance, ascending.
    ``A`` (N, d) and ``Q`` (P, d) are device arrays; returns numpy (P, m)."""
    import jax
    import jax.numpy as jnp

    n = A.shape[0]
    groups = min(1024, max(1, n // 64))      # two-stage top-k width
    width = -(-n // groups)

    @jax.jit
    def one(q, A, an):      # A as an argument: a closure would embed it
        d = an[None, :] - 2.0 * cross(q, A) + jnp.sum(q * q, 1)[:, None]
        d = jnp.pad(d, ((0, 0), (0, groups * width - n)),
                    constant_values=jnp.inf).reshape(q.shape[0], groups,
                                                     width)
        v, i = jax.lax.top_k(-d, min(m, width))              # per group
        i = i + (jnp.arange(groups) * width)[None, :, None]
        _, j = jax.lax.top_k(v.reshape(q.shape[0], -1), m)
        return jnp.take_along_axis(i.reshape(q.shape[0], -1), j, axis=1)

    an = jnp.sum(A * A, axis=1)
    out = []
    for lo in range(0, Q.shape[0], chunk):
        out.append(np.asarray(one(Q[lo:lo + chunk], A, an)))
    return np.concatenate(out)


def exact_sqdist(A_host, Q_host, ids):
    """Exact float64 squared distances of rows ``ids`` (P, m) to each
    query (P, d).  Invalid ids (< 0) read +inf."""
    rows = A_host[np.maximum(ids, 0)].astype(np.float64)
    diff = rows - Q_host[:, None, :].astype(np.float64)
    d = np.einsum("pmd,pmd->pm", diff, diff)
    return np.where(ids < 0, np.inf, d)


def ulp_scale(A_host, Q_host):
    """One comparison unit per query: U * (|q| + max|a|)^2."""
    r = float(np.sqrt(np.max(np.einsum("nd,nd->n", A_host, A_host,
                                       dtype=np.float64))))
    qn = np.sqrt(np.einsum("pd,pd->p", Q_host, Q_host, dtype=np.float64))
    return U * (qn + r) ** 2


class GroundTruth:
    """Exact neighbours of every pool query: ids (P, k+1) and their exact
    float64 distances, ascending by (distance, id)."""

    def __init__(self, A, Q, A_host, Q_host, k: int):
        cand = expansion_topk(A, Q, k + _EXTRA)
        dist = exact_sqdist(A_host, Q_host, cand)
        order = np.lexsort((cand, dist), axis=1)[:, :k + 1]
        self.k = k
        self.ids = np.take_along_axis(cand, order, axis=1)
        self.dist = np.take_along_axis(dist, order, axis=1)
        self.unit = ulp_scale(A_host, Q_host)


def unique_answers(pool_idx, ids):
    """Distinct (pool query, answer) pairs, so that a query served many
    times is compared once per distinct answer."""
    both = np.concatenate([np.asarray(pool_idx)[:, None],
                           np.asarray(ids)], axis=1)
    uniq = np.unique(both, axis=0)
    return uniq[:, 0], uniq[:, 1:]


def malformed(ids, n_rows: int):
    """Per answer: True where it is not k distinct valid row ids."""
    ids = np.asarray(ids)
    bad = np.any((ids < 0) | (ids >= n_rows), axis=1)
    s = np.sort(ids, axis=1)
    return bad | np.any(s[:, 1:] == s[:, :-1], axis=1)


def excess_per_answer(gt: GroundTruth, A_host, Q_host, pool_idx, ids):
    """Per answer, the gap by which its farthest row's exact distance lies
    beyond the reference's k-th, in units of that query's rounding scale;
    0 for an exact top-k."""
    if len(pool_idx) == 0:
        return np.zeros(0)
    d = exact_sqdist(A_host, Q_host[pool_idx], ids)
    gap = (d.max(axis=1) - gt.dist[pool_idx, gt.k - 1]) / gt.unit[pool_idx]
    return np.maximum(gap, 0.0)


def excess_units(gt: GroundTruth, A_host, Q_host, pool_idx, ids):
    """Widest gap over answers (``excess_per_answer``)."""
    gap = excess_per_answer(gt, A_host, Q_host, pool_idx, ids)
    return float(gap.max()) if gap.size else 0.0


def beyond(gap, tolerance: float) -> int:
    """Number of answers whose gap exceeds ``tolerance`` units."""
    return int(np.sum(np.asarray(gap) > tolerance))


def order_excess_units(gt: GroundTruth, A_host, Q_host, pool_idx, ids):
    """Widest inversion, over answers, between neighbouring entries of an
    answer that should be ascending by exact distance, in units."""
    if len(pool_idx) == 0:
        return 0.0
    d = exact_sqdist(A_host, Q_host[pool_idx], ids)
    inv = (d[:, :-1] - d[:, 1:]).max(axis=1) / gt.unit[pool_idx]
    return float(max(0.0, inv.max()))


def recall_at_k(gt: GroundTruth, pool_idx, ids):
    """Mean share of the reference's k nearest found in each answer."""
    ids = np.asarray(ids)
    want = gt.ids[np.asarray(pool_idx), :gt.k]
    hits = (ids[:, :, None] == want[:, None, :]).any(axis=2).sum(axis=1)
    return float(hits.mean() / gt.k) if len(hits) else float("nan")
