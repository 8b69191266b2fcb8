"""Approximate kNN: IVF coarse quantizer + int8 product quantization
(DESIGN.md §10).

Exact kNN is the one estimator whose serve cost grows linearly with the
reference set — the paper's per-device setting caps N at what fits in
L1/VMEM (§5.3).  Production kNN over million-row reference sets is an
ANN index, and both halves already live in this repo:

  * the IVF coarse quantizer IS K-Means (``core/kmeans.py``): ``fit``
    clusters the reference rows into ``n_cells`` cells via the
    registry-dispatched Lloyd iteration, then builds per-cell inverted
    lists padded to one power-of-two capacity (a dense (C, cap) int32
    array, -1 padded — ragged lists with a rectangular layout, the same
    move the serving buckets make for batch sizes);
  * the scorer is product quantization: features split into ``m``
    subspaces, a small K-Means codebook per subspace, every reference
    row stored as ``m`` int8 codes.  Serving runs asymmetric distance
    computation (ADC): the query builds one integer LUT against the
    codebooks (``build_query_luts``) and every candidate costs ``m``
    table lookups (``kernels/ann.py``).

``predict_batch`` probes each query's ``nprobe`` nearest cells with the
SAME fused ``distance_topk`` kernel exact kNN serves with, gathers the
probed cells' members, and scores them with the ADC kernel — so the
whole estimator rides the unchanged dispatch/bucket/scheduler path and
``nprobe`` becomes the recall-vs-latency knob the repo lacked
(benchmarks/ann_sweep.py).

Quantization note: PQ codes are already the int8 representation — the
``int8`` PrecisionPolicy tier (re-quantizing fitted params onto a
lattice) has no meaning here and the constructor refuses it.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kmeans as _kmeans
from repro.kernels import dispatch

# codebook/cell training subsample cap: Lloyd over the full million-row
# reference set is fit-time waste (the codebooks only need the data
# distribution); assignment below always covers every row
_TRAIN_CAP = 1 << 16


class ANNParams(NamedTuple):
    centroids: jax.Array   # (C, d) IVF cell centroids (policy dtype)
    cell_ids: jax.Array    # (C, cap) int32 inverted lists, -1 padded
    codebooks: jax.Array   # (m, n_codes, dsub) PQ codebooks (policy dtype)
    codes: jax.Array       # (N, m) int8 PQ codes, stored code - 128
    refs: jax.Array        # (N, d) raw rows (policy dtype), refine stage
    labels: jax.Array      # (N,) int32
    n_class: int


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def build_query_luts(X, codebooks):
    """Queries (B, d) + codebooks (m, n_codes, dsub) -> per-query integer
    ADC LUTs (B, m * n_codes) int32 on a shared 0..255 step.

    The fp32 table ``lut[b, j, c] = ||x_b_j - codebook[j, c]||^2`` maps
    onto integers by subtracting each subspace's per-query minimum (a
    constant shift per query — rank-irrelevant for candidate ordering)
    and dividing by ONE per-query step (the largest subspace range /
    255).  Sharing the step across subspaces keeps the m-term candidate
    SUM rank-preserving; making it per-query keeps every row of the
    batch independent, so ``predict == predict_batch`` stays exact.
    """
    m, n_codes, dsub = codebooks.shape
    B, d = X.shape
    Xf = jnp.asarray(X, jnp.float32)
    if d < m * dsub:                       # zero-pad to the PQ width
        Xf = jnp.pad(Xf, ((0, 0), (0, m * dsub - d)))
    q = Xf.reshape(B, m, 1, dsub)
    diff = q - codebooks.astype(jnp.float32)[None]     # (B, m, n_codes, dsub)
    lut = jnp.sum(diff * diff, axis=3)                 # (B, m, n_codes)
    lut0 = lut - jnp.min(lut, axis=2, keepdims=True)
    step = jnp.max(lut0, axis=(1, 2), keepdims=True) / 255.0
    step = jnp.maximum(step, 1e-12)
    q8 = jnp.clip(jnp.round(lut0 / step), 0, 255).astype(jnp.int32)
    return q8.reshape(B, m * n_codes)


def _masked_vote(labels, nbr, n_class: int):
    """kNN majority vote over possibly-invalid (-1) neighbour ids: invalid
    slots vote into a discarded overflow bin, ties -> lowest class id
    (the same argmax rule as core/knn.py::_vote)."""
    lab = jnp.where(nbr >= 0, labels[jnp.maximum(nbr, 0)], n_class)
    votes = jnp.zeros((n_class + 1,), jnp.int32).at[lab].add(1)
    return jnp.argmax(votes[:n_class])


def fit_ivf_pq(X, y, *, n_cells: int, m: int, n_codes: int,
               n_class: int, max_iters: int = 25, cast=None) -> ANNParams:
    """Train the IVF index + PQ codebooks and encode every reference row.

    K-Means (cells and per-subspace codebooks) trains on at most
    ``_TRAIN_CAP`` leading rows — deterministic, and the codebooks only
    need the distribution — but cell assignment and PQ encoding cover
    the full reference set through the registry-dispatched
    ``distance_argmin``.
    """
    cast = cast or (lambda a: a)
    Xf = jnp.asarray(np.asarray(X, np.float32))
    N, d = Xf.shape
    train = Xf[:min(N, _TRAIN_CAP)]

    # IVF cells: Lloyd over the (sub)sampled rows, assign every row
    state, _ = _kmeans.kmeans_fit(train, n_cells, max_iters=max_iters)
    _, cell_of = dispatch.distance_argmin(Xf, state.centroids)
    cell_np = np.asarray(cell_of)

    # inverted lists: one power-of-two capacity, -1 padded; members stay
    # in ascending row order (stable sort) so every downstream tie rule
    # sees candidates in global-id order
    counts = np.bincount(cell_np, minlength=n_cells)
    cap = _pow2_at_least(max(int(counts.max()), 1))
    cell_ids = np.full((n_cells, cap), -1, np.int32)
    order = np.argsort(cell_np, kind="stable")
    offsets = np.zeros(n_cells, np.int64)
    offsets[1:] = np.cumsum(counts)[:-1]
    for c in range(n_cells):
        members = order[offsets[c]:offsets[c] + counts[c]]
        cell_ids[c, :counts[c]] = members

    # PQ: d zero-padded to m*dsub, one codebook per subspace, int8 codes
    dsub = -(-d // m)
    Xp = jnp.pad(Xf, ((0, 0), (0, m * dsub - d)))
    books, codes = [], []
    for j in range(m):
        sub = Xp[:, j * dsub:(j + 1) * dsub]
        st, _ = _kmeans.kmeans_fit(sub[:min(N, _TRAIN_CAP)], n_codes,
                                   max_iters=max_iters)
        _, code_j = dispatch.distance_argmin(sub, st.centroids)
        books.append(st.centroids)
        codes.append(code_j)
    codebooks = jnp.stack(books)                       # (m, n_codes, dsub)
    codes8 = (jnp.stack(codes, axis=1) - 128).astype(jnp.int8)   # (N, m)

    return ANNParams(centroids=cast(state.centroids),
                     cell_ids=jnp.asarray(cell_ids),
                     codebooks=cast(codebooks), codes=codes8,
                     refs=cast(Xf), labels=jnp.asarray(y, jnp.int32),
                     n_class=n_class)


def probe_candidates(params: ANNParams, X, k: int, nprobe: int, *,
                     refine: int = 0, policy=None,
                     path: Optional[str] = None):
    """The ADC stage's inputs for a query batch: probe -> gather inverted
    lists.  Returns (query LUTs (B, m*n_codes) int32, candidate codes
    (B, L, m) int8, candidate ids (B, L) int32 with -1 padding, and
    ``want``, the number of ADC survivors the classify keeps)."""
    B = X.shape[0]
    C = params.centroids.shape[0]
    p = min(nprobe, C)

    # coarse probe: the SAME fused distance->top-k kernel exact kNN uses,
    # over the C cell centroids instead of the N reference rows
    _, cells = dispatch.distance_topk(params.centroids, X, p,
                                      policy=policy, path=path)   # (B, p)
    cand = params.cell_ids[cells].reshape(B, p * params.cell_ids.shape[1])
    want = max(k, min(refine, cand.shape[1]) if refine > 0 else 0)
    if cand.shape[1] < want:               # degenerate tiny indexes
        cand = jnp.pad(cand, ((0, 0), (0, want - cand.shape[1])),
                       constant_values=-1)

    qlut = build_query_luts(X, params.codebooks)       # (B, m*n_codes)
    cand_codes = params.codes[jnp.maximum(cand, 0)]    # (B, L, m) int8
    return qlut, cand_codes, cand, want


def ann_classify_batch(params: ANNParams, X, k: int, nprobe: int, *,
                       refine: int = 0, policy=None,
                       path: Optional[str] = None):
    """Batched IVF-PQ classify: probe -> gather inverted lists -> ADC
    score [-> exact refine] -> vote.  Returns (classes (B,), neighbour
    ids (B, k) int32, -1 where a query's probed cells held fewer than k
    members).

    ``refine > 0`` keeps the ADC scan as the candidate filter but
    re-ranks its top ``refine`` survivors with exact fp32 distances (the
    FAISS refine-flat idiom): the int8 LUT resolves which candidates are
    NEAR, while the last few rank swaps among near-equidistant rows sit
    below its 255-step resolution — the short exact pass touches only
    ``refine`` raw rows per query, so the N-proportional work stays on
    the codes (DESIGN.md §10)."""
    qlut, cand_codes, cand, want = probe_candidates(
        params, X, k, nprobe, refine=refine, policy=policy, path=path)
    _, pos = dispatch.adc_topk(qlut, cand_codes, cand, want,
                               policy=policy, path=path)       # (B, want)
    nbr = jnp.take_along_axis(cand, pos, axis=1)       # global ids
    if want > k:
        # exact re-rank of the ADC survivors; per-row arithmetic, so
        # predict == predict_batch and the query partition stay exact.
        # Ties break toward the ADC rank order (top_k keeps the first).
        rows = params.refs[jnp.maximum(nbr, 0)].astype(jnp.float32)
        diff = rows - jnp.asarray(X, jnp.float32)[:, None, :]
        dist = jnp.sum(diff * diff, axis=2)            # (B, want)
        dist = jnp.where(nbr < 0, jnp.inf, dist)
        _, sel = jax.lax.top_k(-dist, k)
        nbr = jnp.take_along_axis(nbr, sel, axis=1)    # (B, k)
    classes = jax.vmap(
        lambda nb: _masked_vote(params.labels, nb, params.n_class))(nbr)
    return classes, nbr
