"""Production-scale shard_map deployments of the paper's kernels.

The VirtualCluster (reshape+vmap) path in each algorithm module reproduces
the 8-core PULP cluster; these wrappers run the SAME chunk-local code over a
real mesh axis — the paper's schemes scaled from 8 cores to 256/512 chips.
Tests prove bit-compatibility between the two paths.

Two layers live here (DESIGN.md §5):

  * single-query Fig. 5–8 ports (``*_shardmap``) — the literal paper
    pipelines over a mesh axis, kept for paper-fidelity tests;
  * the batched sharded fit/serve layer (``*_batch_shardmap`` /
    ``*_fit_shardmap``) behind ``Estimator.fit_sharded`` and the
    ``NonNeuralServeEngine`` mesh path.  Serve-side sharding is exact
    (per-row arithmetic is untouched by the partition: kNN merges
    per-shard fused-kernel candidates, the other four shard the query
    rows); fit-side K-Means/GNB/GMM merges are tolerance-bounded
    (per-shard partial sums psum in a different association than the
    single-device chunked accumulate).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.distribution import pad_to_multiple
from repro.core.gnb import GNBModel, _log_gaussian
from repro.core.knn import KNNModel, sq_distances
from repro.core.kmeans import KMeansState, _pairwise_sq_dist
from repro.core.topk import selection_topk_smallest

# padding rows for a sharded kNN reference set: large enough that padded
# rows can never enter a top-k (squared distance >= ~1e34), small enough
# that the ||p||^2 - 2 p.q + ||q||^2 expansion stays finite in fp32 (no
# inf - inf = NaN) up to d ~ 3000 features
_FAR = 1e17

# serving partition strategies (DESIGN.md §9): "reference" shards the
# model-side axis (kNN rows / centroids / classes / components / trees)
# and merges per-shard partials; "query" shards the batch rows against a
# replicated model — zero merge collective; "single" bypasses the mesh
STRATEGY_NAMES = ("single", "query", "reference")


def _check_divisible(what: str, n: int, mesh: Mesh, axis: str) -> int:
    """The paper-fidelity single-query ports statically partition one model
    axis across the mesh — an incompatible mesh must fail with the shape
    and mesh named, not an opaque AssertionError."""
    c = mesh.shape[axis]
    if n % c != 0:
        raise ValueError(
            f"{what}={n} does not divide across the {c}-shard mesh axis "
            f"{axis!r} (mesh shape {dict(mesh.shape)}); use a mesh whose "
            f"{axis!r} size divides {what}, or the batched "
            f"*_batch_shardmap serving layer which pads ragged shapes")
    return c


def knn_classify_shardmap(model: KNNModel, x, k: int, mesh: Mesh,
                          axis: str = "data"):
    """Fig. 6 over a mesh axis: OP1 local distances, OP2 local SS top-k,
    OP3 all-gather the c*k candidates and merge (every shard redundantly
    computes the merge — cheaper than a roundtrip at c*k elements).
    Each shard gathers only its k WINNERS' labels alongside the candidate
    (value, index) pairs, so the label traffic is c*k rows — not the whole
    N-row label array."""
    N = model.A.shape[0]
    c = _check_divisible("N", N, mesh, axis)
    chunk_len = N // c

    def local(a_chunk, labels_chunk, xq):
        e = sq_distances(a_chunk, xq)                       # OP1
        lv, li = selection_topk_smallest(e, k)              # OP2 (local SS)
        ll = labels_chunk[li]                               # local winners
        all_v = jax.lax.all_gather(lv, axis).reshape(-1)    # -> master merge
        all_l = jax.lax.all_gather(ll, axis).reshape(-1)    # c*k labels only
        gv, gi = selection_topk_smallest(all_v, k)          # OP3
        votes = jnp.zeros((model.n_class,), jnp.int32).at[
            all_l[gi]].add(1)
        return jnp.argmax(votes)

    # the all_gather + redundant merge is replicated by construction, but
    # the static varying-mesh-axes check can't see that
    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P(axis), P()), out_specs=P(),
                    check_vma=False)
    return fn(model.A, model.labels, x)


def kmeans_iteration_shardmap(A, centroids, mesh: Mesh, axis: str = "data"):
    """Fig. 7 over a mesh axis: OP1/OP2 local, OP3 local accumulate,
    OP4 psum combine (the global centroid update)."""
    N = A.shape[0]
    c = _check_divisible("N", N, mesh, axis)
    k = centroids.shape[0]

    def local(a_chunk, cent):
        e = _pairwise_sq_dist(a_chunk, cent)                # OP1
        ids = jnp.argmin(e, axis=1)                         # OP2
        onehot = jax.nn.one_hot(ids, k)                     # OP3 local
        sums = onehot.T @ a_chunk
        counts = jnp.sum(onehot, axis=0)
        sums = jax.lax.psum(sums, axis)                     # OP4 global
        counts = jax.lax.psum(counts, axis)
        new_c = jnp.where(counts[:, None] > 0,
                          sums / jnp.maximum(counts[:, None], 1.0), cent)
        return new_c, ids

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P()), out_specs=(P(), P(axis)))
    return fn(A, centroids)


def gnb_decision_shardmap(model: GNBModel, x, mesh: Mesh, axis: str = "data"):
    """Fig. 5 over a mesh axis: features sharded (vertical split); OP1 local
    partial log-lik sums; OP2 psum + prior; OP3 argmax."""
    d = model.mu.shape[1]
    c = _check_divisible("d", d, mesh, axis)

    def local(mu_k, var_k, x_k, log_prior):
        partial = jnp.sum(_log_gaussian(x_k[None, :], mu_k, var_k), axis=1)
        y = jax.lax.psum(partial, axis) + log_prior         # OP2
        return jnp.argmax(y), y                             # OP3

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(None, axis), P(None, axis), P(axis), P()),
                    out_specs=(P(), P()))
    return fn(model.mu, model.var, x, model.log_prior)


def matvec_shardmap(W, x, b, mesh: Mesh, axis: str = "data"):
    """Fig. 4 (GEMM-based OP1/OP2) over a mesh axis — re-export for API
    completeness; see distribution.two_phase_matvec_shardmap."""
    from repro.core.distribution import two_phase_matvec_shardmap
    return two_phase_matvec_shardmap(W, x, b, mesh, axis)


def forest_predict_shardmap(forest, x, mesh: Mesh, axis: str = "data"):
    """Fig. 8 over a mesh axis: trees statically sharded (Independent-Tasks),
    per-shard tree execution + local one-hot votes, psum vote combine (the
    paper's critical section becomes a reduction — DESIGN.md §2)."""
    from repro.core.random_forest import tree_predict

    T = forest.feature.shape[0]
    c = _check_divisible("T", T, mesh, axis)

    def local(feat, thr, left, right, xq):
        preds = jax.vmap(lambda f, t, l, r: tree_predict(f, t, l, r, xq))(
            feat, thr, left, right)                       # local trees
        votes = jnp.zeros((forest.n_class,), jnp.int32).at[preds].add(1)
        votes = jax.lax.psum(votes, axis)                 # vote combine
        return jnp.argmax(votes), votes

    # check_vma off: the while_loop carry in tree_predict starts unvarying
    # (node 0) and becomes shard-varying; the psum output is replicated by
    # construction
    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P(axis), P(axis), P(axis), P()),
                    out_specs=(P(), P()), check_vma=False)
    return fn(forest.feature, forest.threshold, forest.left, forest.right, x)


# ---------------------------------------------------------------------------
# Batched sharded serve — the op-level mesh arms behind kernels/dispatch.py
# ---------------------------------------------------------------------------


def _pad_rows(x, c: int, value=0.0):
    """Pad axis 0 to a multiple of the shard count; returns (padded, n)."""
    return pad_to_multiple(x, c, axis=0, value=value)


def _butterfly_topk_merge(lv, li, k: int, c: int, axis: str):
    """Hierarchical OP3: XOR-partner butterfly all-reduce of the per-shard
    (value, global-index) candidates — log2(c) rounds each moving k rows
    per query, instead of one all-gather of all c·kl candidates.  Bit-equal
    to the gather merge: every round keeps the k smallest by (value, global
    index), exactly the tie order a flat stable top-k over shard-major
    candidates resolves to (shard blocks are contiguous ascending row
    ranges, so position order == global index order)."""
    kl = lv.shape[1]
    if kl < k:
        # a shard holds at most chunk_len candidates; pad the merge slots
        # with +inf sentinels that can never displace a real candidate
        lv = jnp.pad(lv, ((0, 0), (0, k - kl)),
                     constant_values=jnp.inf)
        li = jnp.pad(li, ((0, 0), (0, k - kl)),
                     constant_values=jnp.iinfo(jnp.int32).max)
    for r in range(c.bit_length() - 1):
        stride = 1 << r
        perm = [(i, i ^ stride) for i in range(c)]
        pv = jax.lax.ppermute(lv, axis, perm)
        pi = jax.lax.ppermute(li, axis, perm)
        cv = jnp.concatenate([lv, pv], axis=1)
        ci = jnp.concatenate([li, pi], axis=1)
        order = jnp.lexsort((ci, cv), axis=-1)[:, :k]
        lv = jnp.take_along_axis(cv, order, axis=1)
        li = jnp.take_along_axis(ci, order, axis=1)
    return lv, li


def distance_topk_shardmap(a, qs, k: int, mesh: Mesh, axis: str = "data", *,
                           policy=None, path: Optional[str] = None,
                           merge: Optional[str] = None):
    """Fig. 6 OP1+OP2 over a sharded reference set, for a QUERY BATCH.

    ``a`` (N, d) is row-sharded; every shard runs the registry-selected
    fused distance→top-k kernel over its chunk for all Q queries, then the
    per-shard candidates merge (OP3) — the batched generalisation of
    ``knn_classify_shardmap``'s candidate merge.  ``merge`` picks the
    collective: ``"gather"`` all-gathers the c·kl candidates and runs one
    flat top-k; ``"tree"`` runs the hierarchical butterfly merge (k rows
    per query per round, log2(c) rounds); None selects tree on power-of-two
    meshes.  Both are bit-equal to the single-device
    ``dispatch.distance_topk``: per-row distances are untouched by the row
    partition and both merges preserve the global stable (smallest-index)
    tie order.  Returns (values (Q, k), indices (Q, k)), replicated.

    The reference set SHOULD be pre-padded to a multiple of the shard count
    with ``_FAR`` rows at fit/engine-construction time
    (``KNNEstimator.fit_sharded`` and the serve engine's param placement
    both do) — the in-call pad survives only as a fallback for direct
    callers, off the serving hot path.
    """
    from repro.kernels import dispatch

    quant = (path == "quant" if path is not None
             else ((policy is not None and policy.quantized)
                   or dispatch.env_override() == "quant"))
    if quant:
        raise NotImplementedError(
            "the reference-sharded kNN arm has no quant tier: the int8 "
            "lattice derives from the reference operand, which this "
            "partition chunks per shard (and any _FAR pad row saturates a "
            "per-shard lattice, zeroing every real feature) -- serve "
            "quantized with the query strategy (DESIGN.md section 9)")
    c = mesh.shape[axis]
    if a.shape[0] % c:
        a, _ = _pad_rows(a, c, value=_FAR)
    Np = a.shape[0]
    assert k <= Np, (k, Np)
    chunk_len = Np // c
    # a shard can contribute at most its whole chunk, so clamping the
    # local candidate count is lossless: c*kl >= N >= k candidates survive
    kl = min(k, chunk_len)
    if merge is None:
        merge = "tree" if c > 1 and (c & (c - 1)) == 0 else "gather"
    assert merge in ("gather", "tree"), merge
    if merge == "tree" and c & (c - 1):
        raise ValueError(
            f"merge='tree' needs a power-of-two shard count for the "
            f"butterfly exchange; mesh axis {axis!r} has {c} shards — "
            f"use merge='gather'")

    def local(a_chunk, q_all):
        core = jax.lax.axis_index(axis)
        lv, li = dispatch.distance_topk(a_chunk, q_all, kl, path=path,
                                        policy=policy)        # (Q, kl) local
        li = li + core * chunk_len
        if merge == "tree":
            return _butterfly_topk_merge(lv, li, k, c, axis)
        all_v = jax.lax.all_gather(lv, axis)                  # (c, Q, kl)
        all_i = jax.lax.all_gather(li, axis)
        cand_v = jnp.moveaxis(all_v, 0, 1).reshape(lv.shape[0], c * kl)
        cand_i = jnp.moveaxis(all_i, 0, 1).reshape(lv.shape[0], c * kl)
        gv, gp = jax.vmap(lambda row: selection_topk_smallest(row, k))(
            cand_v)                                           # OP3 merge
        return gv, jnp.take_along_axis(cand_i, gp, axis=1)

    fn = _shard_map(local, mesh=mesh, in_specs=(P(axis), P()),
                    out_specs=(P(), P()), check_vma=False)
    return fn(a, qs)


def distance_topk_query_shardmap(a, qs, k: int, mesh: Mesh,
                                 axis: str = "data", *, policy=None,
                                 path: Optional[str] = None):
    """Fig. 6 OP1+OP2 with the QUERY rows sharded and the reference set
    replicated on every shard (PULP-NN's weights-in-local-memory layout) —
    zero merge collective, the output re-assembles by construction.  Exact
    per row for every arm including int8 (the quant lattice derives from
    the replicated reference, never the batch).  Accepts ragged Q."""
    from repro.kernels import dispatch

    c = mesh.shape[axis]
    qp, Q = _pad_rows(qs, c)

    def local(q_chunk, a_r):
        return dispatch.distance_topk(a_r, q_chunk, k, path=path,
                                      policy=policy)

    fn = _row_sharded(local, mesh, axis, n_rep=1, n_out=2)
    vals, idx = fn(qp, a)
    return vals[:Q], idx[:Q]


def adc_topk_query_shardmap(qlut, codes, cand_ids, k: int, mesh: Mesh,
                            axis: str = "data", *, policy=None,
                            path: Optional[str] = None):
    """IVF-PQ ADC scoring (DESIGN.md §10) with the QUERY rows sharded:
    every operand — per-query LUTs, candidate codes, candidate ids — is
    query-row-indexed, so each shard runs the whole registry-dispatched
    op on its rows with zero merge collective.  Exact per row; accepts
    ragged Q (pad ids with -1 = the kernel's invalid sentinel)."""
    from repro.kernels import dispatch

    c = mesh.shape[axis]
    lp, Q = _pad_rows(qlut, c, value=0)
    cp, _ = _pad_rows(codes, c, value=0)
    ip, _ = _pad_rows(cand_ids, c, value=-1)

    def local(lut_chunk, code_chunk, id_chunk):
        return dispatch.adc_topk(lut_chunk, code_chunk, id_chunk, k,
                                 path=path, policy=policy)

    fn = _shard_map(local, mesh=mesh, in_specs=(P(axis),) * 3,
                    out_specs=(P(axis),) * 2, check_vma=False)
    vals, pos = fn(lp, cp, ip)
    return vals[:Q], pos[:Q]


def _row_sharded(local, mesh: Mesh, axis: str, n_rep: int, n_out: int):
    """shard_map helper: first arg row-sharded, ``n_rep`` replicated params,
    ``n_out`` row-sharded outputs."""
    return _shard_map(local, mesh=mesh,
                      in_specs=(P(axis),) + (P(),) * n_rep,
                      out_specs=(P(axis),) * n_out if n_out > 1 else P(axis),
                      check_vma=False)


def row_sharded_batch_fn(fn, mesh: Mesh, axis: str = "data"):
    """Lift ANY per-row-independent ``(params, X) -> (classes, aux)`` batch
    fn into a query-row-sharded mesh fn — the generic "query" strategy
    executor behind ``Estimator.predict_batch_sharded_fn``.  Params flow in
    as replicated closure constants, so the wrapped fn runs unchanged per
    shard; this is what lets the int8 tier serve sharded (the quantized
    predict fn's lattice derives from the params, never the batch rows).
    Accepts ragged batch sizes (rows pad to a shard multiple and the pad
    rows are sliced back off)."""
    c = mesh.shape[axis]

    def sharded_fn(params, X):
        Xp, B = _pad_rows(X, c)
        inner = _shard_map(lambda x: fn(params, x), mesh=mesh,
                           in_specs=(P(axis),),
                           out_specs=(P(axis), P(axis)), check_vma=False)
        cls, aux = inner(Xp)
        return cls[:B], aux[:B]

    return sharded_fn


def distance_argmin_shardmap(a, centroids, mesh: Mesh, axis: str = "data", *,
                             policy=None, path: Optional[str] = None):
    """Fig. 7 OP1+OP2 with the data rows sharded and centroids replicated.
    Per-row arithmetic is identical to the single-device kernel, so outputs
    are exact.  Returns (min sq-dist (N,), nearest id (N,)), row-sharded
    semantics hidden behind padding: accepts ragged N."""
    from repro.kernels import dispatch

    c = mesh.shape[axis]
    ap, N = _pad_rows(a, c)

    def local(a_chunk, cent):
        return dispatch.distance_argmin(a_chunk, cent, path=path,
                                        policy=policy)

    fn = _row_sharded(local, mesh, axis, n_rep=1, n_out=2)
    dist, ids = fn(ap, centroids)
    return dist[:N], ids[:N]


def distance_argmin_centroid_shardmap(a, centroids, mesh: Mesh,
                                      axis: str = "data", *, policy=None,
                                      path: Optional[str] = None):
    """Fig. 7 OP1+OP2 with the CENTROIDS sharded and every query row
    replicated — the model-partition dual of ``distance_argmin_shardmap``.
    The merge collective moves only the c per-shard minima per query (an
    argmin over shards), with ties resolved first-shard-wins — the
    smallest global centroid id, the single-device argmin rule — because
    centroid blocks are contiguous ascending ranges.  Assignments are
    exact away from exact distance ties, but the distance VALUES can
    drift ~1 ulp: the fused kernel's d-reduction schedule depends on the
    centroid-axis extent, which the chunking changes (the query strategy
    keeps the full operand and stays bit-exact).  Under the int8 arm the
    per-shard lattice derives from the LOCAL centroid chunk, so results
    are lattice-approximate there; strategy auto-selection never picks a
    model partition for quantized arms."""
    from repro.kernels import dispatch

    c = mesh.shape[axis]
    cp, _ = _pad_rows(centroids, c, value=_FAR)
    chunk_len = cp.shape[0] // c

    def local(cent_chunk, a_all):
        core = jax.lax.axis_index(axis)
        d_loc, id_loc = dispatch.distance_argmin(a_all, cent_chunk,
                                                 path=path, policy=policy)
        id_loc = id_loc + core * chunk_len
        all_d = jax.lax.all_gather(d_loc, axis)       # (c, B) minima only
        all_i = jax.lax.all_gather(id_loc, axis)
        w = jnp.argmin(all_d, axis=0)                 # first shard wins ties

        def take(m):
            return jnp.take_along_axis(m, w[None, :], axis=0)[0]

        return take(all_d), take(all_i)

    fn = _shard_map(local, mesh=mesh, in_specs=(P(axis), P()),
                    out_specs=(P(), P()), check_vma=False)
    return fn(cp, a)


def gnb_scores_shardmap(X, mu, var, log_prior, mesh: Mesh,
                        axis: str = "data", *, policy=None,
                        path: Optional[str] = None):
    """Fig. 5 OP1+OP2 for a query batch with the QUERY rows sharded (the
    single-query ``gnb_decision_shardmap`` shards features instead — that
    is the paper-literal vertical split; serving shards the independent
    axis).  Returns (B, C) joint log-likelihood, exact per row."""
    from repro.kernels import dispatch

    c = mesh.shape[axis]
    Xp, B = _pad_rows(X, c)

    def local(x_chunk, mu_r, var_r, lp):
        return dispatch.gnb_scores(x_chunk, mu_r, var_r, lp, path=path,
                                   policy=policy)

    fn = _row_sharded(local, mesh, axis, n_rep=3, n_out=1)
    return fn(Xp, mu, var, log_prior)[:B]


def gnb_scores_class_shardmap(X, mu, var, log_prior, mesh: Mesh,
                              axis: str = "data", *, policy=None,
                              path: Optional[str] = None):
    """Fig. 5 OP1+OP2 with the CLASSES sharded and the query rows
    replicated (the model-partition serving dual; the single-query port
    shards features instead).  Each class's score column is independent of
    the others, so the gathered (B, C) matrix matches the single-device op
    up to kernel-schedule tolerance (~1 ulp where the arm's reduction
    schedule depends on the class-axis extent; bit-exact argmax classes
    away from exact score ties — the query strategy stays bit-exact
    throughout); the int8 arm derives its lattice from the local class
    chunk (lattice-approximate — auto strategy never picks it quantized).
    Ragged class counts pad with unit-variance zero-mean dummies whose
    columns are sliced off."""
    from repro.kernels import dispatch

    c = mesh.shape[axis]
    mup, C = _pad_rows(mu, c)
    varp, _ = _pad_rows(var, c, value=1.0)    # var=1: finite pad scores
    lpp, _ = _pad_rows(log_prior, c)

    def local(mu_k, var_k, lp_k, x_all):
        s = dispatch.gnb_scores(x_all, mu_k, var_k, lp_k, path=path,
                                policy=policy)             # (B, C/c)
        all_s = jax.lax.all_gather(s, axis)                # (c, B, C/c)
        return jnp.moveaxis(all_s, 0, 1).reshape(x_all.shape[0], -1)

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P(axis), P(axis), P()),
                    out_specs=P(), check_vma=False)
    return fn(mup, varp, lpp, X)[:, :C]


def gmm_responsibilities_shardmap(mu, var, log_pi, X, mesh: Mesh,
                                  axis: str = "data", *, policy=None,
                                  path: Optional[str] = None,
                                  n_cores: int = 8):
    """GMM E-step with query rows sharded.  Returns (log_resp (B, k),
    None) — the mean log-likelihood slot of the single-device op is not
    computed here: the registry arm's mean is over ALL its chunk rows
    (padding included) so the global mean would need a second log-joint
    pass, and no sharded caller consumes it (serving discards it, the
    sharded fit uses ``_gmm_loglik_sharded``)."""
    from repro.kernels import dispatch

    c = mesh.shape[axis]
    Xp, B = _pad_rows(X, c)

    def local(x_chunk, mu_r, var_r, lp):
        lr, _ = dispatch.gmm_responsibilities(mu_r, var_r, lp, x_chunk,
                                              path=path, policy=policy,
                                              n_cores=n_cores)
        return lr

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P(), P(), P()),
                    out_specs=P(axis), check_vma=False)
    return fn(Xp, mu, var, log_pi)[:B], None


def _gmm_log_joint(x, mu, var, log_pi):
    from repro.core.gmm import _log_gauss
    return _log_gauss(x, mu, var) + log_pi[None]


def gmm_responsibilities_comp_shardmap(mu, var, log_pi, X, mesh: Mesh,
                                       axis: str = "data", *, policy=None,
                                       path: Optional[str] = None,
                                       n_cores: int = 8):
    """GMM E-step with the mixture COMPONENTS sharded: each shard computes
    the joint log-density columns of its component chunk — via the same
    arm the single-device dispatch would select at these shapes — the
    (B, k) joint is gathered, and the per-row logsumexp normalisation runs
    on the replicated matrix over exactly the real components.

    NOT bit-equal to ``gmm_e_step``: the fp joint is the GEMM-identity
    ``_log_gauss``, and chunking the component axis changes the matmul
    shape — XLA's accumulation order over d drifts at float tolerance
    (~1e-6 relative; argmax classes agree away from exact ties).  The
    query strategy keeps the full (k, d) operand per shard and stays
    bit-exact — which is why the cost model, not parity, chooses between
    them.  The int8 arm's lattice additionally derives from the local
    component chunk (lattice-approximate — auto never picks it quantized).
    Returns (log_resp (B, k), None) — the query arm's contract."""
    from repro.kernels import dispatch
    from repro.kernels import ops as _ops

    c = mesh.shape[axis]
    K = mu.shape[0]
    mup, _ = _pad_rows(mu, c)
    varp, _ = _pad_rows(var, c, value=1.0)
    lpp, _ = _pad_rows(log_pi, c, value=-jnp.inf)
    arm = dispatch.resolve("gmm", "responsibilities", path=path,
                           policy=policy, B=X.shape[0], d=X.shape[1],
                           k=K).name

    def joint_of(x, mu_k, var_k, lp_k):
        if arm == "blocked":
            return _ops.gnb_scores_batch(x, mu_k, var_k, lp_k)
        if arm == "quant":
            from repro.core import quantization as cq
            from repro.kernels import quantized as qk
            scale = qk.feature_scales(cq.gauss_absmax(
                mu_k.astype(jnp.float32), var_k.astype(jnp.float32)))
            quad, lin, const = cq.gauss_score_tables(mu_k, var_k, scale)
            return qk.affine_scores(qk.quantize_rows(x, scale), quad, lin,
                                    const + lp_k)
        return _gmm_log_joint(x, mu_k, var_k, lp_k)

    def local(mu_k, var_k, lp_k, x_all):
        j = joint_of(x_all, mu_k, var_k, lp_k)             # (B, k/c)
        all_j = jax.lax.all_gather(j, axis)                # (c, B, k/c)
        return jnp.moveaxis(all_j, 0, 1).reshape(x_all.shape[0], -1)

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P(axis), P(axis), P()),
                    out_specs=P(), check_vma=False)
    joint = fn(mup, varp, lpp, X)[:, :K]
    return joint - jax.nn.logsumexp(joint, axis=1, keepdims=True), None


def forest_votes_shardmap(forest, X, mesh: Mesh, axis: str = "data", *,
                          policy=None, path: Optional[str] = None,
                          n_cores: int = 8):
    """Fig. 8 for a query batch with the query rows sharded (the
    single-query ``forest_predict_shardmap`` shards trees — serving shards
    the independent batch axis; both are Independent-Tasks).  Returns
    (classes (B,), votes (B, n_class)), exact per row."""
    from repro.kernels import dispatch

    c = mesh.shape[axis]
    Xp, B = _pad_rows(X, c)

    def local(x_chunk, feat, thr, left, right):
        from repro.core.random_forest import Forest
        f = Forest(feature=feat, threshold=thr, left=left, right=right,
                   n_class=forest.n_class)
        return dispatch.forest_votes(f, x_chunk, path=path, policy=policy,
                                     n_cores=n_cores)

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P(), P(), P(), P()),
                    out_specs=(P(axis), P(axis)), check_vma=False)
    cls, votes = fn(Xp, forest.feature, forest.threshold, forest.left,
                    forest.right)
    return cls[:B], votes[:B]


def forest_votes_tree_shardmap(forest, X, mesh: Mesh, axis: str = "data", *,
                               policy=None, path: Optional[str] = None,
                               n_cores: int = 8):
    """Fig. 8 with the TREES sharded (the paper's literal Independent-Tasks
    axis) for a query batch: each shard runs its tree chunk over every
    query row and the integer vote histograms psum — exact (integer
    addition commutes), matching the query arm bit-for-bit on the fp arms.
    The int8 arm's threshold lattice derives from the local tree chunk
    (lattice-approximate — auto strategy never picks it quantized).
    Ragged tree counts pad with single-leaf sentinel trees voting one bin
    past the real classes, dropped before the argmax."""
    from repro.core.random_forest import Forest
    from repro.kernels import dispatch

    c = mesh.shape[axis]
    nc = forest.n_class
    T = forest.feature.shape[0]
    pad = (-T) % c
    feat, thr, left, right = (forest.feature, forest.threshold,
                              forest.left, forest.right)
    if pad:
        sent = jnp.zeros((pad, feat.shape[1]), feat.dtype)
        feat = jnp.concatenate([feat, sent.at[:, 0].set(-nc - 1)])
        thr = jnp.concatenate([thr, jnp.zeros((pad,) + thr.shape[1:],
                                              thr.dtype)])
        left = jnp.concatenate([left, jnp.zeros((pad,) + left.shape[1:],
                                                left.dtype)])
        right = jnp.concatenate([right, jnp.zeros((pad,) + right.shape[1:],
                                                  right.dtype)])

    def local(feat_c, thr_c, left_c, right_c, x_all):
        f = Forest(feature=feat_c, threshold=thr_c, left=left_c,
                   right=right_c, n_class=nc + 1)  # sentinel bin visible
        _, votes = dispatch.forest_votes(f, x_all, path=path, policy=policy,
                                         n_cores=n_cores)
        return jax.lax.psum(votes, axis)           # exact integer combine

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis),) * 4 + (P(),),
                    out_specs=P(), check_vma=False)
    votes = fn(feat, thr, left, right, X)[:, :nc]
    return jnp.argmax(votes, axis=1).astype(jnp.int32), votes


def knn_classify_batch_shardmap(model: KNNModel, X, k: int, mesh: Mesh,
                                axis: str = "data", *, policy=None,
                                path: Optional[str] = None,
                                strategy: str = "reference",
                                merge: Optional[str] = None):
    """Batched Fig. 6 over a mesh, by strategy.  ``"reference"``:
    shard-resident reference set, per-shard fused distance→top-k, candidate
    merge (gather or butterfly — see ``distance_topk_shardmap``), then the
    shared vote.  ``"query"``: query rows sharded against the replicated
    reference — zero merge collective, votes computed in-shard.  Both are
    bit-equal to ``knn_classify_batch``."""
    from repro.core.knn import _vote

    if strategy == "query":
        from repro.kernels import dispatch

        c = mesh.shape[axis]
        Xp, B = _pad_rows(X, c)

        def local(q_chunk, a_r, labels_r):
            _, nb = dispatch.distance_topk(a_r, q_chunk, k, path=path,
                                           policy=policy)
            cls = jax.vmap(
                lambda row: _vote(labels_r, row, model.n_class))(nb)
            return cls, nb

        fn = _row_sharded(local, mesh, axis, n_rep=2, n_out=2)
        cls, nb = fn(Xp, model.A, model.labels)
        return cls[:B], nb[:B]
    assert strategy == "reference", strategy
    _, nbr_idx = distance_topk_shardmap(model.A, X, k, mesh, axis,
                                        policy=policy, path=path,
                                        merge=merge)
    classes = jax.vmap(lambda nb: _vote(model.labels, nb, model.n_class))(
        nbr_idx)
    return classes, nbr_idx


# ---------------------------------------------------------------------------
# Sharded fit — per-shard partial statistics, psum'd global updates
# ---------------------------------------------------------------------------


def kmeans_iteration_sharded(A, centroids, valid, mesh: Mesh,
                             axis: str = "data"):
    """One Lloyd iteration with data rows sharded: OP1/OP2 per-shard fused
    distance→argmin, OP3 per-shard partial (sums, counts), OP4 psum — the
    Fig. 7 schedule verbatim with cores → shards.  ``valid`` masks padded
    rows out of the update.  Returns (new centroids (k, d) replicated,
    assignments row-sharded)."""
    from repro.kernels import dispatch

    k = centroids.shape[0]

    def local(a_chunk, v_chunk, cent):
        _, ids = dispatch.distance_argmin(a_chunk, cent)      # OP1+OP2
        onehot = jax.nn.one_hot(ids, k) * v_chunk[:, None]    # OP3 local
        sums = jax.lax.psum(onehot.T @ a_chunk, axis)         # OP4 global
        counts = jax.lax.psum(jnp.sum(onehot, axis=0), axis)
        new_c = jnp.where(counts[:, None] > 0,
                          sums / jnp.maximum(counts[:, None], 1.0), cent)
        return new_c, ids

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P(axis), P()),
                    out_specs=(P(), P(axis)), check_vma=False)
    return fn(A, valid, centroids)


def kmeans_fit_shardmap(A, k: int, mesh: Mesh, axis: str = "data", *,
                        threshold: float = 1e-4, max_iters: int = 100):
    """Sharded Lloyd fit: the ``kmeans_fit`` loop with every iteration's
    OP3/OP4 accumulate running as per-shard partial sums + psum.
    Tolerance-bounded vs the single-device fit (the psum associates the
    per-chunk sums differently).  Returns (KMeansState, assignments)."""
    A = jnp.asarray(A)
    c = mesh.shape[axis]
    Ap, N = _pad_rows(A, c)
    valid = (jnp.arange(Ap.shape[0]) < N).astype(A.dtype)

    step = jax.jit(functools.partial(kmeans_iteration_sharded,
                                     mesh=mesh, axis=axis))
    cent = A[:k]
    shift, n_iter = jnp.inf, 0
    while float(shift) > threshold and n_iter < max_iters:
        new_c, _ = step(Ap, cent, valid)
        shift = jnp.max(jnp.linalg.norm(new_c - cent, axis=1))
        cent, n_iter = new_c, n_iter + 1
    _, ids = step(Ap, cent, valid)
    state = KMeansState(centroids=cent, shift=jnp.asarray(shift),
                        n_iter=jnp.asarray(n_iter, jnp.int32))
    return state, ids[:N]


def gnb_fit_shardmap(X, y, n_class: int, mesh: Mesh, axis: str = "data", *,
                     var_smoothing: float = 1e-6) -> GNBModel:
    """Sharded GNB fit: each shard accumulates per-class moment partials
    (counts, Σx, Σx²) over its rows — the Fig. 7 OP3 accumulate applied to
    sufficient statistics — and one psum merges them into the M-step.
    Tolerance-bounded vs ``fit_gnb`` (sum association; the smoothing term
    uses E[x²]−E[x]² instead of jnp.var)."""
    X = jnp.asarray(X)
    y = jnp.asarray(y, jnp.int32)
    c = mesh.shape[axis]
    Xp, N = _pad_rows(X, c)
    yp, _ = _pad_rows(y, c)
    valid = (jnp.arange(Xp.shape[0]) < N).astype(X.dtype)

    def local(x_chunk, y_chunk, v_chunk):
        onehot = jax.nn.one_hot(y_chunk, n_class) * v_chunk[:, None]
        counts = jax.lax.psum(jnp.sum(onehot, axis=0), axis)       # (C,)
        s1 = jax.lax.psum(onehot.T @ x_chunk, axis)                # (C, d)
        s2 = jax.lax.psum(onehot.T @ (x_chunk * x_chunk), axis)
        # global per-feature moments for the shared smoothing scale
        f1 = jax.lax.psum(jnp.sum(x_chunk * v_chunk[:, None], axis=0), axis)
        f2 = jax.lax.psum(
            jnp.sum(x_chunk * x_chunk * v_chunk[:, None], axis=0), axis)
        mu = s1 / counts[:, None]
        var = s2 / counts[:, None] - mu ** 2
        gvar = f2 / N - (f1 / N) ** 2
        var = var + var_smoothing * jnp.max(gvar)
        log_prior = jnp.log(counts / N)
        return mu, var, log_prior

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P(axis), P(axis)),
                    out_specs=(P(), P(), P()), check_vma=False)
    mu, var, log_prior = fn(Xp, yp, valid)
    return GNBModel(mu=mu, var=var, log_prior=log_prior)


def _gmm_em_iteration_sharded(A, valid, mu, var, log_pi, N: int,
                              mesh: Mesh, axis: str = "data", *,
                              var_floor: float = 1e-6):
    """One sharded EM iteration: per-shard E-step (rows independent), then
    the M-step's soft-moment accumulate as per-shard partials + psum
    (Fig. 7 OP3/OP4 with responsibilities).  Returns new (mu, var, log_pi),
    replicated."""

    def local(a_chunk, v_chunk, mu_r, var_r, lp):
        joint = _gmm_log_joint(a_chunk, mu_r, var_r, lp)
        lr = joint - jax.nn.logsumexp(joint, axis=1, keepdims=True)
        r = jnp.exp(lr) * v_chunk[:, None]
        nk = jax.lax.psum(jnp.sum(r, axis=0), axis)                 # (k,)
        s1 = jax.lax.psum(r.T @ a_chunk, axis)                      # (k, d)
        s2 = jax.lax.psum(r.T @ (a_chunk * a_chunk), axis)
        safe = jnp.maximum(nk[:, None], 1e-9)
        mu2 = s1 / safe
        var2 = jnp.maximum(s2 / safe - mu2 * mu2, var_floor)
        log_pi2 = jnp.log(jnp.maximum(nk / N, 1e-12))
        return mu2, var2, log_pi2

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P(axis), P(), P(), P()),
                    out_specs=(P(), P(), P()), check_vma=False)
    return fn(A, valid, mu, var, log_pi)


def _gmm_loglik_sharded(A, valid, mu, var, log_pi, N: int, mesh: Mesh,
                        axis: str = "data"):
    """Mean data log-likelihood over the real rows, psum'd."""

    def local(a_chunk, v_chunk, mu_r, var_r, lp):
        ll = jax.nn.logsumexp(_gmm_log_joint(a_chunk, mu_r, var_r, lp),
                              axis=1)
        return jax.lax.psum(jnp.sum(ll * v_chunk), axis)

    fn = _shard_map(local, mesh=mesh,
                    in_specs=(P(axis), P(axis), P(), P(), P()),
                    out_specs=P(), check_vma=False)
    return fn(A, valid, mu, var, log_pi) / N


def gmm_fit_shardmap(A, k: int, mesh: Mesh, axis: str = "data", *,
                     max_iters: int = 100, tol: float = 1e-4):
    """Sharded EM fit mirroring ``gmm_fit``'s loop: warm-up iteration, then
    iterate while the mean log-likelihood improves by > tol.  E-step rows
    are exact; the M-step moment psum is tolerance-bounded.  Returns
    (GMMState, responsibilities (N, k))."""
    from repro.core.gmm import GMMState

    A = jnp.asarray(A)
    c = mesh.shape[axis]
    Ap, N = _pad_rows(A, c)
    valid = (jnp.arange(Ap.shape[0]) < N).astype(A.dtype)
    d = A.shape[1]

    em = jax.jit(functools.partial(_gmm_em_iteration_sharded, N=N,
                                   mesh=mesh, axis=axis))
    ll_of = jax.jit(functools.partial(_gmm_loglik_sharded, N=N,
                                      mesh=mesh, axis=axis))

    mu, var = A[:k], jnp.ones((k, d), A.dtype)
    log_pi = jnp.full((k,), -math.log(k), A.dtype)
    prev_ll, ll = -jnp.inf, -jnp.inf
    n_iter = 0
    while n_iter < max_iters:
        mu, var, log_pi = em(Ap, valid, mu, var, log_pi)
        prev_ll, ll = ll, ll_of(Ap, valid, mu, var, log_pi)
        n_iter += 1
        # mirror gmm_fit's cond: stop once the improvement is <= tol (the
        # warm-up iteration always runs; NaN improvement also stops)
        if n_iter > 1 and not (float(ll - prev_ll) > tol):
            break
    lr, _ = gmm_responsibilities_shardmap(mu, var, log_pi, A, mesh, axis)
    state = GMMState(mu=mu, var=var, log_pi=log_pi,
                     log_lik=jnp.asarray(ll),
                     n_iter=jnp.asarray(n_iter, jnp.int32))
    return state, jnp.exp(lr)
