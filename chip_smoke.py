#!/usr/bin/env python3
"""Smoke run of the Non-Neural serve path on a TPU.

One process drives the normal serve calls — ``make_fitted`` ->
``NonNeuralServeEngine.warmup`` -> ``classify``, the calls
``python -m repro.launch.serve --algo ...`` makes — at the shapes of
ann-benchmarks SIFT-128-euclidean: 1,000,000 x 128 fp32 base rows and
10,000 queries, generated from ``--seed`` by ``class_blobs`` (10 classes).

One chip (default): exact kNN (k=10), IVF1024,PQ16 (nprobe 8, refine 100)
over the same index, then K-Means, GNB and GMM on the same rows and RF on
a reduced subset.  Every phase prints the dispatch arm of its ops, checks
that the compiled bucket executor holds the Pallas kernel
(``tpu_custom_call``) wherever a Pallas arm serves, and checks its answers
against a plain ``jax.numpy`` reference run under
``jax.default_matmul_precision("highest")``.

``--chips 4``: only the sharded exact-kNN phase — the index row-sharded
over a 4-device mesh, served with the reference partition (per-shard
fused kernel + butterfly merge) and with the query partition, both
compared with the single-chip answer.

Times printed here are smoke timings (compile, one warm pass), not
benchmark numbers.  The last line of standard output is one JSON object
naming the device; any failed check raises, and without a TPU the run
stops before it prints anything.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

N_BASE = 1_000_000          # SIFT-1M base set
N_QUERY = 10_000            # SIFT-1M query set
DIM = 128
N_CLASS = 10
K = 10
BATCH = 256                 # largest serving bucket
RF_ROWS = 4096              # pure-Python CART fit: about 40 s on one core
# a row is a tie when its k-th and (k+1)-th exact distances lie closer
# than this many fp32 unit roundoffs of (|q| + max|a|)^2 — the rounding
# scale of the kernels' ||a||^2 - 2 a.q + ||q||^2 expansion
TIE_ULPS = 16
_U = 2.0 ** -24


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX finds no TPU (platform "
                         f"{devices[0].platform!r}); this run needs the chip")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX finds {len(devices)}")
    return devices


# ------------------------------------------------------------ references


def exact_knn(A, Q, k: int, *, chunk: int = BATCH, extra: int = 4):
    """Plain brute force, independent of ``kernels/``: top-(k+extra) by the
    matmul expansion at highest precision (two-stage ``lax.top_k``), then
    re-ranked by direct differences.  Returns numpy (ids, exact squared
    distances), each (n_query, k+1), ascending by (distance, id)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = A.shape[0]
    groups = min(1024, max(1, n // 64))      # two-stage top-k width
    width = -(-n // groups)
    an = jnp.sum(A * A, axis=1)
    m = k + extra

    @jax.jit
    def one(q, A, an):      # A as an argument: a closure would embed it
        d = an[None, :] - 2.0 * (q @ A.T) + jnp.sum(q * q, 1)[:, None]
        d = jnp.pad(d, ((0, 0), (0, groups * width - n)),
                    constant_values=jnp.inf).reshape(q.shape[0], groups,
                                                     width)
        v, i = jax.lax.top_k(-d, m)                          # per group
        i = i + (jnp.arange(groups) * width)[None, :, None]
        _, j = jax.lax.top_k(v.reshape(q.shape[0], -1), m)
        cand = jnp.take_along_axis(i.reshape(q.shape[0], -1), j, axis=1)
        diff = A[cand] - q[:, None, :]
        exact = jnp.sum(diff * diff, axis=2)
        order = jnp.lexsort((cand, exact), axis=1)[:, :k + 1]
        return (jnp.take_along_axis(cand, order, axis=1),
                jnp.take_along_axis(exact, order, axis=1))

    ids, dist = [], []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, Q.shape[0], chunk):
            i, d = one(Q[lo:lo + chunk], A, an)
            ids.append(np.asarray(i))
            dist.append(np.asarray(d))
    return np.concatenate(ids), np.concatenate(dist)


def tie_rows(dist, Q, A, k: int):
    """Rows whose k-th and (k+1)-th exact distances tie within the fp32
    rounding scale of the expansion (see TIE_ULPS)."""
    import numpy as np

    r = float(np.sqrt(np.max(np.sum(np.square(A, dtype=np.float64), 1))))
    qn = np.sqrt(np.sum(np.square(Q, dtype=np.float64), 1))
    tol = TIE_ULPS * _U * (qn + r) ** 2
    return (dist[:, k] - dist[:, k - 1]) <= tol


def vote(labels, ids, n_class: int):
    """Majority vote, ties to the lowest class (core/knn.py's rule)."""
    import numpy as np

    counts = np.zeros((ids.shape[0], n_class), np.int64)
    np.add.at(counts, (np.arange(ids.shape[0])[:, None], labels[ids]), 1)
    return np.argmax(counts, axis=1)


def argmax_with_margin(scores):
    """(top index, gap between the two best scores) per row."""
    import numpy as np

    part = np.sort(scores, axis=1)
    return np.argmax(scores, axis=1), part[:, -1] - part[:, -2]


def forest_reference(forest, X):
    """Plain numpy traversal of the fitted forest: per-tree leaf class,
    votes, argmax with lowest-class ties."""
    import numpy as np

    feat = np.asarray(forest.feature)
    thr = np.asarray(forest.threshold)
    left, right = np.asarray(forest.left), np.asarray(forest.right)
    rows = np.arange(X.shape[0])
    votes = np.zeros((X.shape[0], forest.n_class), np.int64)
    for t in range(feat.shape[0]):
        node = np.zeros(X.shape[0], np.int64)
        for _ in range(feat.shape[1]):
            f = feat[t, node]
            inner = f >= 0
            if not inner.any():
                break
            go_left = X[rows, np.maximum(f, 0)] <= thr[t, node]
            node = np.where(inner, np.where(go_left, left[t, node],
                                            right[t, node]), node)
        np.add.at(votes, (rows, -feat[t, node] - 1), 1)
    return np.argmax(votes, axis=1)


# ------------------------------------------------------------ serving


def serve(name: str, est, Q, arms: dict, *, mesh=None, strategy=None,
          pallas: bool = True):
    """Warm, serve once, and prove the kernels ran: returns the result."""
    import jax

    from repro.serving import NonNeuralServeEngine

    engine = NonNeuralServeEngine(est, max_batch=BATCH, mesh=mesh,
                                  strategy=strategy)
    log(f"[{name}] arms: " + ", ".join(f"{op}={arm}"
                                       for op, arm in arms.items()))
    t0 = time.perf_counter()
    n_buckets = engine.warmup(Q)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = engine.classify(Q)
    jax.block_until_ready((res.classes, res.aux))
    warm_s = time.perf_counter() - t0
    for bucket in sorted(engine.warmed):
        n_kernels = engine.compiled_text(bucket, Q.shape[1]).count(
            'custom_call_target="tpu_custom_call"')
        log(f"[{name}] bucket {bucket}: {n_kernels} tpu_custom_call in "
            f"the compiled executor"
            + (f" (strategy {engine.bucket_strategies.get(bucket)})"
               if mesh is not None else ""))
        if pallas:
            assert n_kernels >= 1, f"{name}: bucket {bucket} runs no kernel"
    log(f"[{name}] smoke timing: compile+warmup {compile_s:.3f} s "
        f"({n_buckets} buckets), one warm classify of {Q.shape[0]} queries "
        f"{warm_s:.3f} s, launches {dict(engine.bucket_launches)}")
    return engine, res


def knn_arms(est) -> dict:
    from repro.kernels import dispatch

    kw = dispatch.hot_shape_kw("knn", est.serve_cost_shape(), BATCH)
    return {"distance_topk": dispatch.resolve("knn", "distance_topk",
                                              **kw).name}


def check_knn(name: str, ids, ref_ids, ref_dist, Qn, An, k: int):
    import numpy as np

    ties = tie_rows(ref_dist, Qn, An, k)
    same = np.all(np.sort(ids, 1) == np.sort(ref_ids[:, :k], 1), axis=1)
    bad = int(np.sum(~same & ~ties))
    log(f"[{name}] {ids.shape[0]} queries: neighbour ids equal to the "
        f"plain reference on {int(np.sum(same & ~ties))} of "
        f"{int(np.sum(~ties))} non-tie rows ({bad} differ); "
        f"{int(np.sum(ties))} tie rows, {int(np.sum(same & ties))} of them "
        f"equal; same order on {int(np.sum(np.all(ids == ref_ids[:, :k], 1)))}"
        f" rows")
    assert bad == 0, f"{name}: {bad} non-tie rows differ from the reference"
    return ties


# ------------------------------------------------------------ phases


def make_data(seed: int):
    import jax.numpy as jnp

    from repro.data.datasets import class_blobs

    t0 = time.perf_counter()
    X, y = class_blobs(n=N_BASE + N_QUERY, d=DIM, n_class=N_CLASS,
                       seed=seed)
    Xb, yb, Xq = X[:N_BASE], y[:N_BASE], X[N_BASE:]
    Qd = jnp.asarray(Xq)
    log(f"[data] class_blobs seed={seed}: base {Xb.shape} fp32 "
        f"({Xb.nbytes / 2**20:.0f} MiB), queries {Xq.shape}, {N_CLASS} "
        f"classes, {time.perf_counter() - t0:.3f} s")
    return Xb, yb, Xq, Qd


def phase_knn(Xb, yb, Xq, Qd):
    import numpy as np

    from repro.core.estimator import make_fitted

    est = make_fitted("knn", Xb, yb, n_groups=N_CLASS, k=K)
    arms = knn_arms(est)
    assert arms["distance_topk"] == "fused", arms
    _, res = serve("knn", est, Qd, arms)
    t0 = time.perf_counter()
    ref_ids, ref_dist = exact_knn(est.params.A, Qd, K)
    log(f"[knn] plain reference {time.perf_counter() - t0:.3f} s")
    ties = check_knn("knn", np.asarray(res.aux), ref_ids, ref_dist, Xq, Xb,
                     K)
    cls = np.asarray(res.classes)
    assert np.all(cls[~ties] == vote(yb, ref_ids[:, :K], N_CLASS)[~ties]), \
        "knn: labels differ from the reference vote"
    log("[knn] labels equal to the reference vote on every non-tie row")
    return ref_ids


def phase_ann(Xb, yb, Qd, ref_ids):
    import numpy as np

    from repro.core.ann import probe_candidates
    from repro.core.estimator import make_fitted
    from repro.kernels import dispatch

    t0 = time.perf_counter()
    est = make_fitted("ann", Xb, yb, n_groups=N_CLASS, k=K, n_cells=1024,
                      pq_m=16, nprobe=8, refine=100)
    p = est.params
    log(f"[ann] IVF{p.cell_ids.shape[0]},PQ{p.codebooks.shape[0]} fit "
        f"{time.perf_counter() - t0:.3f} s: inverted-list capacity "
        f"{p.cell_ids.shape[1]}, {p.codebooks.shape[1]} codes per subspace")
    shape = est.serve_cost_shape()
    arms = {
        "probe distance_topk": dispatch.resolve(
            "knn", "distance_topk", N=shape["C"], d=DIM, Q=BATCH,
            k=est.nprobe).name,
        "adc_topk": dispatch.resolve(
            "ann", "adc_topk",
            **dispatch.hot_shape_kw("ann", shape, BATCH)).name,
    }
    assert arms["adc_topk"] == "fused", arms
    _, res = serve("ann", est, Qd, arms)
    ids = np.asarray(res.aux)
    hits = [len(set(a[a >= 0]) & set(b[:K])) for a, b in zip(ids, ref_ids)]
    log(f"[ann] nprobe {est.nprobe}, refine {est.refine}: recall@{K} "
        f"{np.sum(hits) / (K * len(hits)):.4f} against the exact reference")
    qlut, codes, cand, want = probe_candidates(p, Qd[:BATCH], K, est.nprobe,
                                               refine=est.refine)
    fv, fp = dispatch.adc_topk(qlut, codes, cand, want, path="fused")
    rv, rp = dispatch.adc_topk(qlut, codes, cand, want, path="ref")
    assert np.array_equal(np.asarray(fv), np.asarray(rv)) and \
        np.array_equal(np.asarray(fp), np.asarray(rp)), \
        "ann: compiled adc_topk differs from its jnp oracle"
    log(f"[ann] compiled adc_topk on {BATCH} queries x {cand.shape[1]} "
        f"candidates, top-{want}: bit-equal to ref_adc_topk")


def phase_kmeans(Xb, Qd):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.estimator import make_fitted
    from repro.kernels import dispatch, ops

    t0 = time.perf_counter()
    est = make_fitted("kmeans", Xb, n_groups=N_CLASS)
    C = est.params.centroids
    log(f"[kmeans] fit on {Xb.shape[0]} rows, K={C.shape[0]}: "
        f"{int(est.params.n_iter)} iterations, "
        f"{time.perf_counter() - t0:.3f} s")
    arm = dispatch.resolve("kmeans", "distance_argmin", N=BATCH, d=DIM,
                           K=N_CLASS).name
    _, res = serve("kmeans", est, Qd, {"distance_argmin": arm},
                   pallas=arm != "ref")
    with jax.default_matmul_precision("highest"):
        d = jnp.sum((Qd[:, None, :] - C[None]) ** 2, axis=2)
    best, gap = argmax_with_margin(-np.asarray(d))
    check_labels("kmeans", np.asarray(res.classes), best, gap,
                 np.asarray(d).max(1))
    A = jax.ShapeDtypeStruct(Xb.shape, jnp.float32)
    mem = ops.distance_argmin.lower(A, C).compile().memory_analysis()
    log(f"[kmeans] fit-time distance_argmin over {Xb.shape[0]} rows: "
        f"outputs hold {8 * Xb.shape[0]} bytes, the compiled call keeps "
        f"{mem.temp_size_in_bytes} temp bytes — the (N, 1) outputs padded "
        f"to 128 lanes")


def check_labels(name: str, got, want, gap, scale):
    """Labels must match wherever the reference's best-vs-second margin
    exceeds fp32 rounding of the scores."""
    import numpy as np

    ties = gap <= TIE_ULPS * _U * np.abs(scale)
    bad = int(np.sum((got != want) & ~ties))
    log(f"[{name}] labels equal to the plain reference on "
        f"{int(np.sum((got == want) & ~ties))} of {int(np.sum(~ties))} "
        f"non-tie rows; {int(np.sum(ties))} tie rows")
    assert bad == 0, f"{name}: {bad} non-tie labels differ"


def gauss_scores(Qd, mu, var, log_w):
    import jax
    import jax.numpy as jnp
    import numpy as np

    with jax.default_matmul_precision("highest"):
        s = -0.5 * jnp.sum((Qd[:, None, :] - mu[None]) ** 2 / var[None]
                           + jnp.log(var)[None] + np.log(2 * np.pi), axis=2)
    return np.asarray(s + log_w[None, :])


def phase_gnb(Xb, yb, Qd):
    import numpy as np

    from repro.core.estimator import make_fitted
    from repro.kernels import dispatch

    est = make_fitted("gnb", Xb, yb, n_groups=N_CLASS)
    arm = dispatch.resolve("gnb", "scores", B=BATCH, d=DIM, C=N_CLASS).name
    _, res = serve("gnb", est, Qd, {"scores": arm}, pallas=arm != "ref")
    p = est.params
    s = gauss_scores(Qd, p.mu, p.var, p.log_prior)
    best, gap = argmax_with_margin(s)
    check_labels("gnb", np.asarray(res.classes), best, gap,
                 np.abs(s).max(1))


def phase_gmm(Xb, Qd):
    import numpy as np

    from repro.core.estimator import make_fitted
    from repro.kernels import dispatch

    t0 = time.perf_counter()
    est = make_fitted("gmm", Xb, n_groups=N_CLASS)
    log(f"[gmm] EM fit on {Xb.shape[0]} rows: {int(est.params.n_iter)} "
        f"iterations, {time.perf_counter() - t0:.3f} s")
    arm = dispatch.resolve("gmm", "responsibilities", B=BATCH, d=DIM,
                           k=N_CLASS).name
    _, res = serve("gmm", est, Qd, {"responsibilities": arm},
                   pallas=arm != "ref")
    p = est.params
    s = gauss_scores(Qd, p.mu, p.var, p.log_pi)
    best, gap = argmax_with_margin(s)
    check_labels("gmm", np.asarray(res.classes), best, gap,
                 np.abs(s).max(1))


def phase_rf(Xb, yb, Xq, Qd):
    import numpy as np

    from repro.core.estimator import make_fitted

    t0 = time.perf_counter()
    est = make_fitted("rf", Xb[:RF_ROWS], yb[:RF_ROWS], n_groups=N_CLASS)
    log(f"[rf] reduced scale: CART fit on {RF_ROWS} of {Xb.shape[0]} rows "
        f"({est.params.feature.shape[0]} trees, {est.params.feature.shape[1]}"
        f" nodes max), {time.perf_counter() - t0:.3f} s")
    _, res = serve("rf", est, Qd, {"forest_votes": "ref"}, pallas=False)
    want = forest_reference(est.params, Xq)
    bad = int(np.sum(np.asarray(res.classes) != want))
    log(f"[rf] labels equal to the plain traversal on "
        f"{Xq.shape[0] - bad} of {Xq.shape[0]} rows")
    assert bad == 0, f"rf: {bad} labels differ"


def phase_sharded(Xb, yb, Qd, chips: int):
    import jax
    import numpy as np

    from jax.sharding import AxisType

    from repro.core.estimator import make_fitted

    single = make_fitted("knn", Xb, yb, n_groups=N_CLASS, k=K)
    _, res1 = serve("knn/1-chip", single, Qd, knn_arms(single))
    ids1 = np.asarray(res1.aux)
    mesh = jax.make_mesh((chips,), ("data",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:chips])
    t0 = time.perf_counter()
    est = make_fitted("knn", Xb, yb, n_groups=N_CLASS, k=K, mesh=mesh)
    A = est.params.A
    jax.block_until_ready(A)
    devs = A.sharding.device_set
    per = {str(s.device): s.data.shape for s in A.addressable_shards}
    log(f"[knn/sharded] index on {len(devs)} devices, rows per device "
        f"{per}, placed in {time.perf_counter() - t0:.3f} s")
    assert len(devs) == chips, f"index spans {len(devs)} devices"
    for strategy in ("reference", "query"):
        name = f"knn/{strategy}x{chips}"
        engine, res = serve(name, est, Qd, knn_arms(est), mesh=mesh,
                            strategy=strategy)
        assert set(engine.bucket_strategies.values()) == {strategy}
        if strategy == "reference":
            text = engine.compiled_text(BATCH, DIM)
            log(f"[{name}] bucket {BATCH}: "
                f"{text.count('collective-permute')} collective-permute "
                f"ops (butterfly merge)")
            assert "collective-permute" in text
        ids = np.asarray(res.aux)
        same = int(np.sum(np.all(ids == ids1, axis=1)))
        log(f"[{name}] neighbour ids equal to the 1-chip answer on {same} "
            f"of {ids.shape[0]} rows")
        assert same == ids.shape[0], f"{name}: ids differ from 1 chip"


def timed(name: str, phase, *args):
    t0 = time.perf_counter()
    out = phase(*args)
    log(f"[{name}] phase wall {time.perf_counter() - t0:.3f} s")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the sharded exact-kNN phase")
    args = ap.parse_args(argv)
    devices = tpu_devices(args.chips)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    t_all = time.perf_counter()
    log(f"[device] {devices[0].device_kind} x{len(devices)}, compile cache "
        f"{enable_compile_cache()}")
    Xb, yb, Xq, Qd = make_data(args.seed)
    if args.chips > 1:
        timed("sharded", phase_sharded, Xb, yb, Qd, args.chips)
    else:
        ref_ids = timed("knn", phase_knn, Xb, yb, Xq, Qd)
        timed("ann", phase_ann, Xb, yb, Qd, ref_ids)
        timed("kmeans", phase_kmeans, Xb, Qd)
        timed("gnb", phase_gnb, Xb, yb, Qd)
        timed("gmm", phase_gmm, Xb, Qd)
        timed("rf", phase_rf, Xb, yb, Xq, Qd)
    stats = devices[0].memory_stats() or {}
    log(f"[device] peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"{stats.get('bytes_limit')}; total wall "
        f"{time.perf_counter() - t_all:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
