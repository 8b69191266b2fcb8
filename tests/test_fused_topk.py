"""Fused distance->top-k streaming kernel: bit-equivalence vs the two-pass
composition (pairwise_sq_dist + topk_smallest), oracle parity, tie
semantics, the early-exit merge against the fixed-pass merge it replaced
and its pass counter, the batched kNN / fused K-Means paths built on it,
the serving engine wiring, the matmul block-clamp regression, and the HBM
bytes A/B."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from conftest import synth_blobs
from repro.core import kmeans as KM
from repro.core import knn as KNN
from repro.kernels import ann as ann_kernels
from repro.kernels import distance_topk as dtopk_kernels
from repro.kernels import ops, quantized, ref
from repro.serving import KNNServeEngine

KEY = jax.random.PRNGKey(11)


def _two_pass(a, c, k):
    """The unfused kernel composition the streaming kernel must match
    bit-for-bit: (N, Q) distances through HBM, then row-wise selection."""
    e = ops.pairwise_sq_dist(a, c)
    return ops.topk_smallest(jnp.transpose(e), k)


# ------------------------------------------------------------ parity


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k", [1, 3, 8])
@pytest.mark.parametrize("n,d,q", [(100, 21, 3), (999, 8, 5), (256, 64, 16),
                                   (37, 5, 1)])
def test_fused_matches_two_pass_bitwise(n, d, q, k, dtype):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, n * 31 + k))
    a = (jax.random.normal(k1, (n, d)) * 0.7).astype(dtype)
    c = (jax.random.normal(k2, (q, d)) * 0.7).astype(dtype)
    gv, gi = ops.distance_topk(a, c, k)
    tv, ti = _two_pass(a, c, k)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(tv))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ti))


@pytest.mark.parametrize("k", [1, 3, 8])
@pytest.mark.parametrize("n,d,q", [(100, 21, 3), (999, 8, 5)])
def test_fused_matches_oracle(n, d, q, k):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, n + k))
    a = jax.random.normal(k1, (n, d))
    c = jax.random.normal(k2, (q, d))
    gv, gi = ops.distance_topk(a, c, k)
    wv, wi = ref.distance_topk(a, c, k)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


@pytest.mark.parametrize("bn", [8, 16, 64])
def test_fused_small_stream_blocks(bn):
    """bn < k and bn that does not divide N both exercise the cross-step
    accumulator (INF placeholders displaced by later tiles)."""
    k1, k2 = jax.random.split(KEY)
    a = jax.random.normal(k1, (123, 12))
    c = jax.random.normal(k2, (4, 12))
    gv, gi = ops.distance_topk(a, c, 8, bn=bn)
    tv, ti = _two_pass(a, c, 8)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(tv))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ti))


def test_fused_tie_semantics_stable_first_index():
    """Duplicate rows -> tied distances; selection must prefer the smallest
    global row index, matching the two-pass kernel and a stable argsort."""
    a = jnp.concatenate([jnp.ones((4, 6)), jnp.zeros((3, 6)),
                         jnp.ones((5, 6))], axis=0)        # rows 0-3,7-11 tie
    c = jnp.stack([jnp.ones((6,)), jnp.zeros((6,))])
    gv, gi = ops.distance_topk(a, c, 6)
    tv, ti = _two_pass(a, c, 6)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ti))
    d = np.sum((np.asarray(a)[None] - np.asarray(c)[:, None]) ** 2, axis=2)
    want = np.argsort(d, axis=1, kind="stable")[:, :6]
    np.testing.assert_array_equal(np.asarray(gi), want)


def test_fused_padded_rows_never_selected():
    """Ragged N: zero-padded rows are close to a zero query but must be
    masked out of the selection."""
    k1 = jax.random.fold_in(KEY, 3)
    a = jax.random.normal(k1, (13, 4)) + 5.0    # all rows far from origin
    c = jnp.zeros((2, 4))                       # pad rows would win unmasked
    _, gi = ops.distance_topk(a, c, 5, bn=8)    # pads 13 -> 16
    assert np.asarray(gi).max() < 13


# ------------------------------------------------------------ early-exit merge


def _fixed_pass_merge(acc_v, acc_i, tile, base, k, *, fill, packed_bn=None):
    """The merge the early exit replaced, kept as the bit-identity oracle:
    k head-to-head passes over a sorted accumulator and the tile, in every
    tile, ties to the accumulator and then the first lane.  Returns a pass
    count of 0, so a kernel traced with it is told apart by its counter."""
    q, bn = tile.shape
    acol = jax.lax.broadcasted_iota(jnp.int32, (q, k), 1)
    tcol = jax.lax.broadcasted_iota(jnp.int32, (q, bn), 1)

    def body(j, carry):
        av, tv, ov, oi = carry
        ma = jnp.min(av, axis=1)
        pa = jnp.min(jnp.where(av == ma[:, None], acol, k), axis=1)
        if packed_bn is None:
            mt = jnp.min(tv, axis=1)
            pt = jnp.min(jnp.where(tv == mt[:, None], tcol, bn), axis=1)
        else:
            key = jnp.min(tv, axis=1)
            mt, pt = key // packed_bn, key % packed_bn
        from_acc = ma <= mt
        ia = jnp.sum(jnp.where(acol == pa[:, None], acc_i, 0), axis=1)
        ov = jnp.where(acol == j, jnp.where(from_acc, ma, mt)[:, None], ov)
        oi = jnp.where(acol == j,
                       jnp.where(from_acc, ia, base + pt)[:, None], oi)
        av = jnp.where(from_acc[:, None] & (acol == pa[:, None]), fill, av)
        tv = jnp.where(~from_acc[:, None] & (tcol == pt[:, None]), fill, tv)
        return av, tv, ov, oi

    _, _, ov, oi = jax.lax.fori_loop(0, k, body, (acc_v, tile, acc_v, acc_i))
    return ov, oi, jnp.int32(0)


def _with_fixed_pass_merge(monkeypatch, fn, *args, **kw):
    """Run a jitted kernel wrapper's body with the fixed-pass merge in every
    streaming top-k kernel; the body is traced anew, not taken from the
    wrapper's jit cache."""
    traced = []

    def merge(*a, **kk):
        traced.append(True)
        return _fixed_pass_merge(*a, **kk)

    with monkeypatch.context() as mp:
        for mod in (dtopk_kernels, ann_kernels, quantized):
            mp.setattr(mod, "merge_topk", merge)
        out = fn.__wrapped__(*args, **kw)
    assert traced
    return out


def _int_rows(seed, n, d, hi):
    """Integer-valued rows: every distance is exact in fp32 whatever the
    order of the sums, and small ranges give many exact ties."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, hi, size=(n, d)), jnp.float32)


def _merge_case(name):
    """(rows, queries, k, bn) of one early-exit case."""
    if name == "ties":               # ties inside tiles and across tiles
        return _int_rows(1, 300, 4, 3), _int_rows(2, 5, 4, 3), 8, 16
    if name == "decreasing":         # each row nearer: every tile takes k
        n = 200
        a = jnp.zeros((n, 3)).at[:, 0].set(jnp.arange(n, 0, -1.0))
        return a, jnp.stack([jnp.zeros(3), -jnp.ones(3)]), 6, 8
    if name == "k_over_bn":          # a tile holds fewer rows than k
        return _int_rows(3, 150, 5, 6), _int_rows(4, 4, 5, 6), 20, 8
    if name == "n_valid":            # 123 rows padded to 128, zero rows
        return _int_rows(5, 123, 6, 9), _int_rows(6, 3, 6, 9), 10, 64
    if name == "zero_queries":       # all-zero queries, as the engine pads
        c = jnp.zeros((3, 4)).at[1].set(jnp.ones(4))
        return _int_rows(7, 250, 4, 4), c, 7, 32
    raise ValueError(name)


@pytest.mark.parametrize("case", ["ties", "decreasing", "k_over_bn",
                                  "n_valid", "zero_queries"])
def test_early_exit_merge_bit_equal_to_fixed_pass(case, monkeypatch):
    a, c, k, bn = _merge_case(case)
    gv, gi = ops.distance_topk(a, c, k, bn=bn)
    ov, oi, (passes, _) = _with_fixed_pass_merge(
        monkeypatch, ops.distance_topk, a, c, k, bn=bn, stats=True,
        interpret=True)
    assert int(passes) == 0                  # the oracle's kernel ran
    tv, ti = _two_pass(a, c, k)
    for want_v, want_i in ((ov, oi), (tv, ti)):
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(want_v))
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(want_i))


def _adc_k100(bl):
    """ADC candidates with ties (a narrow LUT) and invalid ids (-1, scored
    at the DMAX sentinel), k=100 across several blocks."""
    rng = np.random.default_rng(bl)
    Q, L, m, n_codes = 5, 300, 4, 16
    qlut = jnp.asarray(rng.integers(0, 6, size=(Q, m * n_codes)), jnp.int32)
    codes = jnp.asarray(rng.integers(0, n_codes, size=(Q, L, m)) - 128,
                        jnp.int8)
    ids = jnp.asarray(rng.integers(-1, 3, size=(Q, L)), jnp.int32)
    return qlut, codes, ids


@pytest.mark.parametrize("kernel", ["adc_topk-bl8", "adc_topk-bl128",
                                    "distance_topk_q8"])
def test_early_exit_packed_merge_bit_equal_at_k100(kernel, monkeypatch):
    """The packed-key path: unique int32 keys, sentinels in value space
    (ADC's DMAX for invalid ids; IMAX keys on the q8 kernel's padded
    rows)."""
    if kernel.startswith("adc_topk"):
        fn, ref_fn = ann_kernels.adc_topk, ann_kernels.ref_adc_topk
        bl = int(kernel.split("bl")[1])
        args, kw = _adc_k100(bl), {"bl": bl}
    else:
        fn, ref_fn = quantized.distance_topk_q8, quantized.ref_distance_topk_q8
        rng = np.random.default_rng(9)
        args = tuple(jnp.asarray(rng.integers(-3, 4, size=s), jnp.int8)
                     for s in ((500, 16), (4, 16)))
        kw = {"bn": 32}                          # 500 rows padded to 512
    gv, gi = fn(*args, 100, **kw)
    ov, oi = _with_fixed_pass_merge(monkeypatch, fn, *args, 100,
                                    interpret=True, **kw)
    rv, ri = ref_fn(*args, 100)
    for want_v, want_i in ((ov, oi), (rv, ri)):
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(want_v))
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(want_i))


def _passes_needed(a, c, k, bn):
    """Merge passes an exact early-exit merge needs: per tile, the largest
    over queries of the tile entries strictly below that query's running
    k-th value, taken in ascending order, so at most k."""
    dist = ((np.asarray(c, np.float64)[:, None]
             - np.asarray(a, np.float64)[None]) ** 2).sum(-1)
    best = [[] for _ in range(dist.shape[0])]      # (value, index) pairs
    total = 0
    for lo in range(0, dist.shape[1], bn):
        need = 0
        for q, row in enumerate(dist[:, lo:lo + bn]):
            tile = [(v, lo + j) for j, v in enumerate(row)]
            best[q] = sorted(best[q] + tile)[:k]
            need = max(need, sum(ix >= lo for _, ix in best[q]))
        total += need
    return total


def test_merge_pass_counter_matches_numpy():
    a, c, k, bn = _int_rows(11, 333, 4, 5), _int_rows(12, 8, 4, 5), 6, 32
    gv, gi, stats = ops.distance_topk(a, c, k, bn=bn, stats=True)
    assert np.asarray(stats).tolist() == [_passes_needed(a, c, k, bn), 11]
    # off by default: the same answers, a program without the counter
    pv, pi = ops.distance_topk(a, c, k, bn=bn, stats=False)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(pv))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(pi))
    text = ops.distance_topk.lower(a, c, k, bn=bn).as_text()
    assert text == ops.distance_topk.lower(a, c, k, bn=bn,
                                           stats=False).as_text()
    assert text != ops.distance_topk.lower(a, c, k, bn=bn,
                                           stats=True).as_text()


@pytest.mark.parametrize("n,d,kc", [(100, 21, 3), (999, 8, 7), (64, 4, 2)])
def test_distance_argmin_matches_oracle(n, d, kc):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, n))
    a = jax.random.normal(k1, (n, d))
    c = jax.random.normal(k2, (kc, d))
    mv, mi = ops.distance_argmin(a, c)
    rv, ri = ref.distance_argmin(a, c)
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(mv), np.asarray(rv),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ batched kNN


def test_knn_classify_batch_matches_vmapped_loop():
    X, y = synth_blobs(n=400, d=21, n_class=3)
    model = KNN.KNNModel(A=jnp.asarray(X), labels=jnp.asarray(y), n_class=3)
    Q = jnp.asarray(X[:64]) + 0.03
    cls_b, nbr_b = KNN.knn_classify_batch(model, Q, k=5)
    cls_v, nbr_v = jax.vmap(
        lambda x: KNN.knn_classify(model, x, 5))(Q)
    np.testing.assert_array_equal(np.asarray(cls_b), np.asarray(cls_v))
    # neighbour SETS agree (the Fig. 6 two-level merge emits a different
    # order than ascending-distance, but the same k rows)
    for got, want in zip(np.asarray(nbr_b), np.asarray(nbr_v)):
        assert set(got.tolist()) == set(want.tolist())


def test_kmeans_fused_assignment_matches_dense():
    X, _ = synth_blobs(n=300, d=13, n_class=4, seed=2)
    Xj = jnp.asarray(X)
    cents = Xj[:4]
    _, ids = KM.kmeans_iteration(Xj, cents)
    d = np.asarray(KM._pairwise_sq_dist(Xj, cents))
    np.testing.assert_array_equal(np.asarray(ids), d.argmin(axis=1))


# ------------------------------------------------------------ serving


def test_serve_engine_uses_batched_fused_path():
    X, y = synth_blobs(n=400, d=21, n_class=3)
    model = KNN.KNNModel(A=jnp.asarray(X), labels=jnp.asarray(y), n_class=3)
    eng = KNNServeEngine(model, k=4, max_batch=64)
    res = eng.classify(X[:100])
    want_cls, want_nbr = KNN.knn_classify_batch(model, jnp.asarray(X[:100]),
                                                k=4)
    np.testing.assert_array_equal(np.asarray(res.classes),
                                  np.asarray(want_cls))
    np.testing.assert_array_equal(np.asarray(res.neighbors),
                                  np.asarray(want_nbr))
    assert res.launches == 2                       # 64 + 36 -> two launches
    assert eng.bucket_launches == {64: 2}          # 36 padded into the 64s

    res2 = eng.classify(X[:3])                     # bucket 4, fresh compile
    assert eng.bucket_launches[4] == 1
    np.testing.assert_array_equal(
        np.asarray(res2.classes),
        np.asarray(KNN.knn_predict_batch(model, X[:3], k=4)))


# ------------------------------------------------------------ block clamps


def test_clamp_block_divisor_safe():
    for n in range(1, 300):
        b = ops.clamp_block(128, n)
        assert b % 8 == 0                          # Mosaic sublane tiling
        padded = ((n + b - 1) // b) * b
        assert padded % b == 0 and padded >= n


@pytest.mark.parametrize("m", [3, 10, 12, 100, 129])
def test_matmul_small_m_default_blocks(m):
    """Regression: the old clamp produced bm=M for 8 < M < 128, which is
    sublane-misaligned; the divisor-safe clamp must stay correct."""
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, m))
    a = jax.random.normal(k1, (m, 40))
    b = jax.random.normal(k2, (40, 24))
    got = ops.matmul(a, b)                         # default bm=128 -> clamped
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                               rtol=2e-4, atol=2e-4)


def test_fused_autotune_block_fits_budget():
    from benchmarks.kernel_blocks import fused_topk_working_set
    for (n, d, q, k) in [(4096, 64, 16, 8), (1 << 20, 784, 128, 8)]:
        bn = ops.fused_topk_block_rows(n, d, q, k)
        w = fused_topk_working_set(bn, d, q, k)
        assert w["fits"] and w["sublane_aligned"], (n, d, q, k, bn, w)


# ------------------------------------------------------------ bytes A/B


def test_fused_moves_fewer_hbm_bytes_at_4096():
    """Acceptance: for N >= 4096 the fused path's loop-weighted HLO bytes
    accessed are strictly below the two-pass composition's."""
    from benchmarks.hlo_analysis import analyze
    from benchmarks.kernel_blocks import topk_bytes_moved
    n, d, q, k = 4096, 64, 16, 8
    k1, k2 = jax.random.split(KEY)
    a = jax.random.normal(k1, (n, d))
    c = jax.random.normal(k2, (q, d))
    fused = jax.jit(lambda a, c: ops.distance_topk(a, c, k))
    twop = jax.jit(lambda a, c: _two_pass(a, c, k))
    fb = analyze(fused.lower(a, c).compile().as_text()).bytes
    tb = analyze(twop.lower(a, c).compile().as_text()).bytes
    assert fb < tb, (fb, tb)
    # the analytic model agrees on the direction
    m = topk_bytes_moved(n, d, q, k)
    assert m["fused"] < m["two_pass"]
