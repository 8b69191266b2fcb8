"""IVF-PQ asymmetric-distance (ADC) Pallas kernel — the approximate-kNN
scoring hot path (DESIGN.md §10).

Exact kNN's serve cost is linear in the reference set; the ANN estimator
(core/ann.py) caps it by probing ``nprobe`` IVF cells and scoring only
their members against per-subspace product-quantization codebooks.  The
scoring primitive is ADC: each query builds ONE small integer lookup
table (its distance to all ``n_codes`` codebook entries per subspace),
then every candidate's distance is ``m`` table lookups and adds — no
feature arithmetic at all.  This is the paper's L1-resident ``e``-array
discipline applied to a table instead of a distance row: the (Q,
m*n_codes) LUT stays VMEM-resident while int8 candidate codes stream
through in blocks, exactly how PULP-NN keeps its int8 weight LUTs in
per-cluster scratchpad.

The LUT is integer by construction (core/ann.py::build_query_luts
quantizes the fp32 subspace tables onto a shared per-query 0..255 step,
a rank-preserving affine map), so candidate distances are bounded ints:
``dist <= m*255``, with ``adc_dmax(m) = m*255 + 1`` the sentinel for
invalid (ragged-cell padding) candidates.  Bounded integer distances buy
the same two wins as kernels/quantized.py:

  * a distance and its lane pack into ONE unique int32 key
    (``dist * bl + lane``), so each selection pass is a masked min —
    no tie-break machinery — while ties still resolve to the smallest
    global candidate position, bit-equal to ``ref_adc_topk``'s
    ``lax.top_k`` oracle (the acceptance bar for this kernel);
  * the sentinel lives in VALUE space, not key space, so queries whose
    probed cells hold fewer than k real members produce exactly the
    oracle's DMAX-filled tail (smallest invalid positions first).
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
_LANES = 128                   # candidate-block multiple (one vreg of lanes)
_ROW_MULT = 32                 # query-row multiple (int8 sublane tile)
_QSTEPS = 255                  # LUT values live on the 0..255 integer step
_VMEM_BUDGET = 16 * 2 ** 20


def adc_dmax(m: int) -> int:
    """Invalid-candidate sentinel: one past the largest reachable ADC
    distance (``m`` subspaces x 255 steps)."""
    return m * _QSTEPS + 1


def packed_cols_limit(m: int) -> int:
    """Largest candidate block ``bl`` whose packed key ``dist * bl +
    lane`` fits int32 (dist <= adc_dmax(m))."""
    return (2 ** 31 - 1) // (adc_dmax(m) + 1)


def _lane_pad(n: int) -> int:
    return -(-n // _LANES) * _LANES


def adc_working_set_bytes(bl: int, q: int, m: int, n_codes: int,
                          k: int) -> int:
    """VMEM working set of one ADC grid step: the resident (Q, m*ncp)
    int32 LUT (each subspace lane-padded to ncp), double-buffered int8
    code and int32 id tiles, the (Q, bl) distance scratch and the key
    tile plus its selection temporaries, and the (Q, k) accumulator
    scratch + outputs."""
    kp = _lane_pad(k)
    return q * m * _lane_pad(n_codes) * 4 + 2 * (m * q * bl) \
        + 2 * (q * bl * 4) + 4 * (q * bl * 4) + 4 * q * kp * 4


def adc_block_cols(L: int, q: int, m: int, n_codes: int, k: int,
                   budget: int = _VMEM_BUDGET) -> int:
    """Largest lane-multiple candidate block under the VMEM budget and
    the int32 key-packing bound."""
    limit = min(packed_cols_limit(m), max(L, _LANES))
    best = _LANES
    bl = _LANES
    while bl <= limit:
        if adc_working_set_bytes(bl, q, m, n_codes, k) <= budget:
            best = bl
        bl *= 2
    return best


def _adc_topk_kernel(lut_ref, codes_ref, ids_ref, vals_ref, idx_ref,
                     acc_v, acc_i, dist_ref, *, k: int, bl: int, m: int,
                     ncp: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_v[...] = jnp.full_like(acc_v, _IMAX)
        acc_i[...] = jnp.zeros_like(acc_i)

    # ADC hot loop: m LUT lookups + adds per candidate, one chunk of at
    # most 128 candidate lanes at a time.  Codes are stored int8 as
    # (code - 128).  Each subspace's LUT row is lane-padded to ncp, so a
    # lookup is a within-vreg lane gather from one 128-wide LUT slice
    # (code & 127) selected by code >> 7 — the gather form Mosaic lowers.
    q = ids_ref.shape[0]
    lc = min(bl, _LANES)

    def chunk(t, carry):
        lo = pl.multiple_of(t * lc, lc)
        dist = jnp.zeros((q, lc), jnp.int32)
        for j in range(m):
            code = codes_ref[j, :, pl.ds(lo, lc)].astype(jnp.int32) + 128
            low, high = code & (_LANES - 1), code >> 7
            for h in range(ncp // _LANES):
                col = j * ncp + h * _LANES
                lut = lut_ref[:, col:col + _LANES]           # (Q, 128)
                got = jnp.take_along_axis(lut, low, axis=1)  # (Q, lc)
                dist = dist + jnp.where(high == h, got, 0)
        dist_ref[:, pl.ds(lo, lc)] = dist
        return carry

    jax.lax.fori_loop(0, bl // lc, chunk, 0)

    # invalid candidates (ragged-cell padding, id < 0) take the DMAX
    # sentinel in VALUE space so short candidate lists stay bit-equal to
    # the dense oracle (its tail is the same DMAX entries)
    dist = jnp.where(ids_ref[...] < 0, adc_dmax(m), dist_ref[...])

    # pack (dist, lane) into one int32 key — unique by construction — and
    # fold the tile into the running k-smallest: accumulator first on
    # equal distances, then the smallest lane, i.e. the smallest global
    # candidate position — the same stable rule as lax.top_k
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, bl), 1)
    v, ix, _ = merge_topk(acc_v[...], acc_i[...], dist * bl + lane, i * bl,
                          k, fill=_IMAX, packed_bn=bl)
    acc_v[...] = v
    acc_i[...] = ix

    @pl.when(i == pl.num_programs(0) - 1)
    def _store():
        vals_ref[...], idx_ref[...] = sort_topk(v, ix, fill=_IMAX)


def _adc_topk_call(lut, codes_t, ids, k: int, *, bl: int, m: int,
                   ncp: int, interpret: bool):
    Q, Lp = ids.shape
    kernel = functools.partial(_adc_topk_kernel, k=k, bl=bl, m=m, ncp=ncp)
    return pl.pallas_call(
        kernel,
        grid=(Lp // bl,),
        in_specs=[
            pl.BlockSpec((Q, m * ncp), lambda i: (0, 0)),    # resident LUT
            pl.BlockSpec((m, Q, bl), lambda i: (0, 0, i)),   # streams, int8
            pl.BlockSpec((Q, bl), lambda i: (0, i)),         # streams, ids
        ],
        out_specs=(pl.BlockSpec((Q, k), lambda i: (0, 0)),
                   pl.BlockSpec((Q, k), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((Q, k), jnp.int32),
                   jax.ShapeDtypeStruct((Q, k), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((Q, k), jnp.int32),
                        pltpu.VMEM((Q, k), jnp.int32),
                        pltpu.VMEM((Q, bl), jnp.int32)],
        interpret=interpret,
    )(lut, codes_t, ids)


@functools.partial(jax.jit, static_argnames=("k", "bl", "interpret"))
def adc_topk(qlut, codes, cand_ids, k: int, *, bl: int | None = None,
             interpret: bool | None = None):
    """Per-query integer LUTs (Q, m*n_codes) int32, candidate PQ codes
    (Q, L, m) int8 (stored code-128), candidate ids (Q, L) int32 (< 0 =
    invalid) -> (ADC distances (Q, k) int32, candidate POSITIONS (Q, k)
    int32 into the L axis), ascending, smallest-position ties — bit-equal
    to ``ref_adc_topk``.  ``bl`` defaults to a multiple of 128 lanes;
    smaller explicit blocks run in interpret mode only."""
    Q, L, m = codes.shape
    n_codes = qlut.shape[1] // m
    assert qlut.shape == (Q, m * n_codes), (qlut.shape, codes.shape)
    assert cand_ids.shape == (Q, L), (cand_ids.shape, codes.shape)
    assert codes.dtype == jnp.int8, codes.dtype
    assert 1 <= k <= L, (k, L)
    if bl is None:
        bl = adc_block_cols(L, max(Q, _ROW_MULT), m, n_codes, k)
    bl = min(bl, packed_cols_limit(m))
    mult = _LANES if bl >= _LANES else 8
    bl = max(mult, (min(bl, max(L, mult)) // mult) * mult)
    assert (adc_dmax(m) + 1) * bl <= 2 ** 31 - 1, (m, bl)  # key cannot wrap
    interpret = resolve_interpret(interpret)
    ncp = _lane_pad(n_codes)
    lut = pad_dim(jnp.asarray(qlut, jnp.int32).reshape(Q, m, n_codes),
                  ncp, 2, 0).reshape(Q, m * ncp)
    lut = pad_dim(lut, _ROW_MULT, 0, 0)
    ids = pad_dim(pad_dim(cand_ids, bl, 1, -1), _ROW_MULT, 0, -1)
    codes_t = pad_dim(pad_dim(jnp.transpose(codes, (2, 0, 1)), bl, 2, 0),
                      _ROW_MULT, 1, 0)                     # (m, Qp, Lp)
    vals, pos = _adc_topk_call(lut, codes_t, ids, k, bl=bl, m=m, ncp=ncp,
                               interpret=interpret)
    return vals[:Q], pos[:Q]


def ref_adc_topk(qlut, codes, cand_ids, k: int):
    """Pure-jnp oracle: dense integer ADC over all L candidates, invalid
    entries at the DMAX sentinel, smallest-position ties (``lax.top_k``
    on the negated distances)."""
    Q, L, m = codes.shape
    n_codes = qlut.shape[1] // m
    idx = codes.astype(jnp.int32) + 128 \
        + jnp.arange(m, dtype=jnp.int32)[None, None, :] * n_codes
    gathered = jnp.take_along_axis(jnp.asarray(qlut, jnp.int32),
                                   idx.reshape(Q, L * m), axis=1)
    dist = jnp.sum(gathered.reshape(Q, L, m), axis=2)
    dist = jnp.where(cand_ids < 0, adc_dmax(m), dist)
    nv, ni = jax.lax.top_k(-dist, k)
    return -nv, ni
