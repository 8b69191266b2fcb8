"""Selection-Sort partial top-k Pallas kernel (paper §4.4.3), and the
running-accumulator merge every streaming top-k kernel shares.

The paper's insight — k smallest of n needs only O(nk) work — maps to the
VPU as k passes of vectorised min+mask over a row block held in VMEM (the
scalar swap loop of Selection Sort is hostile to 8x128 vregs; the masked-min
pass has identical asymptotics and full lane utilisation; DESIGN.md §2).

The streaming merge (``merge_topk``) runs those passes only while they can
change the answer: once a query's running k-th best is tighter than a whole
tile, the tile costs one min sweep.  Its set stays unsorted until
``sort_topk`` orders it once, after the last tile.

Mosaic cannot store to a column picked by a loop index (``ref[:, j]``
needs a lane offset it can prove is a multiple of 128), so every pass
writes its result into a (rows, k) value with ``where(col == j, ...)`` and
the block is stored once after the last pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INF = float("inf")


def _set_col(out, col, j, v):
    """``out[:, j] = v`` as a whole-block select (Mosaic-compilable)."""
    return jnp.where(col == j, v[:, None], out)


def merge_topk(acc_v, acc_i, tile, base, k: int, *, fill,
               packed_bn: int | None = None):
    """Fold a (Q, bn) tile into an unsorted running (Q, k) k-smallest set.

    ``acc_v``/``acc_i`` hold values and global indices, in any order, every
    real index smaller than the tile's (``base + lane``); an unfilled slot
    holds ``(fill, 0)``.  ``tile`` holds raw values, or with ``packed_bn``
    unique int32 keys ``value * packed_bn + lane``.  A pass runs only while
    some query's smallest remaining tile entry beats the worst entry of its
    set: that query's worst is replaced by its tile minimum (first lane on
    ties), the lane is masked with ``fill``, and the worst is found again.
    The worst is the largest under the total order (value, index), and the
    comparison is strict, so a tie keeps the accumulator's smaller index:
    the set stays the k smallest of ``[accumulator | tile]`` under that
    order, the set a stable sort keeps; ``sort_topk`` orders it.  (A masked
    packed key decodes above every real value, so it fills only an unfilled
    slot, and the next real entry displaces it.)  At most ``min(k, bn)``
    passes run; a tile that improves no query costs one min sweep.
    Returns the new (values, indices) and the number of passes run."""
    q, bn = tile.shape
    acol = jax.lax.broadcasted_iota(jnp.int32, (q, k), 1)
    tcol = jax.lax.broadcasted_iota(jnp.int32, (q, bn), 1)

    def worst(av, ai):
        wv = jnp.max(av, axis=1)
        at_wv = av == wv[:, None]
        wi = jnp.max(jnp.where(at_wv, ai, -1), axis=1)
        wp = jnp.min(jnp.where(at_wv & (ai == wi[:, None]), acol, k), axis=1)
        return wv, wp

    def value(m):                       # a row's tile minimum -> its value
        return m if packed_bn is None else m // packed_bn

    def cond(carry):
        it, _, _, _, m, wv, _ = carry
        return (it < min(k, bn)) & jnp.any(value(m) < wv)

    def body(carry):
        it, av, ai, tv, m, wv, wp = carry
        mt = value(m)
        if packed_bn is None:
            pt = jnp.min(jnp.where(tv == m[:, None], tcol, bn), axis=1)
        else:
            pt = m % packed_bn
        put = (mt < wv)[:, None] & (acol == wp[:, None])
        av = jnp.where(put, mt[:, None], av)
        ai = jnp.where(put, (base + pt)[:, None], ai)
        # a row that took nothing has no remaining entry below its worst,
        # so masking its minimum too loses nothing
        tv = jnp.where(tcol == pt[:, None], fill, tv)
        wv, wp = worst(av, ai)
        return it + 1, av, ai, tv, jnp.min(tv, axis=1), wv, wp

    passes, av, ai, _, _, _, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), acc_v, acc_i, tile, jnp.min(tile, axis=1))
        + worst(acc_v, acc_i))
    return av, ai, passes


def sort_topk(v, ix, *, fill):
    """Order a (Q, k) set from ``merge_topk`` ascending by (value, index),
    as k masked-min passes over the set; ``fill`` is at least every
    value."""
    q, k = v.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (q, k), 1)
    imax = jnp.iinfo(jnp.int32).max

    def body(j, carry):
        v, ix, ov, oi = carry
        m = jnp.min(v, axis=1)
        at_m = v == m[:, None]
        mi = jnp.min(jnp.where(at_m, ix, imax), axis=1)
        p = jnp.min(jnp.where(at_m & (ix == mi[:, None]), col, k), axis=1)
        ov = _set_col(ov, col, j, m)
        oi = _set_col(oi, col, j, mi)
        taken = col == p[:, None]
        return (jnp.where(taken, fill, v), jnp.where(taken, imax, ix),
                ov, oi)

    _, _, ov, oi = jax.lax.fori_loop(0, k, body, (v, ix, v, ix))
    return ov, oi


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)               # (br, n)
    br, n = x.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (br, n), 1)
    ocol = jax.lax.broadcasted_iota(jnp.int32, (br, k), 1)

    def pass_body(j, carry):
        x_cur, ov, oi = carry
        m = jnp.min(x_cur, axis=1)                    # (br,) selection pass j
        # first index attaining the minimum (stable, matches SS order)
        first = jnp.min(jnp.where(x_cur == m[:, None], cols, n), axis=1)
        ov = _set_col(ov, ocol, j, m)
        oi = _set_col(oi, ocol, j, first)
        x_cur = jnp.where(cols == first[:, None], _INF, x_cur)
        return x_cur, ov, oi

    _, ov, oi = jax.lax.fori_loop(
        0, k, pass_body, (x, jnp.zeros((br, k), jnp.float32),
                          jnp.zeros((br, k), jnp.int32)))
    vals_ref[...] = ov.astype(vals_ref.dtype)
    idx_ref[...] = oi


def topk_smallest(x, k: int, *, br: int = 8, interpret: bool = False):
    """x (R, n) -> (values (R, k), indices (R, k)), ascending per row."""
    R, n = x.shape
    assert R % br == 0, (R, br)
    kernel = functools.partial(_topk_kernel, k=k)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((br, k), lambda i: (i, 0)),
                   pl.BlockSpec((br, k), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((R, k), jnp.float32),
                   jax.ShapeDtypeStruct((R, k), jnp.int32)),
        interpret=interpret,
    )(x)
    return vals, idx
