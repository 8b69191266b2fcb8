"""Selection-Sort partial top-k Pallas kernel (paper §4.4.3), and the
running-accumulator merge every streaming top-k kernel shares.

The paper's insight — k smallest of n needs only O(nk) work — maps to the
VPU as k passes of vectorised min+mask over a row block held in VMEM (the
scalar swap loop of Selection Sort is hostile to 8x128 vregs; the masked-min
pass has identical asymptotics and full lane utilisation; DESIGN.md §2).

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
    """Fold a (Q, bn) tile into a sorted running (Q, k) k-smallest list.

    ``acc_v``/``acc_i`` hold values and global indices, ascending, with
    every index smaller than the tile's (``base + lane``).  ``tile`` holds
    raw values, or with ``packed_bn`` unique int32 keys ``value * packed_bn
    + lane``.  Each of the k passes takes the smaller of the two list
    heads; ties go to the accumulator, then to the first lane, so the
    result keeps the smallest-global-index-first rule of a stable sort over
    ``[accumulator | tile]``.  ``fill`` marks a taken entry.  Returns the
    new (values, indices)."""
    q, bn = tile.shape
    acol = jax.lax.broadcasted_iota(jnp.int32, (q, k), 1)
    tcol = jax.lax.broadcasted_iota(jnp.int32, (q, bn), 1)

    def body(j, carry):
        av, tv, ov, oi = carry
        ma = jnp.min(av, axis=1)                              # (Q,)
        pa = jnp.min(jnp.where(av == ma[:, None], acol, k), axis=1)
        if packed_bn is None:
            mt = jnp.min(tv, axis=1)
            pt = jnp.min(jnp.where(tv == mt[:, None], tcol, bn), axis=1)
        else:
            key = jnp.min(tv, axis=1)
            mt, pt = key // packed_bn, key % packed_bn
        from_acc = ma <= mt
        ia = jnp.sum(jnp.where(acol == pa[:, None], acc_i, 0), axis=1)
        ov = _set_col(ov, acol, j, jnp.where(from_acc, ma, mt))
        oi = _set_col(oi, acol, j, jnp.where(from_acc, ia, base + pt))
        av = jnp.where(from_acc[:, None] & (acol == pa[:, None]), fill, av)
        tv = jnp.where(~from_acc[:, None] & (tcol == pt[:, None]), fill, tv)
        return av, tv, ov, oi

    _, _, ov, oi = jax.lax.fori_loop(0, k, body, (acc_v, tile, acc_v, acc_i))
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
