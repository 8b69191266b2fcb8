"""Horizontal / vertical workload distribution (paper §4.1) and the two-phase
(local -> global) reduction schemes (paper §4.2-4.4), in JAX.

The paper dispatches work to 8 PULP cores with offline-chosen chunk sizes and
runtime lb/ub bounds. Here the same decomposition is expressed two ways:

  * ``VirtualCluster`` — reshape + vmap over a "cores" axis. Semantically
    identical to SPMD (each lane sees one chunk), runs on a single device,
    and is what the paper-table benchmarks use (n_cores=8, like the CL).
  * ``shard_map`` wrappers — the same chunk-local functions over a real mesh
    axis with psum/all_gather combines; used at production scale and proven
    equal to the vmap path in tests.

Design note (DESIGN.md §2): the paper's shared intermediate R[N_class,n_cores]
plus the OP2 re-partitioned combine is exactly a reduce-scatter schedule; the
explicit `two_phase_matvec` below keeps that structure visible.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P



# ---------------------------------------------------------------------------
# §4.1 — partitioning strategy and chunk bounds
# ---------------------------------------------------------------------------


def choose_partition(r: int, c: int) -> str:
    """Paper §4.1: r >> c favours row-wise (horizontal), c >> r column-wise
    (vertical) decomposition of an (r x c) operand."""
    return "horizontal" if r >= c else "vertical"


def chunk_bounds(n: int, n_cores: int, core_id):
    """Runtime lb/ub computation, exactly the paper's formula:
    chunk = n / n_cores; lb = core_id * chunk; ub = lb + chunk."""
    chunk = n // n_cores
    lb = core_id * chunk
    return lb, lb + chunk


def pad_to_multiple(x, n_cores: int, axis: int = 0, value=0.0):
    """Real datasets rarely divide by 8; pad (the paper sizes chunks offline,
    we pad like a production system would)."""
    n = x.shape[axis]
    pad = (-n) % n_cores
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), n


def split_chunks(x, n_cores: int, axis: int = 0):
    """(n, ...) -> (n_cores, n/n_cores, ...) along ``axis`` (pre-padded)."""
    n = x.shape[axis]
    assert n % n_cores == 0, (n, n_cores)
    new_shape = x.shape[:axis] + (n_cores, n // n_cores) + x.shape[axis + 1:]
    return x.reshape(new_shape)


# ---------------------------------------------------------------------------
# Two-phase matvec (paper Fig. 4 OP1/OP2): y = W @ x + b
# ---------------------------------------------------------------------------


def two_phase_matvec(W, x, b, n_cores: int = 8):
    """Vertical (column-wise) split of the contraction dim, per-core partial
    products into R[N_class, n_cores], then a row-wise combine with the bias.

    W: (C, d); x: (d,); b: (C,). Returns y: (C,).
    """
    C, d = W.shape
    Wp, _ = pad_to_multiple(W, n_cores, axis=1)
    xp, _ = pad_to_multiple(x, n_cores, axis=0)
    Wc = split_chunks(Wp, n_cores, axis=1)        # (C, n_cores, d/n)
    xc = split_chunks(xp, n_cores, axis=0)        # (n_cores, d/n)

    # OP1 — each core: partial dot over its d-chunk, all classes
    def op1(w_chunk, x_chunk):                    # (C, d/n), (d/n)
        return w_chunk @ x_chunk                  # (C,)

    R = jax.vmap(op1, in_axes=(1, 0))(Wc, xc)     # (n_cores, C) — shared R

    # OP2 — row-wise re-partition: each core combines R rows for its classes
    Rp, C_orig = pad_to_multiple(R, n_cores, axis=1)
    bp, _ = pad_to_multiple(b, n_cores, axis=0)
    Rc = split_chunks(Rp, n_cores, axis=1)        # (n_src_cores, n_cores, C/n)
    bc = split_chunks(bp, n_cores, axis=0)        # (n_cores, C/n)

    def op2(r_rows, b_rows):                      # (n_src_cores, C/n), (C/n)
        return jnp.sum(r_rows, axis=0) + b_rows

    y = jax.vmap(op2, in_axes=(1, 0))(Rc, bc)     # map over OP2's core axis
    return y.reshape(-1)[:C_orig]


def two_phase_matvec_shardmap(W, x, b, mesh: Mesh, axis: str = "data"):
    """shard_map version: the d-contraction is sharded over ``axis``; OP1 is
    the per-shard partial matvec, OP2 is the psum (the R-array combine)."""
    n = mesh.shape[axis]
    Wp, _ = pad_to_multiple(W, n, axis=1)
    xp, _ = pad_to_multiple(x, n, axis=0)

    def local(w_chunk, x_chunk, b_full):
        partial = w_chunk @ x_chunk               # OP1: local chunk product
        return jax.lax.psum(partial, axis) + b_full  # OP2: global combine

    fn = _shard_map(
        functools.partial(local),
        mesh=mesh,
        in_specs=(P(None, axis), P(axis), P()),
        out_specs=P(),
    )
    return fn(Wp, xp, b)


# ---------------------------------------------------------------------------
# Two-phase chunked reduction (GNB-style: per-chunk sums -> combine)
# ---------------------------------------------------------------------------


def two_phase_reduce(fn: Callable, combine: Callable, x, n_cores: int = 8,
                     axis: int = 0):
    """OP1: apply ``fn`` per core chunk; OP2: ``combine`` partials.

    fn maps a chunk (n/n_cores, ...) -> partial; combine reduces the stacked
    (n_cores, ...) partials.
    """
    xc = split_chunks(x, n_cores, axis=axis)
    moved = jnp.moveaxis(xc, axis, 0)
    partials = jax.vmap(fn)(moved)
    return combine(partials)
