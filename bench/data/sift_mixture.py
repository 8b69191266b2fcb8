"""Seeded SIFT-shaped vectors: a two-level Gaussian mixture at SIFT's scale.

Every row lies in [0, 255]^d and most coordinates are small, as in SIFT
descriptors.  A row is ``clip(center + z @ W + noise)``: ``center`` is the
row's component, itself scattered around one of a few super-clusters,
``z`` is a low-dimensional latent (SIFT's local intrinsic dimension is
low) and ``W`` the component's own loadings.  Values stay continuous
(not rounded to integers): integers 0..255 are exact in bfloat16, so on
integer data a lower-precision distance would return the same answers
and the fp32 guarantee could not be checked.

Rows come in i.i.d. order, because the IVF fit trains on the leading
rows.  The base rows and the query pool are drawn from the same mixture;
the pool rows are held out of the index.

``generate(seed, spec)`` builds everything on the device in one jitted
call and returns ``(base (N, d) f32, labels (N,) i32, pool (P, d) f32)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_CHUNK = 8192   # rows generated per step: bounds the (rows, r, d) gather


def key_from_seed(seed: int):
    """A threefry key from any whole number, 64-bit seeds included."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


@functools.partial(jax.jit, static_argnames=(
    "n", "d", "n_super", "n_comp", "rank", "n_labels"))
def _rows(key, *, n, d, n_super, n_comp, rank, n_labels, super_a, super_b,
          comp_spread, within, within_jitter, noise, size_sigma):
    ks = jax.random.split(key, 9)
    supers = 255.0 * jax.random.beta(ks[0], super_a, super_b, (n_super, d))
    parent = jax.random.randint(ks[1], (n_comp,), 0, n_super)
    centers = supers[parent] + comp_spread * jax.random.normal(
        ks[2], (n_comp, d))
    scale = within * jnp.exp(within_jitter * jax.random.normal(
        ks[3], (n_comp, 1, 1)))
    loads = scale * jax.random.normal(ks[4], (n_comp, rank, d)) \
        / np.sqrt(rank)
    # heavy-tailed component sizes: log-normal weights
    logw = size_sigma * jax.random.normal(ks[5], (n_comp,))
    n_pad = -(-n // _CHUNK) * _CHUNK
    comp = jax.random.categorical(ks[6], logw, shape=(n_pad,))
    z = jax.random.normal(ks[7], (n_pad, rank))
    eps = noise * jax.random.normal(ks[8], (n_pad, d))

    def chunk(args):
        c, zc, ec = args
        x = centers[c] + jnp.einsum("nr,nrd->nd", zc, loads[c],
                                    precision="highest") + ec
        return jnp.clip(x, 0.0, 255.0)

    shape = (n_pad // _CHUNK, _CHUNK)
    X = jax.lax.map(chunk, (comp.reshape(shape), z.reshape(shape + (rank,)),
                            eps.reshape(shape + (d,))))
    X = X.reshape(n_pad, d)[:n]
    labels = (comp[:n] % n_labels).astype(jnp.int32)
    return X, labels


def generate(seed: int, spec: dict):
    """Base rows, their labels and the held-out query pool, on the device."""
    n_base, n_pool = int(spec["n_base"]), int(spec["pool"])
    X, labels = _rows(
        key_from_seed(seed), n=n_base + n_pool, d=int(spec["d"]),
        n_super=int(spec["super_clusters"]), n_comp=int(spec["components"]),
        rank=int(spec["intrinsic_dim"]), n_labels=int(spec["labels"]),
        super_a=float(spec["super_beta"][0]),
        super_b=float(spec["super_beta"][1]),
        comp_spread=float(spec["component_spread"]),
        within=float(spec["within_spread"]),
        within_jitter=float(spec["within_jitter"]),
        noise=float(spec["noise"]), size_sigma=float(spec["size_sigma"]))
    return X[:n_base], labels[:n_base], X[n_base:]
