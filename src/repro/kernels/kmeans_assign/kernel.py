"""k-means assignment kernel (the Angle/Sphere hot loop, paper §5.3).

Computes nearest-centroid ids and distances for a block of points. The
centroid table [K, D] stays pinned in VMEM across the whole grid while point
tiles stream through; distances use the MXU via the -2*x@c^T expansion:

    d2(x, c) = |x|^2 - 2 x.c + |c|^2.

Grid: (N / bn,). Outputs per point: argmin id (int32) and min distance.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, c_ref, ids_ref, d2_ref):
    x = x_ref[...].astype(jnp.float32)          # [bn, D]
    c = c_ref[...].astype(jnp.float32)          # [K, D]
    xx = jnp.sum(x * x, axis=1, keepdims=True)  # [bn, 1]
    cc = jnp.sum(c * c, axis=1)[None, :]        # [1, K]
    # HIGHEST: the MXU's default single bf16 pass would round x and c
    # to 8 mantissa bits and move points near a Voronoi boundary
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    d2 = xx - 2.0 * xc + cc                     # [bn, K]
    ids_ref[...] = jnp.argmin(d2, axis=1).astype(jnp.int32)
    d2_ref[...] = jnp.min(d2, axis=1)


def kmeans_assign_call(x: jax.Array, c: jax.Array, *, block_n: int = 1024,
                       interpret: bool = False):
    """x: [N, D]; c: [K, D]. Returns (ids [N] int32, d2 [N] fp32)."""
    N, D = x.shape
    K = c.shape[0]
    bn = min(block_n, N)
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n_blocks = x.shape[0] // bn

    ids, d2 = pl.pallas_call(
        _kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((K, D), lambda i: (0, 0)),   # pinned centroids
        ],
        out_specs=[
            pl.BlockSpec((bn,), lambda i: (i,)),
            pl.BlockSpec((bn,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0],), jnp.int32),
            jax.ShapeDtypeStruct((x.shape[0],), jnp.float32),
        ],
        interpret=interpret,
    )(x, c)
    return ids[:N], d2[:N]
