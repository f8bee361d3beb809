from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.kmeans_assign.kernel import kmeans_assign_call
from repro.kernels.kmeans_assign.ref import kmeans_assign_ref
from repro.utils.backend import pallas_interpret


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign(x, c, *, block_n: int = 1024,
                  interpret: bool | None = None):
    if interpret is None:
        interpret = pallas_interpret()
    return kmeans_assign_call(x, c, block_n=block_n, interpret=interpret)


def kmeans_assign_partials(x, c, valid=None, *, block_n: int = 1024,
                           use_kernel: bool | None = None):
    """Per-centroid (sums, counts) partials for the Sphere assign stage.

    x: [N, D] points (possibly padded up to a fixed block shape);
    c: [K, D] centroids; valid: optional bool [N] mask (True = real
    point) so padding rows contribute nothing to the partials.

    Nearest-centroid ids come from the Pallas ``kmeans_assign`` kernel
    on TPU; elsewhere the jnp oracle does the same math without paying
    interpret-mode overhead.  Designed to be called inside a traced
    stage UDF: (x, c, valid) are all dynamic, so one trace serves every
    task shape and every new centroid value across chained jobs.
    Returns (sums [K, D] f32, counts [K] f32).
    """
    if use_kernel is None:
        use_kernel = not pallas_interpret()
    if use_kernel:
        ids, _ = kmeans_assign(x, c, block_n=block_n)
    else:
        ids, _ = kmeans_assign_ref(x, c)
    oh = jax.nn.one_hot(ids, c.shape[0], dtype=jnp.float32)
    if valid is not None:
        oh = oh * valid.astype(jnp.float32)[:, None]
    sums = jnp.dot(oh.T, x.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    counts = oh.sum(0)
    return sums, counts
