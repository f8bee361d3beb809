"""Plain reference for k-means: Lloyd's algorithm in float64.

The update rule is the engine's: centroids are kept in float32 between
iterations and an empty cluster keeps its centroid.  Distances use the
expansion |x|^2 - 2 x.c + |c|^2 (|x|^2 does not change the argmin) in
float64.  Points are held as float64 blocks of rows, transposed so the
distance product is one wide matrix product; blocks are worked on by a
few threads (numpy releases the interpreter lock in these calls) and
their sums added in block order, so the result does not depend on the
thread count.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 20


def _partial(xt: np.ndarray, c2: np.ndarray, cc: np.ndarray, k: int):
    """(counts, sums) of one transposed float64 block [dims, rows]."""
    a = (c2 @ xt + cc[:, None]).argmin(0)
    counts = np.bincount(a, minlength=k).astype(np.float64)
    sums = np.stack([np.bincount(a, weights=row, minlength=k)
                     for row in xt], axis=1)
    return counts, sums


def reference(points: np.ndarray, init: np.ndarray, iters: int
              ) -> np.ndarray:
    c = np.array(init, np.float32, copy=True)
    k = len(c)
    workers = max(1, min(8, os.cpu_count() or 1))
    with ThreadPoolExecutor(workers) as pool:
        blocks = list(pool.map(
            lambda lo: np.ascontiguousarray(points[lo:lo + BLOCK].T,
                                            dtype=np.float64),
            range(0, len(points), BLOCK)))
        for _ in range(iters):
            cd = c.astype(np.float64)
            cc = (cd * cd).sum(1)
            parts = list(pool.map(lambda xt: _partial(xt, -2.0 * cd, cc, k),
                                  blocks))
            counts = np.zeros(k)
            sums = np.zeros_like(cd)
            for n, s in parts:
                counts += n
                sums += s
            nz = counts > 0
            c[nz] = (sums[nz] / counts[nz, None]).astype(np.float32)
    return c
