"""jit entry points for the bucket-partition kernels.

The wrappers pick interpret mode by backend (Mosaic-compiled lowering on
TPU, interpret on CPU — :func:`repro.utils.backend.pallas_interpret`) and
choose a backend-appropriate block shape when the caller doesn't:

* **interpret (CPU CI)** — every grid step pays a Python interpreter
  pass, so the default is ONE block covering the whole batch; the
  vectorised jaxpr runs once.
* **TPU** — ``block_n = 2048`` keeps a grid step's live set (key words
  ``[k, bn]``, compare state ``[n_bounds, bn]``, see the kernel module's
  VMEM notes) within 2 MiB of VMEM for TeraSort keys and <= 64 buckets.

``bucket_scatter`` takes ``n_valid`` as a *dynamic* argument — callers
pad batches to a fixed shape (e.g. a power-of-two row count) and one
trace serves every record count at that shape.  That is what closes the
engine/kernel throughput gap: the engine's per-worker batch sizes vary
per job, and before this the shuffle re-traced per distinct size.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.bucket_partition.kernel import (bucket_dest_call,
                                                   bucket_partition_call,
                                                   bucket_scatter_call)
from repro.utils.backend import pallas_interpret

# VMEM-conscious default block rows for the TPU lowering (see module
# docstring); interpret mode uses one whole-batch block instead.
ACCEL_BLOCK_N = 2048


@partial(jax.jit, static_argnames=("n_buckets", "block_n", "interpret"))
def bucket_partition(keys, bounds, *, n_buckets: int,
                     block_n: int | None = None,
                     interpret: bool | None = None):
    """(ids [N] int32, hist [n_buckets] int32) for uint32 key rows.

    See :func:`bucket_partition_call` for the comparison contract.
    """
    if interpret is None:
        interpret = pallas_interpret()
    if block_n is None:
        block_n = keys.shape[0] if interpret else ACCEL_BLOCK_N
    return bucket_partition_call(keys, bounds, n_buckets=n_buckets,
                                 block_n=block_n, interpret=interpret)


@partial(jax.jit, static_argnames=("n_buckets", "block_n", "interpret"))
def bucket_scatter(data, keys, bounds, n_valid, *, n_buckets: int,
                   block_n: int | None = None,
                   interpret: bool | None = None):
    """Device-resident stable scatter into bucket-contiguous order.

    ``data [N, width] uint8`` records with ``keys [N(, k)] uint32`` rows;
    rows at positions >= ``n_valid`` (dynamic) are shape padding and land
    after every real bucket.  Returns ``(out [N, width], hist
    [n_buckets])`` — see :func:`bucket_scatter_call`.  Bucket ids never
    exist host-side; sync ``hist`` once to learn the bucket boundaries.
    """
    if interpret is None:
        interpret = pallas_interpret()
    if block_n is None:
        block_n = data.shape[0] if interpret else ACCEL_BLOCK_N
    return bucket_scatter_call(data, keys, bounds, n_valid,
                               n_out=n_buckets, block_n=block_n,
                               interpret=interpret)


@partial(jax.jit, static_argnames=("n_buckets", "block_n", "interpret"))
def bucket_dest(keys, bounds, n_valid, *, n_buckets: int,
                block_n: int | None = None,
                interpret: bool | None = None):
    """Scatter destinations without moving any data.

    Returns ``(dest [Np] int32, hist [n_buckets] int32)`` — the stable
    bucket-contiguous output position of every key row, padded rows
    included (see :func:`bucket_dest_call`).  For callers that invert
    the permutation and move rows themselves — on CPU a host-side numpy
    inversion runs at memcpy speed where XLA's [Np] scatter crawls at
    ~40ns/element, which is why the CPU shuffle path stops here.
    """
    if interpret is None:
        interpret = pallas_interpret()
    if block_n is None:
        block_n = keys.shape[0] if interpret else ACCEL_BLOCK_N
    return bucket_dest_call(keys, bounds, n_valid, n_out=n_buckets,
                            block_n=block_n, interpret=interpret)
