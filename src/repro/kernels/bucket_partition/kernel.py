"""Bucket partition + device scatter kernels — the TeraSort shuffle hot loop.

Two Pallas entry points share one comparison contract:

* :func:`bucket_partition_call` — bucket ids + per-bucket histogram (the
  original analysis pass; ids are returned to the caller).
* :func:`bucket_scatter_call` — the device-resident shuffle: ids, per-block
  histograms and intra-block stable ranks in one kernel pass, then a pure
  device epilogue (exclusive scans + one scatter) that lands the records in
  bucket-contiguous order.  Bucket ids never reach the host; the only value
  a caller needs to sync is the final [n_buckets] histogram.

**Comparison contract (both kernels).**  Keys and boundaries are rows of
``k`` big-endian uint32 words compared lexicographically — ``k = 1`` is the
classic single-word case, 10-byte TeraSort keys use ``k = 3``.  ``k`` is
static, so the word loop unrolls at trace time into ``k`` vectorised
compares against the boundary table pinned in VMEM.  When boundary byte
lengths vary, callers append a trailing *length word* to both keys and
boundaries (see ``RecordBatch.key_words``): zero-padded words can tie where
the byte strings differ, and the length word reproduces Python's
shorter-prefix-sorts-first ``bytes`` ordering exactly.  The bucket rule is
strict: ``id = #{j : bounds[j] < key}``, clamped to ``n_out - 1`` when the
boundary table implies more buckets than the caller wants (mirroring the
bytes reference's ``min(lo, n - 1)``).

**Stability guarantee (scatter).**  Grid blocks execute in input order and
the intra-block rank is a prefix count over the block's rows, so two
records in the same bucket keep their input order in the scattered output
— exactly the bytes backend's append order.  Rows at positions >=
``n_valid`` (shape padding) are routed to a trash bucket *after* every
real bucket, so the first ``sum(hist)`` output rows are the real records.

**Layout.**  Both kernels are lane-major: the wrappers hand Mosaic key
words as ``[k, N]`` int32 (rows along the 128-wide lane axis, each
uint32 word sign-flipped so signed compares keep its order) and the
boundary table as ``[k, n_bounds, 1]`` columns.  A grid step compares a
``[1, bn]`` key row against a ``[n_bounds, 1]`` boundary column per word,
so the compare state is a dense ``[n_bounds, bn]`` tile, ids reduce over
sublanes, and every block shape is tiling-legal: ``(k, bn)`` / ``(1, bn)``
with ``bn`` a multiple of 128 (or the whole batch), per-block histograms
as ``(n_out + 1, 1)`` columns with a squeezed leading block dim.

**Rank scan.**  The intra-block rank is an inclusive running count of
the ``[n_out + 1, bn]`` one-hot along lanes, taken one 128-lane sub-tile
at a time: a ``[n_out + 1, 128] x [128, 128]`` matmul against an
upper-triangular ones matrix on the MXU (exact — 0/1 operands, sums <=
128, f32 accumulation) plus the running count carried from earlier
sub-tiles.  Interpret mode runs the same loop on 32-lane sub-tiles.

**Block shapes / VMEM.**  A grid step holds the ``[k, bn]`` key words, the
``[k, n_bounds, 1]`` boundary columns, the ``[n_bounds, bn]`` compare
state and, in the analysis kernel, the ``[n_buckets, bn]`` one-hot (the
scatter kernel only ever holds one ``[n_out + 1, 128]`` one-hot sub-tile
plus the ``[128, 128]`` triangle), all double-buffered where they are
blocks.  At ``bn = 2048``, 4-word keys and 64 buckets the v5e compiler
accepts the scatter kernel under a 1 MiB VMEM limit and the analysis
kernel under 2 MiB (neither fits half that), against a 16 MiB default
scoped limit.  In interpret mode (CPU CI) every grid step
pays a Python interpreter pass, so callers use ONE block (``bn = n``) —
that is what the ``ops.py`` wrappers default to per backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# rank-scan sub-tile width, which is also the granule block rows round
# up to once a block spans more than one sub-tile: one vreg / MXU tile
# of lanes
_LANES = 128


def _lane_major(keys: jax.Array, bounds: jax.Array):
    """[N] / [N, k] uint32 key rows and [n_bounds] / [n_bounds, k]
    boundary rows -> the kernels' operands: int32 key words ``[k, N]``
    and boundary columns ``[k, n_bounds, 1]``, every word XOR-ed with
    the sign bit so signed int32 order equals unsigned word order."""
    if keys.ndim == 1:
        keys = keys[:, None]
    if bounds.ndim == 1:
        bounds = bounds[:, None]
    if keys.shape[1] != bounds.shape[1]:
        raise ValueError(f"keys have {keys.shape[1]} words per row but "
                         f"bounds have {bounds.shape[1]}")

    def signed(w):
        return jax.lax.bitcast_convert_type(
            w.astype(jnp.uint32) ^ jnp.uint32(0x80000000), jnp.int32)

    return signed(keys).T, signed(bounds).T[:, :, None]


def _block_rows(block_n: int, n: int, lanes: int) -> int:
    """Rows per grid step: ``block_n`` clipped to the batch, rounded up
    to whole ``lanes`` sub-tiles once it spans more than one (a block of
    at most one sub-tile scans in one step, so any size is legal)."""
    bn = max(1, min(block_n, n))
    return bn if bn <= lanes else -(-bn // lanes) * lanes


def _compare_ids(keys_ref, bounds_ref):
    """Strict lexicographic bucket ids: ``#{j : bounds[j] < key}``.

    ``keys_ref [k, bn]`` key words vs ``bounds_ref [k, n_bounds, 1]``
    boundary columns; scans words while prefixes tie (the loop is over
    static k, so it unrolls).  Returns ``[1, bn]`` int32.
    """
    k, bn = keys_ref.shape
    n_bounds = bounds_ref.shape[1]
    lt = jnp.zeros((n_bounds, bn), jnp.bool_)
    eq = jnp.ones((n_bounds, bn), jnp.bool_)
    for w in range(k):
        kw = keys_ref[w:w + 1, :]               # [1, bn]
        bw = bounds_ref[w]                      # [n_bounds, 1]
        lt = lt | (eq & (bw < kw))
        eq = eq & (bw == kw)
    return jnp.sum(lt.astype(jnp.int32), axis=0, keepdims=True)


def _kernel(keys_ref, bounds_ref, ids_ref, hist_ref, *, n_buckets: int,
            n_valid: int, bn: int):
    """Analysis pass: ids + one accumulated histogram.

    The histogram accumulates in the output ref across the sequentially-
    executed grid (TPU grid semantics), so no host-side reduction is
    needed.  Padded tail keys (positions >= n_valid) land in bucket 0
    with zero histogram weight.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    ids = _compare_ids(keys_ref, bounds_ref)
    pos = i * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    valid = pos < n_valid
    ids = jnp.where(valid, ids, 0)
    ids_ref[...] = ids
    onehot = valid & (ids == jax.lax.broadcasted_iota(
        jnp.int32, (n_buckets, 1), 0))          # [n_buckets, bn]
    hist_ref[...] += jnp.sum(onehot.astype(jnp.int32), axis=1,
                             keepdims=True)


def bucket_partition_call(keys: jax.Array, bounds: jax.Array, *,
                          n_buckets: int, block_n: int = 2048,
                          interpret: bool = False):
    """keys: [N] or [N, k] uint32; bounds: [n_buckets-1] or [n_buckets-1, k]
    uint32 rows, sorted lexicographically.

    Returns (ids [N] int32, hist [n_buckets] int32)."""
    kt, bt = _lane_major(keys, bounds)
    k, N = kt.shape
    bn = _block_rows(block_n, N, _LANES)
    pad = (-N) % bn
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, pad)))
    Np = kt.shape[1]

    kern = functools.partial(_kernel, n_buckets=n_buckets, n_valid=N, bn=bn)
    ids, hist = pl.pallas_call(
        kern,
        grid=(Np // bn,),
        in_specs=[
            pl.BlockSpec((k, bn), lambda i: (0, i)),
            pl.BlockSpec(bt.shape, lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((n_buckets, 1), lambda i: (0, 0)),  # accumulated
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
            jax.ShapeDtypeStruct((n_buckets, 1), jnp.int32),
        ],
        interpret=interpret,
    )(kt, bt)
    return ids[0, :N], hist[:, 0]


def _scatter_kernel(valid_ref, keys_ref, bounds_ref, ids_ref, rank_ref,
                    bhist_ref, *, n_out: int):
    """Scatter pass: per-block ids, intra-block stable ranks, block hists.

    Unlike :func:`_kernel`, validity arrives as a *dynamic* [1, bn] int32
    mask input, so one trace serves every record count (and any
    interleaving of padding — e.g. several resident pieces stacked with
    their junk tails in place) at a fixed padded shape — the property
    that keeps the engine path compile-once.  Masked rows get id
    ``n_out`` (the trash bucket ordered after every real bucket); real
    ids are clamped to ``n_out - 1`` when the boundary table implies
    more buckets.

    The intra-block rank is a same-bucket prefix count: with ``incl``
    the inclusive running one-hot count, ``rank[r] = incl[ids[r], r] -
    1`` (an elementwise masked sum over buckets — no gather inside the
    kernel), built one lane sub-tile at a time (see the module
    docstring).  ``bhist_ref`` gets this block's ``[n_out + 1, 1]``
    bucket counts; the epilogue turns block hists into global offsets.
    """
    raw = _compare_ids(keys_ref, bounds_ref)
    ids = jnp.where(valid_ref[...] != 0, jnp.minimum(raw, n_out - 1), n_out)
    ids_ref[...] = ids
    bn = ids.shape[1]
    lanes = min(bn, _LANES)
    bucket = jax.lax.broadcasted_iota(jnp.int32, (n_out + 1, 1), 0)
    upper = (jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 1)
             ).astype(jnp.float32)

    def scan(sub_ids, carry):                   # carry: [n_out + 1, 1]
        """(ranks, carry + counts) of one ``[1, lanes]`` sub-tile."""
        onehot = (sub_ids == bucket).astype(jnp.int32)
        incl = carry + jnp.dot(onehot.astype(jnp.float32), upper,
                               preferred_element_type=jnp.float32
                               ).astype(jnp.int32)
        return (jnp.sum(onehot * (incl - 1), axis=0, keepdims=True),
                carry + jnp.sum(onehot, axis=1, keepdims=True))

    zero = jnp.zeros((n_out + 1, 1), jnp.int32)
    if bn == lanes:
        # one sub-tile: no loop — Mosaic needs a dynamic lane offset it
        # can prove 128-aligned, which a block under 128 rows never is
        rank_ref[...], bhist_ref[...] = scan(ids, zero)
        return

    def tile(t, carry):
        off = pl.multiple_of(t * lanes, lanes)
        rank_ref[:, pl.ds(off, lanes)], carry = scan(
            ids_ref[:, pl.ds(off, lanes)], carry)
        return carry

    bhist_ref[...] = jax.lax.fori_loop(0, bn // lanes, tile, zero)


def bucket_dest_call(keys: jax.Array, bounds: jax.Array, n_valid, *,
                     n_out: int, block_n: int = 2048,
                     interpret: bool = False):
    """Destination indices + histogram of the stable counting scatter.

    ``keys``: [N] or [N, k] uint32 key rows; ``bounds``: [n_bounds] or
    [n_bounds, k] sorted boundary rows; ``n_valid``: a dynamic scalar —
    the leading ``n_valid`` rows are real, the rest shape padding, which
    goes to the trash bucket after every real bucket.

    Returns ``(dest [Np] int32, hist [n_out] int32)`` where ``Np`` is
    ``N`` rounded up to a multiple of the grid block (``block_n``, itself
    rounded up to whole rank-scan sub-tiles) and ``dest[r]`` is the
    bucket-contiguous, input-stable output position of row ``r`` —
    ``dest`` is a permutation of ``[0, Np)`` with every valid row landing
    below ``hist.sum()``.  The destination of record ``r`` in block ``i``
    with bucket ``b`` is ``bucket_start[b] + count of b in blocks < i +
    intra-block rank`` — the classic three-level exclusive-scan scatter,
    with the two outer scans (over buckets and over blocks) done by the
    XLA epilogue on the kernel's per-block histograms.  This is the
    data-free half of :func:`bucket_scatter_call`.
    """
    kt, bt = _lane_major(keys, bounds)
    k, N = kt.shape
    bn = _block_rows(block_n, N, _LANES)
    pad = (-N) % bn
    if pad:  # rows past n_valid are trash-bucketed, so padding is benign
        kt = jnp.pad(kt, ((0, 0), (0, pad)))
    Np = kt.shape[1]
    nb = Np // bn
    valid = (jax.lax.iota(jnp.int32, Np)
             < jnp.asarray(n_valid).astype(jnp.int32)).astype(jnp.int32)

    kern = functools.partial(_scatter_kernel, n_out=n_out)
    ids, rank, bhist = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((k, bn), lambda i: (0, i)),
            pl.BlockSpec(bt.shape, lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((None, n_out + 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
            jax.ShapeDtypeStruct((nb, n_out + 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(valid[None, :], kt, bt)
    ids, rank, bhist = ids[0], rank[0], bhist[:, :, 0]

    total = jnp.sum(bhist, axis=0)              # [n_out + 1]
    starts = jnp.cumsum(total) - total          # exclusive bucket starts
    if nb == 1:
        # single grid block (the CPU/interpret default): the inter-block
        # exclusive scan is identically zero, so skip its 2-D gather
        dest = starts[ids] + rank
    else:
        blk_excl = jnp.cumsum(bhist, axis=0) - bhist  # [nb, n_out + 1]
        block_of = jax.lax.iota(jnp.int32, Np) // bn
        dest = starts[ids] + blk_excl[block_of, ids] + rank
    return dest, total[:n_out]


def bucket_scatter_call(data: jax.Array, keys: jax.Array, bounds: jax.Array,
                        n_valid, *, n_out: int, block_n: int = 2048,
                        interpret: bool = False):
    """Device-resident bucketed scatter (stable counting scatter).

    ``data``: [N, width] uint8 records; ``keys``: [N] or [N, k] uint32 key
    rows for the same records; ``bounds``: [n_bounds] or [n_bounds, k]
    sorted boundary rows; ``n_valid``: how many leading rows are real
    (the rest are shape padding and scatter to the tail).

    Returns ``(out [N, width] uint8, hist [n_out] int32)`` where
    ``out[:hist.sum()]`` holds the real records in bucket-contiguous,
    input-stable order — bucket ``b`` occupies rows
    ``[sum(hist[:b]), sum(hist[:b+1]))``.  Everything stays on device;
    the caller decides when (if ever) to sync ``hist``.

    Destination indices come from :func:`bucket_dest_call`; the move
    here inverts the destination permutation with a [Np] int32 scatter,
    then gathers the wide uint8 rows (XLA lowers the row gather several
    times faster than the equivalent row scatter).
    """
    if keys.ndim == 1:
        keys = keys[:, None]
    if data.shape[0] != keys.shape[0]:
        raise ValueError(f"data has {data.shape[0]} rows but keys have "
                         f"{keys.shape[0]}")
    N = data.shape[0]
    dest, hist = bucket_dest_call(keys, bounds, n_valid, n_out=n_out,
                                  block_n=block_n, interpret=interpret)
    Np = dest.shape[0]
    if Np != N:
        data = jnp.pad(data, ((0, Np - N), (0, 0)))
    perm = jnp.zeros((Np,), jnp.int32).at[dest].set(
        jax.lax.iota(jnp.int32, Np), unique_indices=True)
    out = jnp.take(data, perm, axis=0)
    return out[:N], hist
