"""Partitioners for the Sphere shuffle — bytes reference + array backend.

Each partitioner is a callable ``(record: bytes, n: int) -> int`` (the
bytes reference path, unchanged engine protocol) and additionally exposes

* ``kernel_inputs(batch, n)`` — the (keys, bounds) uint32 rows the Pallas
  kernels compare, or ``None`` when the batch must take the host loop;
* ``bucket_ids(batch, n)`` — ids + histogram via ``bucket_partition``
  (the analysis path: ids come back to the caller);
* :func:`scatter_dispatch` / :func:`scatter_batch` — the engine shuffle
  path: the ``bucket_scatter`` kernel lands records bucket-contiguously
  ON DEVICE (stable counting scatter), and the only host sync is the
  final [n] histogram that slices the contiguous result into per-bucket
  batches (the same counts the planner's movement pricing needs).
  ``scatter_dispatch`` enqueues that work without blocking and defers
  the histogram sync into :meth:`ScatterDispatch.harvest`, so a caller
  shuffling many batches (the engine's per-worker loop) dispatches them
  all and pays ONE barrier per shuffle round; ``scatter_batch`` is the
  dispatch-plus-immediate-harvest convenience.  Batches are padded to a
  power-of-two row count and ``n_valid`` is dynamic, so one kernel trace
  serves every batch size at a given padded shape — this is what keeps
  engine-level throughput at kernel speed instead of re-tracing per
  per-worker batch size.

The kernel's rule is ``bucket = #{i : bounds[i] < key}``; both
partitioners phrase their bytes-side decision with exactly that rule so
the two paths agree record-for-record:

* ``HashPartitioner`` hashes the key prefix with FNV-1a 32-bit (scalar
  and vectorised twins in :mod:`repro.core.records`) and buckets the
  hash against ``uniform_hash_bounds``.
* ``RangePartitioner`` keeps the classic TeraSort binary search over
  sampled boundaries.  Its array path compares rows of big-endian uint32
  words lexicographically (the kernel's multi-word compare), covering
  boundaries of any length — 10-byte TeraSort keys use 3 words.  When
  boundary lengths vary, a trailing length word reproduces Python's
  shorter-prefix-sorts-first bytes ordering exactly, so the kernel path
  never needs the per-record host fallback.
"""
from __future__ import annotations

from bisect import bisect_left
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.records import (RecordBatch, StackedBatch,  # noqa: F401
                                _pow2_rows, _quarter_rows, fnv1a32,
                                scatter_by_ids, uniform_hash_bounds)
from repro.kernels.bucket_partition import (bucket_dest, bucket_partition,
                                            bucket_scatter)
from repro.utils.backend import pallas_interpret


def _kernel_partition(keys: jax.Array, bounds_u32: np.ndarray, n: int,
                      *, block_n: int | None = None,
                      interpret: bool | None = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """bucket_partition over uint32 keys with degenerate-shape handling.

    ``keys`` is [N] (single-word) or [N, k] (multi-word rows) with
    ``bounds_u32`` shaped to match.  The Pallas kernel needs at least one
    boundary; n == 1 (or an empty boundary list) means every record lands
    in bucket 0.  When there are more boundaries than n - 1 the tail
    buckets are clamped onto n - 1, mirroring the ``min(lo, n - 1)`` in
    the bytes reference.
    """
    nrec = keys.shape[0]
    if nrec == 0 or n <= 1 or len(bounds_u32) == 0:
        ids = jnp.zeros((nrec,), jnp.int32)
        hist = jnp.zeros((max(n, 1),), jnp.int32).at[0].set(nrec)
        return ids, hist
    nb = len(bounds_u32) + 1
    ids, hist = bucket_partition(keys, jnp.asarray(bounds_u32), n_buckets=nb,
                                 block_n=block_n, interpret=interpret)
    if nb > n:  # clamp overflow buckets, fold their histogram tail
        ids = jnp.minimum(ids, n - 1)
        hist = hist[:n].at[n - 1].add(hist[n:].sum())
    return ids, hist


class HashPartitioner:
    """FNV-1a hash of the first ``key_bytes`` bytes -> uniform bucket."""

    def __init__(self, key_bytes: int = 8):
        self.key_bytes = key_bytes
        self._bounds: Dict[int, List[int]] = {}

    def _bounds_for(self, n: int) -> List[int]:
        if n not in self._bounds:
            self._bounds[n] = uniform_hash_bounds(n).tolist()
        return self._bounds[n]

    def __call__(self, record: bytes, n: int) -> int:
        h = fnv1a32(record[:self.key_bytes])
        return bisect_left(self._bounds_for(n), h)

    def kernel_inputs(self, batch: RecordBatch, n: int
                      ) -> Tuple[jax.Array, np.ndarray]:
        """(keys, bounds) uint32 rows for the Pallas kernels."""
        return batch.hash_keys_u32(self.key_bytes), uniform_hash_bounds(n)

    def scatter_spec(self, batch: RecordBatch, n: int):
        """(static key spec, bounds) for the jitted device scatter, or
        None when every record belongs in bucket 0."""
        if n <= 1:
            return None
        return ("hash", self.key_bytes), uniform_hash_bounds(n)

    def bucket_ids(self, batch: RecordBatch, n: int, *,
                   block_n: int | None = None, interpret: bool | None = None
                   ) -> Tuple[jax.Array, jax.Array]:
        keys, bounds = self.kernel_inputs(batch, n)
        return _kernel_partition(keys, bounds, n,
                                 block_n=block_n, interpret=interpret)


class RangePartitioner:
    """TeraSort-style: bucket by key position among sorted boundaries."""

    def __init__(self, boundaries: Sequence[bytes]):
        self.bnd = list(boundaries)

    def __call__(self, record: bytes, n: int) -> int:
        bnd = self.bnd
        key = record[:len(bnd[0])] if bnd else record
        lo, hi = 0, len(bnd)
        while lo < hi:
            mid = (lo + hi) // 2
            if key > bnd[mid]:
                lo = mid + 1
            else:
                hi = mid
        return min(lo, n - 1)

    def bounds_words(self, n_words: int, lengths: bool) -> np.ndarray:
        """Boundaries as [n-1, k] big-endian uint32 word rows, zero-padded
        to ``n_words`` words, plus a trailing byte-length word when
        ``lengths`` is set (the variable-length tiebreak)."""
        rows = []
        for b in self.bnd:
            padded = b[:4 * n_words].ljust(4 * n_words, b"\0")
            row = [int.from_bytes(padded[4 * i:4 * i + 4], "big")
                   for i in range(n_words)]
            if lengths:
                row.append(len(b))
            rows.append(row)
        return np.array(rows, dtype=np.uint32)

    def kernel_inputs(self, batch: RecordBatch, n: int
                      ) -> Tuple[jax.Array, np.ndarray]:
        """(keys, bounds) uint32 rows for the Pallas kernels.

        Multi-word lexicographic compare: boundary bytes and key
        prefixes become rows of big-endian uint32 words, so boundaries
        of any length stay on the kernel path.  A record's comparison
        key is its first len(bnd[0]) bytes (clipped to the record), so
        when any boundary length differs from that key length the
        zero-padded words can tie where the byte strings differ — a
        trailing length word reproduces bytes ordering exactly.
        """
        if not self.bnd:
            return batch.keys_u32(4), np.empty(0)
        key_len = min(len(self.bnd[0]), batch.record_size)
        width = max(key_len, max(len(b) for b in self.bnd))
        n_words = max(1, -(-width // 4))
        need_len = any(len(b) != key_len for b in self.bnd)
        keys = batch.key_words(key_len, n_words=n_words,
                               length_word=key_len if need_len else None)
        return keys, self.bounds_words(n_words, lengths=need_len)

    def scatter_spec(self, batch: RecordBatch, n: int):
        """(static key spec, bounds) for the jitted device scatter —
        same word-row construction as :meth:`kernel_inputs`, but the key
        extraction itself runs *inside* the jitted scatter so the whole
        shuffle of a padded batch is one compiled call."""
        if not self.bnd or n <= 1:
            return None
        key_len = min(len(self.bnd[0]), batch.record_size)
        width = max(key_len, max(len(b) for b in self.bnd))
        n_words = max(1, -(-width // 4))
        need_len = any(len(b) != key_len for b in self.bnd)
        return (("range", key_len, n_words, key_len if need_len else None),
                self.bounds_words(n_words, lengths=need_len))

    def bucket_ids(self, batch: RecordBatch, n: int, *,
                   block_n: int | None = None, interpret: bool | None = None
                   ) -> Tuple[jax.Array, jax.Array]:
        keys, bounds = self.kernel_inputs(batch, n)
        return _kernel_partition(keys, bounds, n,
                                 block_n=block_n, interpret=interpret)


class ReducePartitioner:
    """Every record to bucket 0 — the reduction shuffle (e.g. k-means
    partials folding on one worker).  The array path computes ids and
    histogram directly instead of dropping to the per-record host loop
    that arbitrary ``lambda r, n: 0`` callables would take, so reduce
    stages stay on the array fast path even for a single tiny batch of
    partials."""

    def __call__(self, record: bytes, n: int) -> int:
        return 0

    def bucket_ids(self, batch: RecordBatch, n: int, *,
                   block_n: int | None = None, interpret: bool | None = None
                   ) -> Tuple[jax.Array, jax.Array]:
        nrec = batch.num_records
        ids = jnp.zeros((nrec,), jnp.int32)
        hist = jnp.zeros((max(n, 1),), jnp.int32).at[0].set(nrec)
        return ids, hist


def hash_partitioner(key_bytes: int = 8) -> HashPartitioner:
    return HashPartitioner(key_bytes)


def reduce_partitioner() -> ReducePartitioner:
    return ReducePartitioner()


def range_partitioner(boundaries: Sequence[bytes]) -> RangePartitioner:
    return RangePartitioner(boundaries)


def _host_partition(batch: RecordBatch, partitioner, n: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Per-record host loop — the correctness fallback for partitioners
    the kernel cannot express."""
    ids_np = np.fromiter((partitioner(r, n) for r in batch.to_records()),
                         np.int32, count=batch.num_records)
    hist = np.bincount(ids_np, minlength=n).astype(np.int32)
    return jnp.asarray(ids_np), jnp.asarray(hist)


def partition_batch(batch: RecordBatch, partitioner, n: int, *,
                    block_n: int | None = None, interpret: bool | None = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """(ids, hist) for a batch under any engine partitioner.

    Array-aware partitioners go through the Pallas kernel; arbitrary
    ``(record, n) -> int`` callables fall back to a per-record host loop
    so the array backend stays correct for custom partitioners.
    """
    batch = batch.compact()  # analysis keys are host-visible: no junk rows
    if hasattr(partitioner, "bucket_ids"):
        return partitioner.bucket_ids(batch, n, block_n=block_n,
                                      interpret=interpret)
    return _host_partition(batch, partitioner, n)


def shuffle_batch(batch: RecordBatch, partitioner, n: int, *,
                  block_n: int | None = None, interpret: bool | None = None
                  ) -> List[RecordBatch]:
    """Partition + host-driven scatter: one kernel call, one host
    argsort, n gathers.  The engine uses :func:`scatter_batch` (fully
    device-resident) instead; this path remains for custom callable
    partitioners and as the ids-visible reference."""
    ids, hist = partition_batch(batch, partitioner, n, block_n=block_n,
                                interpret=interpret)
    return scatter_by_ids(batch, ids, hist)


# _pow2_rows / _quarter_rows live in repro.core.records (shared with
# StackedBatch.pack) and are re-exported above for their historical home.


def _single_bucket_pieces(batch: RecordBatch, n: int) -> List[RecordBatch]:
    return [batch] + [RecordBatch.empty(batch.record_size)
                      for _ in range(max(n, 1) - 1)]


@partial(jax.jit,
         static_argnames=("n_buckets", "key_spec", "block_n", "interpret"))
def _scatter_padded(data, bounds, n_valid, *, n_buckets: int, key_spec,
                    block_n: int | None, interpret: bool):
    """One compiled call for the whole padded-batch shuffle: key
    extraction (``key_spec`` is static — ``("hash", key_bytes)`` or
    ``("range", key_len, n_words, length_word)``), the bucket_scatter
    kernel, and its scan/scatter epilogue.  Re-traces only per
    (padded shape, key spec, n_buckets) — never per record count,
    because ``n_valid`` is dynamic."""
    keys = _extract_keys(data, key_spec)
    return bucket_scatter(data, keys, bounds, n_valid, n_buckets=n_buckets,
                          block_n=block_n, interpret=interpret)


def _cpu_block_n(rows: int) -> int | None:
    """Grid size for the interpret (CPU) kernel, or None for a single
    block.  The in-kernel rank scan is O(rows log rows) *per block*, so
    gridding a large input into 64k blocks beats one giant block by
    ~25% (measured: four 64k blocks vs one 256k block) and by several
    x at the 1M single-batch shape; below ~1.5 blocks the
    pad-to-block-multiple junk rows would outweigh the saved scan
    levels."""
    return 65536 if rows > 98304 else None


def _extract_keys(data, key_spec):
    batch = RecordBatch(data)
    if key_spec[0] == "hash":
        return batch.hash_keys_u32(key_spec[1])
    _, key_len, n_words, length_word = key_spec
    return batch.key_words(key_len, n_words=n_words, length_word=length_word)


@partial(jax.jit,
         static_argnames=("n_buckets", "key_spec", "block_n", "interpret"))
def _scatter_dest_padded(data, bounds, n_valid, *, n_buckets: int, key_spec,
                         block_n: int | None, interpret: bool):
    """The data-free twin of :func:`_scatter_padded`: key extraction +
    kernel + scan epilogue, stopping at the destination vector instead
    of moving the rows.  Used on CPU, where XLA lowers the [rows]
    permutation-inverting scatter at ~40ns/element while numpy's fancy
    assignment inverts it host-side at memcpy speed — so the rows are
    moved by a plain device gather against the host-inverted
    permutation at harvest time (see :meth:`ScatterDispatch.harvest`).
    """
    keys = _extract_keys(data, key_spec)
    return bucket_dest(keys, bounds, n_valid, n_buckets=n_buckets,
                       block_n=block_n, interpret=interpret)


@partial(jax.jit,
         static_argnames=("n_buckets", "key_spec", "block_n", "interpret"))
def _scatter_dest_segments(pieces, bounds, n_valids, *, n_buckets: int,
                           key_spec, block_n: int | None, interpret: bool):
    """Segmented twin of :func:`_scatter_dest_padded` for a WHOLE round:
    ``pieces`` is a tuple of s [rows, width] resident pieces at one
    ladder shape, junk tails in place — and ``n_valids`` [s] their
    dynamic valid counts.  The stack happens INSIDE the trace: an eager
    ``jnp.stack`` over s arrays dispatches s reshapes plus a
    concatenate (~1ms of pure host overhead per piece on CPU — it was
    the single largest line of a profiled round), while here XLA sees
    one fused concatenate.  Rows flatten in piece order and each
    piece's junk tail is masked into the trash bucket, so the
    destination vector orders valid rows bucket-major then
    global-input-major across the whole stack — exactly the order a
    concat of the pieces would have produced, without ever
    materialising the concat eagerly.  Returns the flattened data
    alongside (dest, hist) so the harvest gathers straight off it.
    Re-traces only per (piece count, piece shape, key spec, n_buckets)
    — ``n_valids`` is dynamic.

    The flatten is a direct 2D ``jnp.concatenate``, NOT stack+reshape:
    XLA:CPU turns the [s, rows, width] stack of 2D operands plus the
    flattening reshape into a program ~3x slower than the plain
    concatenate (measured 61-79ms vs 20-25ms for 33 x [6144, 100]
    uint8 pieces), while the 2D concat compiles to one linear copy."""
    rows, width = pieces[0].shape
    s = len(pieces)
    data = jnp.concatenate(pieces, axis=0)
    keys = _extract_keys(data, key_spec)
    pos = jax.lax.iota(jnp.int32, s * rows)
    valid = (pos % rows) < n_valids[pos // rows]
    dest, hist = bucket_dest(keys, bounds, valid.astype(jnp.int32),
                             n_buckets=n_buckets, block_n=block_n,
                             interpret=interpret)
    return data, dest, hist


@dataclass
class ScatterDispatch:
    """The in-flight half of a dispatch-then-sync shuffle.

    :func:`scatter_dispatch` returns one of these per batch after
    enqueueing all device work (pad, key extraction, kernel, epilogue)
    WITHOUT blocking.  A caller shuffling many batches dispatches them
    all first — the device queue stays full — then fetches every
    dispatch's :attr:`sync_arrays` in one host barrier and calls
    :meth:`harvest` with the synced values.  ``harvest()`` with no
    argument syncs this dispatch's own metadata (the compatibility path
    :func:`scatter_batch` uses).

    A pending dispatch is in one of two shapes, per backend:

    * **compiled (TPU)** — ``out`` holds the bucket-contiguous rows
      (the kernel's device epilogue already moved them); harvest slices
      it by the synced histogram.
    * **host-invert (CPU)** — ``src`` holds the untouched padded block
      and ``dest`` the destination vector; harvest inverts the
      permutation host-side (numpy fancy assignment at memcpy speed,
      where XLA:CPU's scatter crawls at ~40ns/element) and gathers each
      bucket's rows off ``src`` directly — only valid rows ever move.

    Either way the barrier is ONE ``device_get`` per round of [n]-sized
    (plus, on CPU, [rows]-sized int32) metadata — record bytes stay on
    device.  Degenerate/fallback shapes resolve at dispatch time into
    ``pieces``: those harvest for free, and ``host_syncs`` records any
    sync the fallback already paid (1 for the per-record host loop, else
    0), so executor-level sync accounting stays truthful.
    """

    n: int                                        # bucket count
    pieces: Optional[List[RecordBatch]] = None    # resolved at dispatch
    out: Optional[jax.Array] = None               # compiled: scattered rows
    src: Optional[jax.Array] = None               # host-invert: padded block
    dest: Optional[jax.Array] = None              # host-invert: [rows] dest
    hist: Optional[jax.Array] = None              # pending [n] counts
    host_syncs: int = field(default=0)            # syncs paid at dispatch

    @property
    def pending(self) -> bool:
        """True when metadata must reach the host before slicing."""
        return self.pieces is None

    @property
    def sync_arrays(self):
        """The device values the round barrier must fetch: the [n]
        histogram, plus the destination vector on the host-invert path."""
        return (self.hist,) if self.dest is None else (self.hist, self.dest)

    def harvest(self, synced=None) -> List[RecordBatch]:
        """Per-bucket batches.  ``synced`` is the already-fetched
        :attr:`sync_arrays` tuple (numpy); omitted, the dispatch syncs
        its own."""
        if self.pieces is not None:
            return self.pieces
        if synced is None:
            synced = jax.device_get(self.sync_arrays)   # host sync
        hist = np.asarray(synced[0])
        offsets = np.concatenate([[0], np.cumsum(hist)])
        if self.out is not None:
            self.pieces = [RecordBatch(self.out[offsets[i]:offsets[i + 1]])
                           for i in range(self.n)]
        else:
            dest = np.asarray(synced[1])
            perm = np.empty(dest.shape[0], np.int32)
            perm[dest] = np.arange(dest.shape[0], dtype=np.int32)
            self.pieces = [
                RecordBatch(jnp.take(self.src,
                                     jnp.asarray(perm[offsets[i]:
                                                      offsets[i + 1]]),
                                     axis=0))
                for i in range(self.n)]
        return self.pieces


def scatter_dispatch(batch: RecordBatch, partitioner, n: int, *,
                     pad_block: int = 4096, block_n: int | None = None,
                     interpret: bool | None = None) -> ScatterDispatch:
    """Enqueue the device-resident shuffle of one batch; never blocks.

    The fast path places the batch in a power-of-two-ladder block
    (floored at ``pad_block``; a padding-resident batch at a usable
    shape is reused as-is, junk tail included) and runs ONE jitted call
    — key extraction, ``bucket_scatter`` kernel and scan/scatter
    epilogue — with the real row count as a *dynamic* argument: records
    land bucket-contiguously on device without the bucket ids ever
    reaching the host, and one trace serves every batch size at a given
    padded shape.  The ONE host sync each batch ever needs is the final
    [n] histogram, deferred into :meth:`ScatterDispatch.harvest` so a
    caller with many batches pays it once for all of them.

    Within a bucket records keep input order (the kernel's stability
    guarantee), matching the bytes backend's append order exactly.
    Degenerate shapes (empty batch, single bucket, no boundaries) take a
    zero-kernel shortcut; partitioners without ``scatter_spec``
    (arbitrary ``(record, n) -> int`` callables) fall back to the
    host-loop + host-argsort path so correctness never depends on the
    kernel being expressible.
    """
    nrec = batch.num_records
    if n <= 1:
        return ScatterDispatch(n, pieces=[batch])
    if nrec == 0:
        empty = [batch.take(jnp.zeros((0,), jnp.int32)) for _ in range(n)]
        return ScatterDispatch(n, pieces=empty)
    if isinstance(partitioner, ReducePartitioner):
        return ScatterDispatch(n, pieces=_single_bucket_pieces(batch, n))
    if not hasattr(partitioner, "scatter_spec"):
        ids, hist = _host_partition(batch, partitioner, n)
        return ScatterDispatch(n, pieces=scatter_by_ids(batch, ids, hist),
                               host_syncs=1)
    spec = partitioner.scatter_spec(batch, n)
    if spec is None:
        return ScatterDispatch(n, pieces=_single_bucket_pieces(batch, n))
    key_spec, bounds = spec
    if interpret is None:
        interpret = pallas_interpret()
    data = batch.block(_pow2_rows(nrec, min(pad_block, 1 << 20)))
    if interpret:
        # CPU: stop the jitted call at the destination vector and let
        # harvest invert it host-side — numpy's fancy assignment beats
        # XLA:CPU's [rows] int32 scatter ~15x, and the harvest gather
        # then touches only the valid rows
        if block_n is None:
            block_n = _cpu_block_n(data.shape[0])
        dest, hist = _scatter_dest_padded(data, jnp.asarray(bounds), nrec,
                                          n_buckets=n, key_spec=key_spec,
                                          block_n=block_n, interpret=True)
        return ScatterDispatch(n, src=data, dest=dest, hist=hist)
    out, hist = _scatter_padded(data, jnp.asarray(bounds), nrec,
                                n_buckets=n, key_spec=key_spec,
                                block_n=block_n, interpret=interpret)
    return ScatterDispatch(n, out=out, hist=hist)


def scatter_batch(batch: RecordBatch, partitioner, n: int, *,
                  pad_block: int = 4096, block_n: int | None = None,
                  interpret: bool | None = None) -> List[RecordBatch]:
    """Device-resident shuffle: batch in, n bucket-sliced batches out.

    Dispatch + immediate harvest (one host sync) — see
    :func:`scatter_dispatch` for the split the engine's shuffle loop
    uses to amortise that sync across every worker batch of a round.
    """
    return scatter_dispatch(batch, partitioner, n, pad_block=pad_block,
                            block_n=block_n, interpret=interpret).harvest()


def scatter_pieces_dispatch(pieces: Sequence[RecordBatch], partitioner,
                            n: int, *, pad_block: int = 4096,
                            block_n: int | None = None,
                            interpret: bool | None = None
                            ) -> ScatterDispatch:
    """Enqueue one worker's stage output — its list of resident pieces —
    as a single scatter; never blocks.

    The fast path is the SEGMENTED scatter: when every piece shares one
    resident ladder shape (the executor's fixed per-stage blocks make
    that the common case) and the partitioner is on the host-invert
    kernel path, the pieces enter the jitted call as a pytree and the
    stack, junk-tail masking and key extraction all trace into one
    fused program.  That removes the eager concat-to-ladder copy and
    its per-piece dispatch overhead (~1ms/op on a CPU host — profiled
    as the largest single line of a shuffle round), and the kernel runs
    on the pieces' resident rows instead of a re-padded ladder block.
    The destination vector still orders valid rows bucket-major then
    piece-then-input-major — byte-identical to what a concat would
    have produced.

    Everything else (single piece, ragged piece shapes, degenerate or
    host-loop partitioners, compiled backends whose device epilogue
    already moves the rows) concatenates and falls through to
    :func:`scatter_dispatch`, so the caller sees one ScatterDispatch
    either way.
    """
    if len(pieces) == 1:
        return scatter_dispatch(pieces[0], partitioner, n,
                                pad_block=pad_block, block_n=block_n,
                                interpret=interpret)
    if interpret is None:
        interpret = pallas_interpret()
    kernelish = (n > 1 and not isinstance(partitioner, ReducePartitioner)
                 and getattr(partitioner, "scatter_spec", None) is not None)
    nrec = sum(p.num_records for p in pieces)
    if kernelish and interpret and nrec:
        rows = pieces[0].padded_rows
        width = pieces[0].record_size
        if rows and all(p.padded_rows == rows and p.record_size == width
                        for p in pieces):
            spec = partitioner.scatter_spec(pieces[0], n)
            if spec is not None:
                key_spec, bounds = spec
                if block_n is None:
                    block_n = _cpu_block_n(len(pieces) * rows)
                n_valids = jnp.asarray([p.num_records for p in pieces],
                                       jnp.int32)
                src, dest, hist = _scatter_dest_segments(
                    tuple(p.data for p in pieces), jnp.asarray(bounds),
                    n_valids, n_buckets=n, key_spec=key_spec,
                    block_n=block_n, interpret=True)
                return ScatterDispatch(n, src=src, dest=dest, hist=hist)
    if kernelish and nrec:
        # concat+pad fusion for the non-segmented kernel path: build the
        # shape-ladder block the scatter would pad to anyway in ONE
        # copy, so scatter_dispatch's block() is a shape-match no-op
        batch = RecordBatch.concat_block(
            pieces, _pow2_rows(nrec, min(pad_block, 1 << 20)))
    else:
        batch = RecordBatch.concat(list(pieces))
    return scatter_dispatch(batch, partitioner, n, pad_block=pad_block,
                            block_n=block_n, interpret=interpret)


# --------------------------------------------------------------------------
# Fused worker-axis round: the whole shuffle of a stage — every slot's key
# extraction, kernel pass and destination bookkeeping — as O(1) dispatches
# over a StackedBatch, instead of one dispatch per worker.

#: Target rows per segmented-shard dispatch on the interpret (CPU)
#: lowering.  The interpret kernel's cost grows super-linearly with the
#: per-call row count at a fixed block_n (measured on the TeraSort 1M
#: shape, 200 slots x 5120 rows: one flat call 101ms, 8 shards of ~128k
#: rows 50ms — matching the old per-worker path — while a per-slot vmap
#: took 599ms), so the stacked round is cut into at most
#: ``_ROUND_MAX_SHARDS`` contiguous slot ranges of about this many rows.
_ROUND_SHARD_ROWS = 131072
_ROUND_MAX_SHARDS = 8


@partial(jax.jit,
         static_argnames=("size", "rows_eff", "n_buckets", "key_spec",
                          "block_n", "interpret"))
def _scatter_dest_shard(data, n_valids, bounds, lo, *, size: int,
                        rows_eff: int, n_buckets: int, key_spec,
                        block_n: int | None, interpret: bool):
    """Destination vector + histogram for one contiguous slot range of a
    stacked [s, rows, width] round — the stacked twin of
    :func:`_scatter_dest_segments`.  The shard is sliced INSIDE the jit
    (``lo`` is a dynamic start, ``size`` static), so the round re-traces
    only per shard size (at most two sizes: the even split and the
    remainder), never per shard position.  ``rows_eff`` trims each
    slot's pad-ladder tail to the round's own quarter-ladder (every
    junk row beyond it would ride through the mask, kernel scan and
    destination fetch — at a 5k-record round on 4096-row slots that's
    ~80% of the kernel's work); the slice is static inside the jit so
    XLA fuses it for free."""
    shard = jax.lax.dynamic_slice_in_dim(data, lo, size, axis=0)
    nv = jax.lax.dynamic_slice_in_dim(n_valids, lo, size, axis=0)
    shard = shard[:, :rows_eff]
    s, rows, width = shard.shape
    flat = shard.reshape(s * rows, width)
    keys = _extract_keys(flat, key_spec)
    pos = jax.lax.iota(jnp.int32, s * rows)
    valid = (pos % rows) < nv[pos // rows]
    return bucket_dest(keys, bounds, valid.astype(jnp.int32),
                       n_buckets=n_buckets, block_n=block_n,
                       interpret=interpret)


@partial(jax.jit,
         static_argnames=("n_buckets", "key_spec", "block_n", "interpret"))
def _scatter_stacked(data, bounds, n_valids, *, n_buckets: int, key_spec,
                     block_n: int | None, interpret: bool):
    """The compiled-backend stacked round: ``bucket_scatter`` (key
    extraction + kernel + on-device row movement) vmapped over the slot
    axis.  One call scatters EVERY slot's rows bucket-contiguously and
    returns the one [s, n_buckets] histogram the round syncs — rows
    never leave the device.  (On CPU the segmented-shard path above is
    used instead: interpret-mode vmap serialises the per-slot scans and
    is ~10x slower than shard-flattened calls at the 1M shape.)"""
    def one(slot, nv):
        keys = _extract_keys(slot, key_spec)
        return bucket_scatter(slot, keys, bounds, nv, n_buckets=n_buckets,
                              block_n=block_n, interpret=interpret)
    return jax.vmap(one)(data, n_valids)


@partial(jax.jit, static_argnames=("rows_eff",))
def _regroup_take(src, idx, *, rows_eff: int):
    """The round's regrouping gather: flatten the [s, rows, width]
    source and take the [W, block2] global row positions in one fused
    program (the reshape is a view inside the jit, never a copy).
    ``rows_eff`` is the same per-round row trim the scatter shards used
    — harvest positions are strided by it.  The gather itself always
    runs on a FLAT index (XLA:CPU's batched gather is ~2x slower than
    the equivalent 1-D take); the index reshape and the output's
    [wn, block2, width] restore are free inside the jit."""
    s, _, width = src.shape
    flat = jnp.take(src[:, :rows_eff].reshape(s * rows_eff, width),
                    idx.reshape(-1), axis=0)
    return flat.reshape(idx.shape[0], idx.shape[1], width)


@dataclass
class FusedRoundResult:
    """The regrouped output of one fused shuffle round.

    ``data`` is uint8 [n_workers, block2, width]: destination worker
    ``w``'s resident partition occupies slot ``w`` — its buckets
    ``{b : b % n_workers == w}`` concatenated in ascending bucket order,
    records within a bucket in (slot-major, then input) order — i.e.
    exactly the order the bytes backend's per-worker append loop
    produces.  ``counts`` is the host [n_workers] valid-row vector
    (``data`` tails are junk) and ``origins[b]`` maps origin worker name
    to the bytes bucket ``b`` drew from it — the planner's movement
    pricing input.

    Large rounds come back SHARDED instead of as one stack: ``groups``
    holds ``(w_start, stack)`` pairs covering consecutive worker ranges
    (and ``data`` is None).  XLA:CPU's gather falls off its fast path
    above ~``_ROUND_SHARD_ROWS`` rows per call (a single 1M-row take is
    ~2x slower than the same rows split across a few separate calls),
    so the harvest caps rows per regrouping call exactly like the
    scatter caps rows per shard — the call count stays bounded by
    ``_ROUND_MAX_SHARDS``, never O(workers).  ``data is None`` with no
    ``groups`` means the round carried no records.
    """

    data: Optional[jax.Array]
    counts: np.ndarray
    origins: List[Dict[str, int]]
    dispatches: int = 0
    groups: Optional[List[Tuple[int, jax.Array]]] = None

    @property
    def record_size(self) -> int:
        if self.data is not None:
            return self.data.shape[2]
        if self.groups:
            return self.groups[0][1].shape[2]
        return 0


@dataclass
class StackedRoundDispatch:
    """The in-flight half of a FUSED shuffle round (cf. the per-batch
    :class:`ScatterDispatch`).

    :func:`scatter_round_dispatch` enqueues the whole round's device
    work — O(1) compiled calls regardless of worker or task count —
    and defers the single metadata sync into :meth:`harvest`.  Two
    lowerings share this container:

    * **segmented (CPU)** — at most ``_ROUND_MAX_SHARDS`` shard calls of
      :func:`_scatter_dest_shard`; ``metas`` holds each shard's
      (dest, hist) and harvest inverts the permutations host-side
      (numpy fancy assignment at memcpy speed).
    * **vmapped (TPU)** — ONE :func:`_scatter_stacked` call whose
      device epilogue already moved the rows; ``metas`` holds the
      [s, n] per-slot histogram and harvest only computes offsets.

    Either way :attr:`sync_arrays` is fetched in one ``device_get`` per
    round and :meth:`harvest` finishes with ONE gather that lands every
    destination worker's regrouped partition in a single stacked array —
    the device-side segment permutation that replaces the per-worker
    ``RecordBatch.concat`` loop.
    """

    n: int                           # bucket count
    worker_names: List[str]          # destination ring (bucket b -> b % W)
    slot_workers: np.ndarray         # [s] origin ring index per slot
    rows: int                        # padded rows per slot
    width: int
    pad_block: int
    src: jax.Array                   # [s, rows, width] round source
    mode: str                        # "segmented" | "vmapped"
    shards: List[Tuple[int, int]]    # segmented: (lo, size) slot ranges
    metas: List[Tuple[jax.Array, ...]]
    dispatches: int = 0
    host_syncs: int = 0

    @property
    def sync_arrays(self):
        """Device metadata the round barrier fetches — per-shard
        (dest, hist) on the segmented path, the [s, n] histogram on the
        vmapped path.  Record bytes never cross."""
        return tuple(a for m in self.metas for a in m)

    def harvest(self, synced=None) -> FusedRoundResult:
        """Regroup the round onto destination workers.  ``synced`` is
        the already-fetched :attr:`sync_arrays` tuple; omitted, the
        dispatch syncs its own (counted in :attr:`host_syncs`)."""
        if synced is None:
            synced = jax.device_get(self.sync_arrays)
            self.host_syncs += 1
        W, B, rows = len(self.worker_names), self.n, self.rows
        seg_pos: List[List[np.ndarray]] = [[] for _ in range(B)]
        origin_counts = np.zeros((B, W), np.int64)
        if self.mode == "segmented":
            i = 0
            for lo, size in self.shards:
                dest = np.asarray(synced[i])
                hist = np.asarray(synced[i + 1])
                i += 2
                perm = np.empty(dest.shape[0], np.int32)
                perm[dest] = np.arange(dest.shape[0], dtype=np.int32)
                off = np.concatenate(([0], np.cumsum(hist[:B])))
                n_valid = int(off[B])
                if not n_valid:
                    continue
                # dest order is bucket-contiguous, so perm[:n_valid] is
                # every bucket's ascending input rows back to back;
                # int32 throughout — global positions top out at s*rows
                gpos_all = perm[:n_valid] + np.int32(lo * rows)
                # each bucket's run is ascending, so slot boundaries
                # fall out of a searchsorted against the shard's slot
                # edges — origin pricing without touching every row
                # (the per-row bucket/worker decode was ~9ms of a ~20ms
                # 1M harvest)
                edges = (lo + np.arange(1, size)) * rows
                shard_workers = self.slot_workers[lo:lo + size]
                for b in range(B):
                    if off[b + 1] > off[b]:
                        seg = gpos_all[off[b]:off[b + 1]]
                        seg_pos[b].append(seg)
                        per_slot = np.diff(np.concatenate(
                            ([0], np.searchsorted(seg, edges),
                             [seg.size])))
                        np.add.at(origin_counts[b], shard_workers,
                                  per_slot)
        else:
            hist_sb = np.asarray(synced[0])[:, :B].astype(np.int64)
            off_sb = np.cumsum(hist_sb, axis=1) - hist_sb  # exclusive
            for b in range(B):
                for s in range(hist_sb.shape[0]):
                    c = int(hist_sb[s, b])
                    if c:
                        start = s * rows + int(off_sb[s, b])
                        seg_pos[b].append(
                            np.arange(start, start + c, dtype=np.int64))
                        origin_counts[b, self.slot_workers[s]] += c
        origins = [
            {self.worker_names[w]: int(origin_counts[b, w]) * self.width
             for w in np.nonzero(origin_counts[b])[0]}
            for b in range(B)]
        counts = np.zeros(W, np.int64)
        hist_total = origin_counts.sum(axis=1)
        for b in range(B):
            counts[b % W] += hist_total[b]
        nmax = int(counts.max()) if W else 0
        if nmax == 0:
            return FusedRoundResult(None, counts, origins, 0)
        # the regrouped stack gets its own quarter-ladder row count (same
        # trim rationale as scatter_round_dispatch's rows_eff: the
        # stage's pad_block floor would make a 1k-record partition carry
        # a 4096-row gather output)
        block2 = _quarter_rows(nmax, min(self.pad_block, 256))

        def idx_rows(ws) -> np.ndarray:
            """Global gather positions for workers ``ws`` (consecutive):
            each worker's buckets ascending, shard order within a
            bucket, input order within a shard — the bytes backend's
            append order.  Junk tail slots point at row 0; their content
            is never read (counts marks the valid prefixes)."""
            sub = np.zeros((len(ws), block2), np.int32)
            for j, w in enumerate(ws):
                fill = 0
                for b in range(w, B, W):
                    for gpos in seg_pos[b]:
                        sub[j, fill:fill + gpos.size] = gpos
                        fill += gpos.size
            return sub

        # The regrouping gather(s).  The [s, rows] -> [s*rows] flatten
        # happens INSIDE the gather jit where XLA fuses it away — an
        # eager reshape on XLA:CPU is a full copy of the round (~60ms at
        # the 1M shape).  Rows per call are capped like the scatter
        # shards: XLA:CPU's gather loses its fast path above
        # ~_ROUND_SHARD_ROWS rows per call, so big rounds split into at
        # most _ROUND_MAX_SHARDS worker-contiguous group takes —
        # bounded, never O(workers) — and each group's take is
        # dispatched as soon as its index rows are built, so the host
        # index build for group g+1 hides behind group g's gather.
        n_groups = int(min(_ROUND_MAX_SHARDS, W,
                           max(1, (W * block2) // _ROUND_SHARD_ROWS)))
        if n_groups <= 1:
            data = _regroup_take(self.src, jnp.asarray(idx_rows(range(W))),
                                 rows_eff=self.rows)
            return FusedRoundResult(data, counts, origins, 1)
        groups: List[Tuple[int, jax.Array]] = []
        w0 = 0
        for part in np.array_split(np.arange(W), n_groups):
            ws = [w0 + j for j in range(int(part.size))]
            groups.append(
                (w0, _regroup_take(self.src, jnp.asarray(idx_rows(ws)),
                                   rows_eff=self.rows)))
            w0 += int(part.size)
        return FusedRoundResult(None, counts, origins, n_groups,
                                groups=groups)


def scatter_round_dispatch(stacked: StackedBatch, partitioner, n: int, *,
                           worker_names: Sequence[str],
                           slot_workers=None, pad_block: int = 4096,
                           block_n: int | None = None,
                           interpret: bool | None = None,
                           lowering: str | None = None
                           ) -> Optional[StackedRoundDispatch]:
    """Enqueue a WHOLE round's shuffle over a stacked slot axis; never
    blocks.  Returns ``None`` when the round cannot stay on the fused
    kernel path (single bucket, reduce shuffle, host-loop partitioner,
    empty stack) — the caller falls back to the per-worker dispatch loop.

    ``slot_workers[i]`` names (by index into ``worker_names``) the worker
    whose stage output slot ``i`` holds, for movement accounting; slots
    must be ordered worker-major (ascending ``worker_names`` order, plan
    order within a worker) so the regrouped record order matches the
    bytes backend's append order record-for-record.  ``lowering``
    forces ``"segmented"`` / ``"vmapped"`` (default: segmented on the
    interpret/CPU backend, vmapped on compiled backends)."""
    s, rows, width = stacked.data.shape
    if n <= 1 or s == 0 or rows == 0 \
            or isinstance(partitioner, ReducePartitioner) \
            or getattr(partitioner, "scatter_spec", None) is None:
        return None
    # partitioners are immutable after construction, so the per-round
    # (key spec, device bounds) pair is cached on the instance — the
    # spec build + bounds device_put are ~0.3ms of host work per round,
    # which is real money on a ~2ms small round
    cached = getattr(partitioner, "_round_spec_cache", None)
    if cached is not None and cached[0] == (n, width):
        _, key_spec, bounds_dev = cached
    else:
        spec = partitioner.scatter_spec(RecordBatch.empty(width), n)
        if spec is None:
            return None
        key_spec, bounds = spec
        bounds_dev = jnp.asarray(bounds)
        try:
            partitioner._round_spec_cache = ((n, width), key_spec,
                                             bounds_dev)
        except AttributeError:
            pass                       # __slots__ partitioner: skip cache
    if interpret is None:
        interpret = pallas_interpret()
    if lowering is None:
        lowering = "segmented" if interpret else "vmapped"
    W = len(worker_names)
    if slot_workers is None:
        slot_workers = np.arange(s, dtype=np.int64) % max(W, 1)
    else:
        slot_workers = np.asarray(slot_workers, dtype=np.int64)
    nv_dev = jnp.asarray(stacked.n_valid, jnp.int32)
    metas: List[Tuple[jax.Array, ...]] = []
    shards: List[Tuple[int, int]] = []
    if lowering == "vmapped":
        src, hist_sb = _scatter_stacked(stacked.data, bounds_dev, nv_dev,
                                        n_buckets=n, key_spec=key_spec,
                                        block_n=block_n, interpret=interpret)
        metas.append((hist_sb,))
        dispatches = 1              # the stacked scatter
    else:
        src = stacked.data          # flattened inside the harvest gather
        dispatches = 0
        # trim each slot to the round's own quarter-ladder row count:
        # pad-ladder slots carry the STAGE's block shape (e.g. 4096-row
        # floors), but the round only needs rows up to its max n_valid —
        # the trim is a static in-jit slice and cuts the kernel's junk
        # work ~4x on small rounds
        nv_max = int(np.max(stacked.n_valid)) if s else 0
        rows = min(rows, _quarter_rows(nv_max, 256))
        n_shards = min(s, max(1, min(_ROUND_MAX_SHARDS,
                                     -(-s * rows // _ROUND_SHARD_ROWS))))
        base_sz = -(-s // n_shards)
        lo = 0
        while lo < s:
            size = min(base_sz, s - lo)
            shard_bn = _cpu_block_n(size * rows) if block_n is None \
                else block_n
            dest, hist = _scatter_dest_shard(
                stacked.data, nv_dev, bounds_dev, lo, size=size,
                rows_eff=rows, n_buckets=n, key_spec=key_spec,
                block_n=shard_bn, interpret=interpret)
            metas.append((dest, hist))
            shards.append((lo, size))
            dispatches += 1
            lo += size
    return StackedRoundDispatch(
        n=n, worker_names=list(worker_names), slot_workers=slot_workers,
        rows=rows, width=width, pad_block=pad_block, src=src,
        mode=lowering, shards=shards, metas=metas, dispatches=dispatches)


def terasort_stages(bounds: Sequence[bytes], backend: str, n_buckets: int,
                    key_bytes: int = 10) -> list:
    """The canonical TeraSort stage pair (partition+shuffle, then sort)
    on either record backend — shared by benchmarks, examples and tests
    so the two paths always run the same job shape."""
    from repro.core.job import SphereStage
    part = range_partitioner(bounds)
    if backend == "array":
        # pad_value=0xff declares both batch UDFs pad-stable, so the
        # executor pads to a fixed block shape and traces each once:
        # identity trivially keeps padding rows at the tail, and the
        # stable sort sends all-0xff padding keys to the end (ties with a
        # real all-0xff key keep the real record first — input order).
        return [
            SphereStage("partition", batch_udf=lambda b: b,
                        partitioner=part, n_buckets=n_buckets,
                        pad_value=0xFF),
            SphereStage("sort",
                        batch_udf=lambda b: b.sort_by_key(key_bytes),
                        pad_value=0xFF),
        ]
    return [
        SphereStage("partition", lambda rs: list(rs),
                    partitioner=part, n_buckets=n_buckets),
        SphereStage("sort",
                    lambda rs: sorted(rs, key=lambda r: r[:key_bytes])),
    ]


def sample_boundaries(records: Sequence[bytes], n_buckets: int,
                      key_bytes: int = 10) -> List[bytes]:
    """Sample keys to build balanced range boundaries (TeraSort pre-pass).

    Boundaries of any length stay on the kernel path (multi-word
    compare), so full 10-byte TeraSort keys are fine on the array
    backend.  When ``n_buckets > len(records)`` some boundaries repeat
    (the tail buckets stay empty); the index is clamped at both ends so
    the result is always sorted.
    """
    keys = sorted(r[:key_bytes] for r in records)
    if not keys or n_buckets <= 1:
        return []
    step = len(keys) / n_buckets
    return [keys[min(max(int(step * i) - 1, 0), len(keys) - 1)]
            for i in range(1, n_buckets)]
