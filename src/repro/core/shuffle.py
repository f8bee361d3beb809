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
from repro.kernels.bucket_partition import bucket_partition, bucket_scatter
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


def _extract_keys(data, key_spec):
    batch = RecordBatch(data)
    if key_spec[0] == "hash":
        return batch.hash_keys_u32(key_spec[1])
    _, key_len, n_words, length_word = key_spec
    return batch.key_words(key_len, n_words=n_words, length_word=length_word)


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

    A pending dispatch holds in ``out`` the bucket-contiguous rows (the
    kernel's device epilogue already moved them); harvest slices it by
    the synced histogram.  The barrier is ONE ``device_get`` per round
    of [n]-sized metadata — record bytes stay on device.
    Degenerate/fallback shapes resolve at dispatch time into
    ``pieces``: those harvest for free, and ``host_syncs`` records any
    sync the fallback already paid (1 for the per-record host loop, else
    0), so executor-level sync accounting stays truthful.
    """

    n: int                                        # bucket count
    pieces: Optional[List[RecordBatch]] = None    # resolved at dispatch
    out: Optional[jax.Array] = None               # scattered rows
    hist: Optional[jax.Array] = None              # pending [n] counts
    host_syncs: int = field(default=0)            # syncs paid at dispatch

    @property
    def pending(self) -> bool:
        """True when metadata must reach the host before slicing."""
        return self.pieces is None

    @property
    def sync_arrays(self):
        """The device values the round barrier must fetch: the [n]
        histogram."""
        return (self.hist,)

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
        self.pieces = [RecordBatch(self.out[offsets[i]:offsets[i + 1]])
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

    A single piece delegates to :func:`scatter_dispatch` outright.
    Several pieces on the kernel path concatenate straight into the
    shape-ladder block the scatter pads to, valid rows in piece-then-
    input order; degenerate or host-loop partitioners take a plain
    concat.  The caller sees one ScatterDispatch either way.
    """
    if len(pieces) == 1:
        return scatter_dispatch(pieces[0], partitioner, n,
                                pad_block=pad_block, block_n=block_n,
                                interpret=interpret)
    kernelish = (n > 1 and not isinstance(partitioner, ReducePartitioner)
                 and getattr(partitioner, "scatter_spec", None) is not None)
    nrec = sum(p.num_records for p in pieces)
    if kernelish and nrec:
        # concat+pad fusion: build the shape-ladder block the scatter
        # would pad to anyway in ONE copy, so scatter_dispatch's block()
        # is a shape-match no-op
        batch = RecordBatch.concat_block(
            pieces, _pow2_rows(nrec, min(pad_block, 1 << 20)))
    else:
        batch = RecordBatch.concat(list(pieces))
    return scatter_dispatch(batch, partitioner, n, pad_block=pad_block,
                            block_n=block_n, interpret=interpret)


# --------------------------------------------------------------------------
# Fused worker-axis round: the whole shuffle of a stage — every slot's key
# extraction, kernel pass and row movement — as O(1) dispatches over a
# StackedBatch, instead of one dispatch per worker.


@partial(jax.jit,
         static_argnames=("n_buckets", "key_spec", "block_n", "interpret"))
def _scatter_stacked(data, bounds, n_valids, *, n_buckets: int, key_spec,
                     block_n: int | None, interpret: bool):
    """The stacked round: ``bucket_scatter`` (key extraction + kernel +
    on-device row movement) vmapped over the slot axis.  One call
    scatters EVERY slot's rows bucket-contiguously and returns the one
    [s, n_buckets] histogram the round syncs — rows never leave the
    device."""
    def one(slot, nv):
        keys = _extract_keys(slot, key_spec)
        return bucket_scatter(slot, keys, bounds, nv, n_buckets=n_buckets,
                              block_n=block_n, interpret=interpret)
    return jax.vmap(one)(data, n_valids)


@jax.jit
def _regroup_take(src, idx):
    """The round's regrouping gather: flatten the [s, rows, width]
    source and take the [W, block2] global row positions in one fused
    program (the reshape is a view inside the jit, never a copy)."""
    s, rows, width = src.shape
    flat = jnp.take(src.reshape(s * rows, width), idx.reshape(-1), axis=0)
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
    pricing input.  ``data is None`` means the round carried no records.
    """

    data: Optional[jax.Array]
    counts: np.ndarray
    origins: List[Dict[str, int]]
    dispatches: int = 0

    @property
    def record_size(self) -> int:
        return 0 if self.data is None else self.data.shape[2]


@dataclass
class StackedRoundDispatch:
    """The in-flight half of a FUSED shuffle round (cf. the per-batch
    :class:`ScatterDispatch`).

    :func:`scatter_round_dispatch` enqueues the whole round's device
    work — ONE :func:`_scatter_stacked` call, whose device epilogue
    already moved the rows — and defers the single metadata sync into
    :meth:`harvest`.  :attr:`sync_arrays` (the [s, n] per-slot
    histogram) is fetched in one ``device_get`` per round, and
    :meth:`harvest` finishes with ONE gather that lands every
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
    src: jax.Array                   # [s, rows, width] scattered slots
    hist: jax.Array                  # [s, n] per-slot histogram
    dispatches: int = 1              # the stacked scatter
    host_syncs: int = 0

    @property
    def sync_arrays(self):
        """Device metadata the round barrier fetches: the [s, n]
        histogram.  Record bytes never cross."""
        return (self.hist,)

    def harvest(self, synced=None) -> FusedRoundResult:
        """Regroup the round onto destination workers.  ``synced`` is
        the already-fetched :attr:`sync_arrays` tuple; omitted, the
        dispatch syncs its own (counted in :attr:`host_syncs`)."""
        if synced is None:
            synced = jax.device_get(self.sync_arrays)
            self.host_syncs += 1
        W, B, rows = len(self.worker_names), self.n, self.rows
        hist_sb = np.asarray(synced[0])[:, :B].astype(np.int64)
        off_sb = np.cumsum(hist_sb, axis=1) - hist_sb  # exclusive
        seg_pos: List[List[np.ndarray]] = [[] for _ in range(B)]
        origin_counts = np.zeros((B, W), np.int64)
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
        # the regrouped stack gets its own quarter-ladder row count: the
        # stage's pad_block floor would make a 1k-record partition carry
        # a 4096-row gather output
        block2 = _quarter_rows(nmax, min(self.pad_block, 256))
        # global gather positions: each worker's buckets ascending, slot
        # order within a bucket, input order within a slot — the bytes
        # backend's append order.  Junk tail slots point at row 0; their
        # content is never read (counts marks the valid prefixes).
        idx = np.zeros((W, block2), np.int32)
        for w in range(W):
            fill = 0
            for b in range(w, B, W):
                for gpos in seg_pos[b]:
                    idx[w, fill:fill + gpos.size] = gpos
                    fill += gpos.size
        data = _regroup_take(self.src, jnp.asarray(idx))
        return FusedRoundResult(data, counts, origins, 1)


def scatter_round_dispatch(stacked: StackedBatch, partitioner, n: int, *,
                           worker_names: Sequence[str],
                           slot_workers=None, pad_block: int = 4096,
                           block_n: int | None = None,
                           interpret: bool | None = None
                           ) -> Optional[StackedRoundDispatch]:
    """Enqueue a WHOLE round's shuffle over a stacked slot axis; never
    blocks.  Returns ``None`` when the round cannot stay on the fused
    kernel path (single bucket, reduce shuffle, host-loop partitioner,
    empty stack) — the caller falls back to the per-worker dispatch loop.

    ``slot_workers[i]`` names (by index into ``worker_names``) the worker
    whose stage output slot ``i`` holds, for movement accounting; slots
    must be ordered worker-major (ascending ``worker_names`` order, plan
    order within a worker) so the regrouped record order matches the
    bytes backend's append order record-for-record."""
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
    W = len(worker_names)
    if slot_workers is None:
        slot_workers = np.arange(s, dtype=np.int64) % max(W, 1)
    else:
        slot_workers = np.asarray(slot_workers, dtype=np.int64)
    src, hist_sb = _scatter_stacked(
        stacked.data, bounds_dev, jnp.asarray(stacked.n_valid, jnp.int32),
        n_buckets=n, key_spec=key_spec, block_n=block_n, interpret=interpret)
    return StackedRoundDispatch(
        n=n, worker_names=list(worker_names), slot_workers=slot_workers,
        rows=rows, width=width, pad_block=pad_block, src=src, hist=hist_sb)


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
