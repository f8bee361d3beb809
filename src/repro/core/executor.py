"""Sphere data plane: per-backend executors (planner/executor split).

An executor owns everything that touches record data — fetching chunks
from Sector (with bounded retries), running stage UDFs on the worker the
planner chose, bucketizing stage output for the shuffle, and materialising
the final per-bucket blobs.  The planner (:mod:`repro.core.planner`)
never sees a record; the executor never makes a placement decision.

* :class:`BytesExecutor` — the per-record Python reference.  A worker's
  partition is a list of ``bytes`` records.

* :class:`ArrayExecutor` — the device-resident backend.  A worker's
  partition is ONE :class:`RecordBatch` that stays on device across
  stages: UDF apply -> bucket_partition kernel -> argsort/gather ->
  device concat on the destination worker, with host bytes touched only
  when reading Sector chunks (stage 0) and materialising final outputs.
  Stage UDFs that declare ``pad_value`` are applied through a jit-once
  wrapper: inputs are padded to a fixed block shape (the next power of
  two at or above ``pad_block`` rows) so tasks share one traced shape
  instead of recompiling per task shape.

Both executors report identical shuffle flows (per-bucket origin bytes),
so the planner charges movement from each bucket's *actual* origin
workers and simulated time agrees across backends for the same job.
"""
from __future__ import annotations

import collections
import functools
import math
import queue
import re
import threading
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.job import SphereJob, SphereStage
from repro.core.planner import SphereReport, StagePlan
from repro.core.records import RecordBatch, StackedBatch
from repro.core.shuffle import (FusedRoundResult, _quarter_rows,
                                scatter_pieces_dispatch,
                                scatter_round_dispatch)
from repro.core.trace import NULL_TRACER
from repro.sector.server import ServerDown

# per-bucket origin accounting: origins[i][worker] = bytes of bucket i
# that were produced on that worker
Origins = List[Dict[str, int]]


class _ExecutorBase:
    def __init__(self, client, workers: Sequence[str], max_retries: int = 3,
                 cache_chunks: bool = False, prefetch: bool = True,
                 prefetch_depth: int = 1, tracer=None):
        self.client = client
        self.workers = list(workers)
        self.max_retries = max_retries
        self.prefetch = prefetch
        self.prefetch_depth = max(1, prefetch_depth)
        # wall-clock span tracer (NULL_TRACER = record nothing, but
        # spans still time themselves — the one timing idiom)
        self.tracer = tracer or NULL_TRACER
        # session mode: stage-0 chunks, once fetched and decoded, stay
        # resident (bytes: record lists; array: device RecordBatches) so
        # a chain of jobs over the same file pays the host round-trip
        # exactly once.  Keyed by chunk id; cleared by session.refresh().
        self._chunk_cache: Optional[Dict[str, object]] = \
            {} if cache_chunks else None

    def clear_chunk_cache(self) -> None:
        if self._chunk_cache is not None:
            self._chunk_cache.clear()

    def evict_chunks(self, keys) -> None:
        """Drop specific cached chunks — stream window retirement: an
        expired file's decoded chunks are released while every surviving
        cache entry stays untouched (and, on the array backend,
        device-resident)."""
        if self._chunk_cache is not None:
            for k in keys:
                self._chunk_cache.pop(k, None)

    def _fetch_chunk(self, key: str, rep: SphereReport) -> Optional[bytes]:
        """Read a stage-0 chunk, retrying over surviving replicas."""
        for _ in range(self.max_retries):
            try:
                return self.client.read_chunk(key)
            except (IOError, ServerDown):
                rep.retried += 1
                self.client.run_repair()
        return None

    def _stage0_input(self, job: SphereJob, key: str, rep: SphereReport,
                      device=None):
        """Decoded stage-0 input for one chunk task, through the session
        chunk cache when enabled (put on ``device``, where given).
        Returns None when every replica is gone."""
        if self._chunk_cache is not None and key in self._chunk_cache:
            return self._chunk_cache[key]
        with self.tracer.span("fetch-chunk", track="fetch",
                              attrs={"key": key}) as sp:
            with self.tracer.span("sector-read", track="fetch"):
                blob = self._fetch_chunk(key, rep)
            if blob is None:
                sp.set_attrs(lost=True)
                return None
            decoded = self._decode_chunk(job, blob, "fetch", device)
        if self._chunk_cache is not None:
            self._chunk_cache[key] = decoded
        return decoded

    # ------------------------------------------------- stage-0 prefetch
    def _stage0_batches(self, job: SphereJob, tasks, rep: SphereReport,
                        devices=None) -> Iterator[tuple]:
        """Yield ``(task, decoded_input)`` for the stage-0 task list with
        a ``prefetch_depth``-deep fetch+decode pipeline: ONE producer
        thread walks the chunks strictly in task order — so Sector
        client state (transfer log, cache warmth) evolves exactly as in
        the synchronous loop — pushing decoded inputs into a bounded
        queue the caller drains, so host I/O of up to ``prefetch_depth``
        chunks overlaps device compute.  The producer makes one bare
        ``read_chunk`` attempt per chunk; a failed read is replayed on
        the MAIN thread through :meth:`_stage0_input`'s retry loop from
        attempt one, so ``rep.retried`` and repair behaviour are
        bit-identical with prefetching off (and across depths).
        ``decoded_input`` is None when every replica of a chunk is gone
        (the caller skips the task).  ``devices``, aligned with
        ``tasks``, names the device each chunk is put on (the default
        device where None).  The consumer's wait on the queue is
        the ``prefetch-wait`` span: time the data plane stood waiting on
        Sector, where ``fetch-chunk`` is fetch work that overlaps it."""
        if devices is None:
            devices = [None] * len(tasks)
        if not self.prefetch or len(tasks) <= 1:
            for t, dev in zip(tasks, devices):
                yield t, self._stage0_input(job, t.key, rep, dev)
            return
        q: "queue.Queue[tuple]" = queue.Queue(maxsize=self.prefetch_depth)

        def produce():
            for t, dev in zip(tasks, devices):
                if self._chunk_cache is not None \
                        and t.key in self._chunk_cache:
                    # cache hits are resolved by the consumer (the cache
                    # may gain entries while this thread runs ahead)
                    q.put(("cache", None))
                    continue
                try:
                    with self.tracer.span("fetch-chunk", track="prefetch",
                                          attrs={"key": t.key}):
                        with self.tracer.span("sector-read",
                                              track="prefetch"):
                            blob = self.client.read_chunk(t.key)
                        payload = self._decode_chunk(job, blob, "prefetch",
                                                     dev)
                    q.put(("ok", payload))
                except (IOError, ServerDown):
                    q.put(("retry", None))
                except BaseException as err:  # noqa: BLE001 — re-raised
                    q.put(("error", err))
                    return

        th = threading.Thread(target=produce, daemon=True,
                              name="sphere-prefetch")
        th.start()
        for t, dev in zip(tasks, devices):
            with self.tracer.span("prefetch-wait", track="fetch"):
                kind, payload = q.get()
            if kind == "ok":
                if self._chunk_cache is not None:
                    self._chunk_cache[t.key] = payload
                yield t, payload
            elif kind in ("cache", "retry"):
                yield t, self._stage0_input(job, t.key, rep, dev)
            else:
                raise payload
        th.join()


class BytesExecutor(_ExecutorBase):
    """Reference data plane: partitions are lists of Python bytes."""

    def empty_parts(self) -> Dict[str, List[bytes]]:
        return {w: [] for w in self.workers}

    def part_sizes(self, parts) -> Dict[str, int]:
        return {w: sum(len(r) for r in parts[w]) for w in self.workers}

    def _decode_chunk(self, job: SphereJob, blob: bytes, track: str,
                      device=None) -> List[bytes]:
        return job.split_records(blob)

    def run_stage(self, job: SphereJob, stage: SphereStage, plan: StagePlan,
                  parts, rep: SphereReport, *, first_stage: bool
                  ) -> Dict[str, List[bytes]]:
        out: Dict[str, List[bytes]] = {w: [] for w in self.workers}
        if first_stage:
            source = self._stage0_batches(job, plan.tasks, rep)
        else:
            source = ((t, parts.get(t.key)) for t in plan.tasks)
        for t, records in source:
            if not records:
                continue
            if first_stage and self._chunk_cache is not None:
                # hand UDFs a copy: an in-place-mutating UDF (sort,
                # pop) must not corrupt the cache for later jobs
                records = list(records)
            # stage-0 chunks land wherever they were computed; a later
            # stage's partition keeps its OWNER slot even when the
            # planner priced the compute elsewhere — merging two
            # partitions into one executor slot would destroy partition
            # identity (and a sort stage's per-partition record order)
            dst = t.executor if first_stage else t.key
            out[dst].extend(stage.apply_bytes(records))
        return out

    def bucketize(self, stage: SphereStage, out, n: int, rep: SphereReport
                  ) -> Tuple[List[List[bytes]], Origins]:
        """Reference shuffle: one partitioner call per Python record.
        Pure host work — a bytes shuffle round never syncs a device
        (``rep.host_syncs`` stays 0)."""
        buckets: List[List[bytes]] = [[] for _ in range(n)]
        origins: Origins = [{} for _ in range(n)]
        rep.shuffle_rounds += 1
        with self.tracer.span("shuffle-round", track="shuffle",
                              attrs={"backend": "bytes",
                                     "buckets": n}) as sp:
            for w in self.workers:
                for r in out[w]:
                    b = stage.partitioner(r, n)
                    buckets[b].append(r)
                    origins[b][w] = origins[b].get(w, 0) + len(r)
                    rep.partitioned_records += 1
        rep.partition_seconds += sp.wall_seconds
        return buckets, origins

    def place_buckets(self, buckets, parts) -> None:
        for w in self.workers:
            parts[w] = []
        for i, bucket in enumerate(buckets):
            parts[self.workers[i % len(self.workers)]].extend(bucket)

    def set_parts(self, parts, out) -> None:
        for w in self.workers:
            parts[w] = out[w]

    def outputs(self, parts) -> List[bytes]:
        with self.tracer.span("materialise", track="output"):
            return [b"".join(parts[w]) for w in self.workers if parts[w]]


def _stage_fn(fn, stage: str, kind: str):
    """``fn`` under the name ``stage_<stage>_<kind>``, which jit gives
    the compiled module; the signature stays ``fn``'s, for
    ``static_argnames``."""
    @functools.wraps(fn)
    def named(*args, **kwargs):
        return fn(*args, **kwargs)
    safe = re.sub(r"\W", "_", stage)
    named.__name__ = named.__qualname__ = f"stage_{safe}_{kind}"
    return named


class _TracedUDF:
    """jit wrapper around a pad-stable (or mask-aware) UDF that counts
    trace events — the trace-time side effect fires once per distinct
    input shape, so ``traces == 1`` certifies the stage compiled exactly
    once.

    Both modes jit over ``(data, n_valid, ...)`` with ``n_valid``
    dynamic, and normalise the block's padding tail to the stage's pad
    byte ON DEVICE before the UDF sees it: the executor hands over raw
    fixed-shape blocks (:meth:`RecordBatch.block`) whose padding content
    is junk — there is no host-side slice-then-repad copy per hop, and
    the one fused ``where`` inside the trace replaces it.

    Masked mode additionally passes the params pytree as a *dynamic*
    argument: every task of the stage — and every re-run of the stage
    across a chained session (e.g. k-means iterations with fresh
    centroids in ``params``) — shares one trace."""

    def __init__(self, name: str, udf, *, masked: bool = False,
                 pad_value: int = 0, mesh=None):
        self.name = name
        self.udf = udf
        self.pad_value = pad_value
        self.mesh = mesh
        self.traces = 0
        # every entry point compiles to a module named after the stage
        # (``jit_stage_sort_pieces``), so the device trace attributes
        # each op to its stage
        self._jit = jax.jit(
            _stage_fn(self._call_masked, name, "masked") if masked
            else _stage_fn(self._call_padded, name, "padded"))
        # fused-round entry points: the whole stage as ONE vmapped call
        # over the stacked slot axis (``target`` static so one trace
        # serves every round at the stage's block shape)
        self._jit_stacked = jax.jit(
            _stage_fn(self._call_stacked, name, "stacked"),
            static_argnames=("target",))
        self._jit_stack_pieces = jax.jit(
            _stage_fn(self._call_stack_pieces, name, "pieces"),
            static_argnames=("target",))

    def _check(self, out) -> jax.Array:
        if not isinstance(out, RecordBatch):
            raise TypeError(f"stage {self.name!r} UDF must return "
                            f"a RecordBatch, got {type(out).__name__}")
        return out.data

    def _normalize(self, data: jax.Array, n_valid):
        """(mask, block with padding rows set to the stage pad byte) —
        junk tails must never reach a UDF: a pad-stable sort keys on the
        pad byte, and masked reductions may bitcast rows to floats where
        junk could be NaN (NaN * 0 still poisons a sum)."""
        mask = jnp.arange(data.shape[0], dtype=jnp.int32) < n_valid
        return mask, jnp.where(mask[:, None], data,
                               jnp.asarray(self.pad_value, data.dtype))

    def _call_padded(self, data: jax.Array, n_valid) -> jax.Array:
        self.traces += 1
        _, norm = self._normalize(data, n_valid)
        return self._check(self.udf(RecordBatch(norm)))

    def _call_masked(self, data: jax.Array, n_valid, params) -> jax.Array:
        self.traces += 1
        mask, norm = self._normalize(data, n_valid)
        return self._check(self.udf(RecordBatch(norm), mask, params))

    def _vmapped(self, data3: jax.Array, n_valids: jax.Array) -> jax.Array:
        """The per-slot body vmapped over the slot axis — and, when a
        mesh was supplied, lowered through ``shard_map`` over the
        ``data`` axis so each device runs only its resident slots."""
        fn = jax.vmap(self._call_padded)
        if self.mesh is not None:
            from repro.core.spmd import sphere_map
            fn = sphere_map(fn, self.mesh)
        return fn(data3, n_valids)

    def _call_stacked(self, data3: jax.Array, n_valids: jax.Array, *,
                      target: int) -> jax.Array:
        """Stacked [s, rows, width] input (a previous fused round's
        resident partitions); rows are adjusted to ``target`` in-jit —
        slicing off junk tail or growing it — before the vmapped body."""
        rows = data3.shape[1]
        if rows > target:
            data3 = data3[:, :target, :]
        elif rows < target:
            data3 = jnp.pad(data3, ((0, 0), (0, target - rows), (0, 0)))
        return self._vmapped(data3, n_valids)

    def _call_stack_pieces(self, pieces, n_valids: jax.Array, *,
                           target: int) -> jax.Array:
        """Tuple of per-task 2-D pieces (stage-0 decoded chunks) stacked
        INSIDE the trace: each piece pads/slices to ``target`` rows and
        one ``jnp.stack`` forms the [s, target, width] block — no eager
        per-piece dispatch, mirroring _scatter_dest_segments' in-jit
        stack rationale.  Not concatenate+reshape: for uint8 [rows, 100]
        pieces the TPU compiler takes minutes over that form (PERF.md,
        "Where the time goes")."""
        blocks = []
        for p in pieces:
            r = p.shape[0]
            if r > target:
                p = p[:target]
            elif r < target:
                p = jnp.pad(p, ((0, target - r), (0, 0)))
            blocks.append(p)
        return self._vmapped(jnp.stack(blocks), n_valids)

    def stacked(self, data3: jax.Array, n_valids, target: int) -> jax.Array:
        return self._jit_stacked(data3, n_valids, target=target)

    def stack_pieces(self, pieces, n_valids, target: int) -> jax.Array:
        return self._jit_stack_pieces(tuple(pieces), n_valids,
                                      target=target)

    def __call__(self, *args) -> jax.Array:
        return self._jit(*args)


class _SlotRef:
    """One worker's partition as a VIEW into a round-stacked array.

    A fused round leaves every destination worker's records inside one
    [n_workers, block, width] device array (:class:`FusedRoundResult`);
    installing per-worker ``RecordBatch`` copies would undo the fusion
    with n_workers slice dispatches.  A ``_SlotRef`` instead records
    (stacked, slot index) and answers the host-side shape queries
    (``num_records``/``nbytes`` from the host count vector, no device
    op); :meth:`batch` materialises the slot as a padding-resident
    RecordBatch only when a non-fused consumer actually needs one.
    """

    __slots__ = ("stacked", "idx")

    def __init__(self, stacked: StackedBatch, idx: int):
        self.stacked = stacked
        self.idx = idx

    @property
    def num_records(self) -> int:
        return int(self.stacked.n_valid[self.idx])

    @property
    def record_size(self) -> int:
        return self.stacked.record_size

    @property
    def nbytes(self) -> int:
        return self.num_records * self.record_size

    def batch(self) -> RecordBatch:
        return self.stacked.slot(self.idx)


def _as_batch(part) -> Optional[RecordBatch]:
    """A parts-dict value as a RecordBatch (None stays None) — the
    read-side adapter every non-fused consumer goes through."""
    return part.batch() if isinstance(part, _SlotRef) else part


# A stack slot whose padded rows fill at least this many bytes leaves the
# device as a uint32 slab (see _slab).  Warm, the slab is the faster copy
# at every size (on a v5e, six 800-byte slots 6.5 ms against 10.6 ms as
# rows); what a small one pays is the pack's compile for each new stack
# shape, 0.14-3.4 s up to 1 MiB a slot, which the 4-36 ms it saves per
# six-slot copy-out there takes 34-95 copy-outs of one shape to repay.
SLAB_MIN_BYTES = 1 << 20


@jax.jit
def _slab(data: jax.Array, idx) -> jax.Array:
    """The rows of slot ``idx`` of a [s, block, width] stack as a uint32
    [M, 128] slab: the same bytes in row-major order, four to a
    little-endian word, zero-padded to whole 512-byte slab rows.

    A TPU tiles narrow uint8 rows with the row index minor, so their
    copy to the host must be untiled and transposed there; the slab's
    (8, 128)-tiled layout is its row-major one, so its copy is a straight
    DMA into a C-contiguous host array.  Byte k of each word is a
    strided ``lax.slice`` of the row bytes: a reshape to a minor axis of
    4 (what a bitcast to uint32 takes) would be padded to 128 lanes, 32
    times the slot, and jnp's step indexing lowers to gathers whose
    program stays resident in HBM.  ``idx`` is traced, so every slot of
    a stack shares one executable, and a stack's shape comes from the
    executor's block ladder, never from a record count."""
    rows = data[idx]
    n, width = rows.shape
    g = 4 // math.gcd(width, 4)  # rows that hold whole words
    rows = jnp.pad(rows, ((0, -n % g), (0, 0))).reshape(-1, g * width)
    words = functools.reduce(jnp.bitwise_or, [
        jax.lax.slice(rows, (0, k), rows.shape, (1, 4)).astype(jnp.uint32)
        << (8 * k) for k in range(4)])
    flat = words.reshape(-1)
    return jnp.pad(flat, (0, -flat.size % 128)).reshape(-1, 128)


def _slot_shard(data: jax.Array, idx: int):
    """``(shard, index)``: the one-device piece of the stack ``data``
    that holds slot ``idx`` whole, and the slot's index in it — the
    stack itself when it sits on one device; ``(None, None)`` where no
    addressable shard holds the slot whole."""
    for shard in data.addressable_shards:
        lo, hi, _ = shard.index[0].indices(data.shape[0])
        if lo <= idx < hi and shard.data.shape[1:] == data.shape[1:]:
            return shard.data, idx - lo
    return None, None


@functools.partial(jax.jit, static_argnames=("target", "n_empty"))
def _stack_rows(pieces, *, target: int, n_empty: int) -> jax.Array:
    """The 2-D ``pieces`` (on one device) as a [len + n_empty, target,
    width] stack: each cut or zero-padded to ``target`` rows, then
    ``n_empty`` zero slots."""
    width = pieces[0].shape[1]
    blocks = [p[:target] if p.shape[0] >= target
              else jnp.pad(p, ((0, target - p.shape[0]), (0, 0)))
              for p in pieces]
    blocks += [jnp.zeros((target, width), jnp.uint8)] * n_empty
    return jnp.stack(blocks)


@dataclass
class _StackedOut:
    """A fused run_stage result: the whole stage output as ONE
    StackedBatch, plus each slot's origin worker (index into the
    executor's worker ring).  Slots are ordered worker-major (ascending
    worker order, plan order within a worker), which is exactly the
    iteration order of the per-worker dict path — so fused and
    per-worker rounds see records in the same global order."""

    stacked: StackedBatch
    slot_workers: np.ndarray

    def to_worker_dict(self, workers: Sequence[str]
                       ) -> Dict[str, List[RecordBatch]]:
        """Downgrade to the legacy per-worker pieces dict (used when the
        following shuffle cannot stay on the fused kernel path)."""
        out: Dict[str, List[RecordBatch]] = {w: [] for w in workers}
        for i in range(self.stacked.n_slots):
            if self.stacked.n_valid[i]:
                out[workers[int(self.slot_workers[i])]].append(
                    self.stacked.slot(i))
        return out


class ArrayExecutor(_ExecutorBase):
    """Device-resident data plane: one RecordBatch per worker partition.

    With ``fused_rounds`` (the default), pad-stable stages run the whole
    round — every task's UDF apply, every worker's bucket scatter, and
    the regrouping onto destination workers — through O(1) compiled
    dispatches over a stacked slot axis instead of a Python loop of
    per-task/per-worker calls (see :class:`_StackedOut`,
    :func:`scatter_round_dispatch` and :class:`FusedRoundResult`).
    Mask-aware, shape-polymorphic and host-loop shapes keep the
    per-task path.  Supplying ``mesh`` (a 1-D ``data`` mesh,
    ``core.spmd.data_mesh``) spreads each stacked stage over its
    devices, a device holding a contiguous run of slots: stage-0 chunks
    are put straight on their slot's device, the round runs through
    ``shard_map`` with the bucket exchange as ``lax.all_to_all``
    (``core.spmd.fused_scatter_round``), and each later stage applies
    per device to the workers it holds."""

    def __init__(self, client, workers: Sequence[str], max_retries: int = 3,
                 pad_block: int = 4096, cache_chunks: bool = False,
                 prefetch: bool = True, timing_sync: bool = False,
                 fused_rounds: bool = True, mesh=None,
                 prefetch_depth: int = 1, tracer=None):
        super().__init__(client, workers, max_retries,
                         cache_chunks=cache_chunks, prefetch=prefetch,
                         prefetch_depth=prefetch_depth, tracer=tracer)
        self.pad_block = pad_block
        self.fused_rounds = fused_rounds
        # the mesh only carries rounds whose slot/worker counts divide
        # its data axis; others use the single-device lowering, and
        # their shuffle-round span says so (path "fused", not "mesh")
        if mesh is not None and tuple(mesh.axis_names) != ("data",):
            raise ValueError(f"the data plane's mesh has the one axis "
                             f"'data', not {mesh.axis_names}")
        self.mesh = mesh
        # benchmark honesty knob: block on every shuffled piece before
        # stopping the partition_seconds clock, so deferred-sync timing
        # can never report still-in-flight device work as finished.
        # Off by default — a timing-only barrier, excluded from the
        # host_syncs data-plane accounting.
        self.timing_sync = timing_sync

    def empty_parts(self) -> Dict[str, Optional[RecordBatch]]:
        return {w: None for w in self.workers}

    def part_sizes(self, parts) -> Dict[str, int]:
        return {w: (parts[w].nbytes if parts[w] is not None else 0)
                for w in self.workers}

    def _decode_chunk(self, job: SphereJob, blob: bytes, track: str,
                      device=None) -> RecordBatch:
        # the split is a zero-copy view: what takes the time is the put,
        # whose host side this span holds; its transfer runs on after it
        dev = device if device is not None else jax.devices()[0]
        with self.tracer.span("h2d-put", track=track,
                              attrs={"bytes": len(blob), "device": dev.id}):
            return job.split_batch(blob, device)

    # --------------------------------------------------------- UDF apply
    def _traced_for(self, stage: SphereStage, udf, *,
                    masked: bool = False) -> _TracedUDF:
        pad_value = stage.pad_value or 0
        # the wrapper lives ON the stage object (not in an executor-side
        # id()-keyed dict): same-named stages keep their own traced UDFs,
        # a stage re-run across a whole session chain keeps one compiled
        # wrapper, and — now that the executor outlives individual jobs —
        # a dead stage can never collide with a new stage allocated at
        # the same address, nor does trace state accumulate unboundedly
        traced = getattr(stage, "_traced", None)
        if traced is None or traced.udf is not udf \
                or traced.pad_value != pad_value \
                or traced.mesh is not self.mesh:
            traced = _TracedUDF(stage.name, udf, masked=masked,
                                pad_value=pad_value, mesh=self.mesh)
            stage._traced = traced
        return traced

    def _note_traces(self, stage: SphereStage, traced: _TracedUDF,
                     rep: SphereReport) -> None:
        rep.note_udf_traces(stage.name, traced.traces)

    def _apply_masked(self, stage: SphereStage, batch: RecordBatch,
                      target: int, rep: SphereReport) -> RecordBatch:
        """Mask-aware reduction path: hand the UDF the stage's fixed
        block (padding normalised on device by the traced wrapper), a
        validity mask, and the stage's current params.  The output is
        returned whole — reduction outputs have no padding rows to
        slice off."""
        traced = self._traced_for(stage, stage.masked_udf, masked=True)
        out = traced(batch.block(target), batch.num_records, stage.params)
        rep.device_dispatches += 1
        self._note_traces(stage, traced, rep)
        return RecordBatch(out)

    def _apply_padded(self, stage: SphereStage, batch: RecordBatch,
                      target: int, rep: SphereReport) -> RecordBatch:
        """Pad-stable path: the UDF runs on the stage's fixed block and
        its output STAYS at block shape — the result is a
        padding-resident batch (``n_valid``) handed to the next hop
        as-is, instead of a slice-to-n copy here and a re-pad copy
        there."""
        traced = self._traced_for(stage, stage.batch_udf)
        n = batch.num_records
        out = traced(batch.block(target), n)
        rep.device_dispatches += 1
        self._note_traces(stage, traced, rep)
        if out.shape[0] != target:
            raise ValueError(
                f"stage {stage.name!r} declares pad_value but its batch_udf "
                f"changed the row count ({target} -> {out.shape[0]}); "
                f"pad-stable UDFs must map padding rows to tail padding")
        return RecordBatch(out, n_valid=n)

    def _stage_block_shape(self, job: SphereJob, plan: StagePlan, parts,
                           first_stage: bool) -> int:
        """Fixed block shape for a pad-stable stage: the stage's largest
        task rounded up on the quarter-octave
        {2^k, 1.25 * 2^k, 1.5 * 2^k, 1.75 * 2^k} ladder, floored at
        pad_block.  This shape is computed once per stage, so the finer
        ladder costs no extra traces while capping the junk-tail of
        resident pieces at ~25% worst case — typically a few percent —
        junk the segmented scatter would otherwise mask, scan and fetch
        every round (a pure power-of-two ceiling wastes up to ~100%).
        Row counts come from the plan's task sizes / resident
        partitions, so no batch has to be fetched (or held) to compute
        it."""
        max_rows = 0
        for t in plan.tasks:
            if first_stage:
                rows = t.nbytes // job.record_size
            else:
                batch = parts.get(t.key)
                rows = batch.num_records if batch is not None else 0
            max_rows = max(max_rows, rows)
        if not max_rows:
            return 0
        return _quarter_rows(max_rows, self.pad_block)

    def run_stage(self, job: SphereJob, stage: SphereStage, plan: StagePlan,
                  parts, rep: SphereReport, *, first_stage: bool):
        masked = stage.masked_udf is not None
        pad_stable = (stage.batch_udf is not None
                      and stage.pad_value is not None)
        # the one fixed shape every task of this stage pads to, so the
        # UDF traces exactly once per stage
        target = (self._stage_block_shape(job, plan, parts, first_stage)
                  if masked or pad_stable else 0)
        if self.fused_rounds and pad_stable and target and plan.tasks:
            fused = self._run_stage_fused(job, stage, plan, parts, rep,
                                          first_stage, target)
            if fused is not None:
                return fused
        out: Dict[str, List[RecordBatch]] = {w: [] for w in self.workers}
        if first_stage:
            source = self._stage0_batches(job, plan.tasks, rep)
        else:
            source = ((t, _as_batch(parts.get(t.key))) for t in plan.tasks)
        for t, batch in source:
            if batch is None or not batch.num_records:
                continue
            # same owner-slot rule as the bytes executor: a later stage's
            # partition stays in its owner's slot regardless of where the
            # planner priced the compute
            dst = t.executor if first_stage else t.key
            if masked:
                # a mask-aware stage NEVER leaves the fixed-shape array
                # path — even a single tiny partial batch in a chained
                # reduce job pads up to the block shape rather than
                # silently taking a decode/bytes fallback
                if batch.num_records:
                    out[dst].append(
                        self._apply_masked(stage, batch, target, rep))
            elif pad_stable and target:
                out[dst].append(
                    self._apply_padded(stage, batch, target, rep))
            else:
                # legacy/compat path: bytes-udf decode, per-shape tracing
                # (shape-polymorphic UDFs see exact batches, never junk
                # padding rows)
                out[dst].append(stage.apply_batch(batch.compact()))
                rep.device_dispatches += 1
        return out

    def _check_stacked(self, stage: SphereStage, out, s: int, target: int
                       ) -> None:
        if out.ndim != 3 or out.shape[0] != s or out.shape[1] != target:
            raise ValueError(
                f"stage {stage.name!r} declares pad_value but its batch_udf "
                f"changed the row count ({target} -> {out.shape[1]}); "
                f"pad-stable UDFs must map padding rows to tail padding")

    def _mesh_slots(self, n: int) -> int:
        """Slot count padded up to a multiple of the mesh data axis (the
        shard_map sharding requirement); extra slots ride through with
        zero valid rows.  ``n`` when no mesh is bound."""
        if self.mesh is None:
            return n
        d = self.mesh.shape["data"]
        return -(-n // d) * d

    def _slot_devices(self, owners: Sequence[int]) -> list:
        """The device of each item's slot, for items whose slots are
        ordered by ``owners`` (stable, worker-major): a mesh device
        holds a contiguous run of ``_mesh_slots(n) / d`` slots."""
        devs = list(self.mesh.devices.flat)
        per = self._mesh_slots(len(owners)) // len(devs)
        out = [None] * len(owners)
        for slot, i in enumerate(sorted(range(len(owners)),
                                        key=owners.__getitem__)):
            out[i] = devs[slot // per]
        return out

    def _shard_stack(self, pieces: Sequence[jax.Array], target: int,
                     width: int, rep: SphereReport) -> jax.Array:
        """The [S, target, width] stack of ``pieces`` sharded over the
        mesh, built device by device: each device stacks only its own
        slots' pieces (moved there first, should one sit elsewhere), and
        the slots past ``pieces`` (up to ``_mesh_slots``) are empty."""
        devs = list(self.mesh.devices.flat)
        s = self._mesh_slots(len(pieces))
        per = s // len(devs)
        shards = []
        for k, dev in enumerate(devs):
            own = tuple(jax.device_put(p, dev)
                        for p in pieces[k * per:(k + 1) * per])
            if own:
                shards.append(_stack_rows(own, target=target,
                                          n_empty=per - len(own)))
                rep.device_dispatches += 1
            else:
                shards.append(jax.device_put(
                    np.zeros((per, target, width), np.uint8), dev))
        return jax.make_array_from_single_device_arrays(
            (s, target, width), NamedSharding(self.mesh, P("data")), shards)

    def _aligned_stacked(self, parts) -> Optional[StackedBatch]:
        """The previous fused round's StackedBatch, when every worker's
        resident part is exactly its slot of ONE stack (the steady state
        of chained fused rounds) — lets the next stage consume the stack
        directly with zero per-worker slicing."""
        base: Optional[StackedBatch] = None
        for i, w in enumerate(self.workers):
            p = parts.get(w)
            if p is None:
                continue
            if not isinstance(p, _SlotRef) or p.idx != i:
                return None
            if base is None:
                base = p.stacked
            elif p.stacked is not base:
                return None
        if base is None or base.n_slots != len(self.workers):
            return None
        # empty workers hold None — consistent only if their slot counts
        # are zero (place_buckets guarantees this)
        return base

    def _run_stage_fused(self, job: SphereJob, stage: SphereStage,
                         plan: StagePlan, parts, rep: SphereReport,
                         first_stage: bool, target: int):
        """The whole stage as ONE vmapped UDF dispatch over a stacked
        slot axis.  Slots collect worker-major (ascending slot-worker
        order — the chunk's executor at stage 0, the partition's OWNER
        later, matching the per-worker dict path — plan order within a
        worker, so record order is preserved exactly).  Returns None
        when the stage must take the per-task path (a task placed on an
        unknown worker)."""
        windex = {w: i for i, w in enumerate(self.workers)}
        if any(t.executor not in windex for t in plan.tasks):
            return None
        traced = self._traced_for(stage, stage.batch_udf)
        if not first_stage:
            stacked = self._aligned_stacked(parts)
            if stacked is not None \
                    and stacked.n_slots == self._mesh_slots(stacked.n_slots):
                # steady state: the resident stack IS the stage input
                out = traced.stacked(stacked.data,
                                     jnp.asarray(stacked.n_valid, jnp.int32),
                                     target)
                rep.device_dispatches += 1
                self._note_traces(stage, traced, rep)
                self._check_stacked(stage, out, stacked.n_slots, target)
                return _StackedOut(
                    StackedBatch(out, stacked.n_valid),
                    np.arange(stacked.n_slots, dtype=np.int64))
        items: List[Tuple[int, RecordBatch]] = []
        if first_stage:
            # on a mesh each chunk is put straight on its slot's device
            devices = None if self.mesh is None else self._slot_devices(
                [windex[t.executor] for t in plan.tasks])
            for t, batch in self._stage0_batches(job, plan.tasks, rep,
                                                 devices):
                if batch is not None and batch.num_records:
                    items.append((windex[t.executor], batch))
        else:
            for t in plan.tasks:
                batch = _as_batch(parts.get(t.key))
                if batch is not None and batch.num_records:
                    items.append((windex[t.key], batch))
        if not items:
            # nothing to run — return the legacy-shaped empty dict
            # directly (falling back to the per-task loop would replay
            # the stage-0 fetches, double-counting retries)
            return {w: [] for w in self.workers}
        items.sort(key=lambda p: p[0])          # stable: worker-major
        n_valid = np.fromiter((b.num_records for _, b in items), np.int32,
                              count=len(items))
        slot_workers = np.fromiter((i for i, _ in items), np.int64,
                                   count=len(items))
        pieces = [b.data for _, b in items]
        if self.mesh is None:
            out = traced.stack_pieces(pieces,
                                      jnp.asarray(n_valid, jnp.int32), target)
        else:
            pad_slots = self._mesh_slots(len(items)) - len(items)
            n_valid = np.concatenate([n_valid,
                                      np.zeros(pad_slots, np.int32)])
            slot_workers = np.concatenate(
                [slot_workers, np.zeros(pad_slots, np.int64)])
            stack = self._shard_stack(pieces, target,
                                      items[0][1].record_size, rep)
            out = traced.stacked(stack, jnp.asarray(n_valid, jnp.int32),
                                 target)
        rep.device_dispatches += 1
        self._note_traces(stage, traced, rep)
        self._check_stacked(stage, out, len(n_valid), target)
        return _StackedOut(StackedBatch(out, n_valid), slot_workers)

    # ----------------------------------------------------------- shuffle
    def _bucketize_mesh(self, stage: SphereStage, out: _StackedOut, n: int,
                        rep: SphereReport):
        """The fused round through ``shard_map`` + ``all_to_all`` (see
        ``core.spmd.fused_scatter_round``).  Returns None when the round
        cannot ride the mesh (indivisible slot/worker counts, host-loop
        partitioner) — the caller then uses the single-device fused
        lowering."""
        from repro.core.shuffle import ReducePartitioner
        from repro.core.spmd import (fused_scatter_round, mesh_round_capacity,
                                     mesh_round_regrow)
        stacked = out.stacked
        W, S = len(self.workers), stacked.n_slots
        d = self.mesh.shape["data"]
        if W % d or S % d or n <= 1 \
                or isinstance(stage.partitioner, ReducePartitioner) \
                or getattr(stage.partitioner, "scatter_spec", None) is None:
            return None
        spec = stage.partitioner.scatter_spec(
            RecordBatch.empty(stacked.record_size), n)
        if spec is None:
            return None
        key_spec, bounds = spec
        rows = stacked.block_rows
        cap, wcap = mesh_round_capacity(stacked.n_valid, rows, n_buckets=n,
                                        n_workers=W, d=d)
        n_valid = jnp.asarray(stacked.n_valid, jnp.int32)
        rep.shuffle_rounds += 1
        rep.mesh_rounds += 1
        with self.tracer.span("shuffle-round", track="shuffle",
                              attrs={"backend": "array", "path": "mesh",
                                     "buckets": n}) as sp:
            regrow = False
            while True:
                parts_dev, counts_dev, hist_dev, over_dev = \
                    fused_scatter_round(stacked.data, n_valid, bounds,
                                        key_spec=key_spec, n_buckets=n,
                                        n_workers=W, mesh=self.mesh,
                                        cap=cap, wcap=wcap)
                rep.device_dispatches += 1
                counts, hist_sb, over = jax.device_get(
                    (counts_dev, hist_dev, over_dev))
                rep.host_syncs += 1
                if self.tracer.enabled:
                    self.tracer.instant("host-sync", track="host-sync",
                                        attrs={"where": "mesh-harvest"})
                if not over.any():
                    break
                # a section or a partition outgrew its capacity and left
                # records out: redo the round from the same input, larger
                cap, wcap = mesh_round_regrow(hist_sb, rows, cap, wcap,
                                              n_workers=W, d=d)
                rep.exchange_regrows += 1
                regrow = True
            sp.set_attrs(capacity=cap, regrow=regrow)
            origin_counts = np.zeros((n, W), np.int64)
            for s in range(S):
                origin_counts[:, int(out.slot_workers[s])] += hist_sb[s]
            origins: Origins = [
                {self.workers[w]:
                 int(origin_counts[b, w]) * stacked.record_size
                 for w in np.nonzero(origin_counts[b])[0]}
                for b in range(n)]
            result = FusedRoundResult(parts_dev, counts.astype(np.int64),
                                      origins, 1)
            rep.partitioned_records += stacked.num_records
            if self.timing_sync:
                jax.block_until_ready(result.data)
        rep.partition_seconds += sp.wall_seconds
        return result, origins

    def _bucketize_fused(self, stage: SphereStage, out: _StackedOut, n: int,
                         rep: SphereReport):
        """One fused shuffle round: O(1) dispatches, one host sync, one
        regrouping gather — regardless of task or worker count.  Returns
        None when the round cannot stay on the fused kernel path (the
        caller downgrades to the per-worker loop)."""
        if self.mesh is not None:
            mesh_res = self._bucketize_mesh(stage, out, n, rep)
            if mesh_res is not None:
                return mesh_res
        rd = scatter_round_dispatch(out.stacked, stage.partitioner, n,
                                    worker_names=self.workers,
                                    slot_workers=out.slot_workers,
                                    pad_block=self.pad_block)
        if rd is None:
            return None
        rep.shuffle_rounds += 1
        with self.tracer.span("shuffle-round", track="shuffle",
                              attrs={"backend": "array", "path": "fused",
                                     "lowering": rd.mode,
                                     "buckets": n}) as sp:
            rep.device_dispatches += rd.dispatches
            synced = jax.device_get(rd.sync_arrays)  # the round's ONE sync
            rep.host_syncs += 1
            if self.tracer.enabled:
                self.tracer.instant("host-sync", track="host-sync",
                                    attrs={"where": "fused-harvest"})
            result = rd.harvest(synced)
            rep.device_dispatches += result.dispatches
            rep.partitioned_records += out.stacked.num_records
            if self.timing_sync:
                if result.data is not None:
                    jax.block_until_ready(result.data)
                elif result.groups:
                    jax.block_until_ready([g for _, g in result.groups])
        rep.partition_seconds += sp.wall_seconds
        return result, result.origins

    def bucketize(self, stage: SphereStage, out, n: int, rep: SphereReport
                  ) -> Tuple[List[List[RecordBatch]], Origins]:
        """Dispatch-then-sync array shuffle.

        Phase 1 enqueues each worker's scatter without blocking —
        :func:`scatter_pieces_dispatch` takes the worker's resident
        pieces straight into ONE jitted call (stack + junk-tail mask +
        key-extract + kernel trace as one fused program; no eager
        concat-and-re-pad copy) whenever the pieces share a ladder
        shape, and concatenates to the shape ladder otherwise.  Phase 2
        harvests every dispatch's metadata behind ONE barrier and
        resolves each worker's per-bucket pieces.  One kernel-path
        shuffle round therefore costs exactly one host sync —
        ``rep.host_syncs`` advances by 1 per round, not by the worker
        count — which is the invariant tests assert.  Degenerate
        batches (reduce rounds, single bucket) resolve at dispatch
        time; a round of only those syncs zero times (host-loop
        fallbacks excepted — they pay their sync at dispatch and say
        so).

        Batches pad to power-of-two-ladder row counts (floored at
        ``pad_block``), so the kernel traces once per padded shape, not
        once per batch size; padding-resident stage outputs feed the
        scatter at their resident shape (junk tails ride to the kernel's
        trash bucket) instead of being sliced and re-padded.

        With ``fused_rounds`` the stage output arrives stacked and the
        whole round — every worker's scatter plus the regrouping onto
        destination workers — runs through :func:`scatter_round_dispatch`
        (or ``spmd.fused_scatter_round`` on a mesh) instead of this loop,
        keeping ``device_dispatches`` O(1) per round."""
        if self.timing_sync:
            # start-of-timing barrier (benchmarks only, same policy as
            # the stop barrier below): ``partition_seconds`` measures
            # the shuffle round alone, so drain the stage's async
            # output before starting the clock.  The fused round is one
            # dependency chain — its single sync would otherwise charge
            # the stacked UDF apply to the round, where the per-worker
            # loop's many small dispatches drain on their own during
            # intervening host work.
            if isinstance(out, _StackedOut):
                jax.block_until_ready(out.stacked.data)
            else:
                jax.block_until_ready([p.data for ps in out.values()
                                       for p in ps])
        if isinstance(out, _StackedOut):
            fused = self._bucketize_fused(stage, out, n, rep)
            if fused is not None:
                return fused
            # ineligible round (reduce partitioner, single bucket, odd
            # record widths): downgrade to the per-worker loop
            out = out.to_worker_dict(self.workers)
        buckets: List[List[RecordBatch]] = [[] for _ in range(n)]
        origins: Origins = [{} for _ in range(n)]
        rep.shuffle_rounds += 1
        with self.tracer.span("shuffle-round", track="shuffle",
                              attrs={"backend": "array",
                                     "path": "per-worker",
                                     "buckets": n}) as sp:
            round_: List[Tuple[str, int, object]] = []
            for w in self.workers:                  # phase 1: dispatch all
                pieces = out[w]
                if not pieces:
                    continue
                disp = scatter_pieces_dispatch(pieces, stage.partitioner, n,
                                               pad_block=self.pad_block)
                rep.host_syncs += disp.host_syncs
                if disp.host_syncs and self.tracer.enabled:
                    self.tracer.instant(
                        "host-sync", track="host-sync",
                        attrs={"where": "dispatch-fallback", "worker": w,
                               "count": disp.host_syncs})
                rep.device_dispatches += 1          # the worker's scatter
                round_.append((w, sum(p.num_records for p in pieces), disp))
            pending = [d for (_, _, d) in round_ if d.pending]
            if pending:                             # phase 2: one barrier
                synced = jax.device_get([d.sync_arrays for d in pending])
                rep.host_syncs += 1
                if self.tracer.enabled:
                    self.tracer.instant(
                        "host-sync", track="host-sync",
                        attrs={"where": "round-barrier",
                               "dispatches": len(pending)})
                for d, s in zip(pending, synced):
                    d.harvest(synced=s)
                    rep.device_dispatches += d.n    # per-bucket slices
            for w, nrec, disp in round_:
                for i, piece in enumerate(disp.harvest()):
                    if piece.num_records:
                        buckets[i].append(piece)
                        origins[i][w] = piece.nbytes
                rep.partitioned_records += nrec
            if self.timing_sync:
                jax.block_until_ready([p.data for bucket in buckets
                                       for p in bucket])
        rep.partition_seconds += sp.wall_seconds
        return buckets, origins

    def place_buckets(self, buckets, parts) -> None:
        # bucket i lives on worker i % len(workers); a destination holding
        # several buckets keeps them in bucket order (matching the bytes
        # path's append order), merged into one device-resident batch
        if isinstance(buckets, FusedRoundResult):
            # the fused round already regrouped on device: slot i of the
            # stacked result IS worker i's merged partition — parts hold
            # zero-copy views into the stack, so chained stages restack
            # for free (see _aligned_stacked)
            if buckets.groups is not None:
                # big rounds arrive as a few worker-contiguous group
                # stacks (gather rows per call are capped, see
                # FusedRoundResult.groups); every worker still gets a
                # zero-copy view into its group's stack
                for w0, arr in buckets.groups:
                    g = StackedBatch(arr,
                                     buckets.counts[w0:w0 + arr.shape[0]])
                    for j in range(arr.shape[0]):
                        parts[self.workers[w0 + j]] = (
                            _SlotRef(g, j) if int(g.n_valid[j]) else None)
                return
            if buckets.data is None:
                for w in self.workers:
                    parts[w] = None
                return
            stacked = StackedBatch(buckets.data, buckets.counts)
            for i, w in enumerate(self.workers):
                parts[w] = (_SlotRef(stacked, i)
                            if int(stacked.n_valid[i]) else None)
            return
        incoming: Dict[str, List[RecordBatch]] = {w: [] for w in self.workers}
        for i, pieces in enumerate(buckets):
            incoming[self.workers[i % len(self.workers)]].extend(pieces)
        for w in self.workers:
            parts[w] = (RecordBatch.concat(incoming[w])
                        if incoming[w] else None)

    def set_parts(self, parts, out) -> None:
        if isinstance(out, _StackedOut):
            # partitionerless stage: each worker keeps its own slots
            slots: Dict[str, List[int]] = {w: [] for w in self.workers}
            for s, wi in enumerate(out.slot_workers):
                if int(out.stacked.n_valid[s]):
                    slots[self.workers[int(wi)]].append(s)
            for w in self.workers:
                own = slots[w]
                if not own:
                    parts[w] = None
                elif len(own) == 1:
                    parts[w] = _SlotRef(out.stacked, own[0])
                else:
                    parts[w] = RecordBatch.concat(
                        [out.stacked.slot(s) for s in own])
            return
        for w in self.workers:
            parts[w] = RecordBatch.concat(out[w]) if out[w] else None

    def outputs(self, parts) -> List[bytes]:
        """The ONLY host materialisation of record data after stage 0.
        A stack slot (``_SlotRef``) of at least ``SLAB_MIN_BYTES`` padded
        bytes crosses as a :func:`_slab` of the one-device shard that
        holds it, the whole stack when it sits on one device (attr
        ``layout=slab``).  Every other partition crosses as its rows
        (``layout=rows``): small slots, and 2-D batches, whose row count
        a concat sets from the data, so a pack of one would compile once
        per record count.

        Copies are issued ahead of the host's: ``d2h-issue`` dispatches a
        partition's pack and starts its ``copy_to_host_async``, in worker
        order, while each of its devices holds fewer than two issued
        copies (the one being turned into ``bytes`` and the next), so a
        transfer runs under the previous partition's ``tobytes`` and
        different devices' transfers run together.  Then, per partition
        in worker order, ``output-wait`` holds the wait for the device
        work behind it and ``d2h`` the copy to the host: ``d2h-transfer``
        the wait for what is left of the issued copy, ``d2h-tobytes`` its
        valid bytes to ``bytes``.  ``d2h`` attr ``ahead``: the copy was
        issued before the previous partition's ``d2h-tobytes`` began;
        ``materialise`` attr ``ahead`` counts them."""
        with self.tracer.span("materialise", track="output") as msp:
            todo = []
            for w in self.workers:
                part = parts[w]
                if part is None or not part.num_records:
                    continue
                data = None
                if isinstance(part, _SlotRef):
                    data, idx = _slot_shard(part.stacked.data, part.idx)
                if data is not None and (data.shape[-2] * data.shape[-1]
                                         >= SLAB_MIN_BYTES):
                    todo.append((data, idx, part.nbytes))
                else:
                    todo.append((_as_batch(part).data, None, part.num_records))
            out, issued, live = [], collections.deque(), collections.Counter()
            nxt = n_ahead = issued_before = 0
            for k, (data, idx, end) in enumerate(todo):
                while nxt < len(todo) and all(
                        live[d] < 2 for d in todo[nxt][0].devices()):
                    issued.append(self._issue_copy(*todo[nxt][:2]))
                    live.update(todo[nxt][0].devices())
                    nxt += 1
                ahead = k < issued_before
                with self.tracer.span("output-wait", track="output"):
                    jax.block_until_ready(data)
                with self.tracer.span("d2h", track="output") as sp:
                    with self.tracer.span("d2h-transfer", track="output"):
                        arr = issued.popleft()
                        host = np.asarray(arr)
                        if idx is not None:
                            host = host.view(np.uint8).reshape(-1)
                    issued_before = nxt
                    with self.tracer.span("d2h-tobytes", track="output"):
                        # valid records only: padding never leaks out
                        out.append(host[:end].tobytes())
                    sp.set_attrs(bytes=len(out[-1]), ahead=ahead,
                                 layout="rows" if idx is None else "slab")
                # the issued copy and its host array go before the next
                # partition, so at most the window is live
                del arr, host
                live.subtract(data.devices())
                n_ahead += ahead
            msp.set_attrs(ahead=n_ahead)
        return out

    def _issue_copy(self, data: jax.Array, idx) -> jax.Array:
        """``data`` (rows; ``idx`` None) or its slot ``idx`` packed as a
        slab, with its copy to the host started."""
        ids = sorted(d.id for d in data.devices())
        with self.tracer.span("d2h-issue", track="output", attrs={
                "device": ids[0] if len(ids) == 1 else ids}):
            arr = data if idx is None else _slab(data, idx)
            arr.copy_to_host_async()
        return arr


def make_executor(backend: str, client, workers: Sequence[str], *,
                  max_retries: int = 3, pad_block: int = 4096,
                  cache_chunks: bool = False, prefetch: bool = True,
                  prefetch_depth: int = 1, timing_sync: bool = False,
                  fused_rounds: bool = True, mesh=None, tracer=None):
    if backend == "array":
        return ArrayExecutor(client, workers, max_retries=max_retries,
                             pad_block=pad_block, cache_chunks=cache_chunks,
                             prefetch=prefetch, prefetch_depth=prefetch_depth,
                             timing_sync=timing_sync,
                             fused_rounds=fused_rounds, mesh=mesh,
                             tracer=tracer)
    return BytesExecutor(client, workers, max_retries=max_retries,
                         cache_chunks=cache_chunks, prefetch=prefetch,
                         prefetch_depth=prefetch_depth, tracer=tracer)
