"""Array-native record batches for the Sphere engine.

The paper's Sphere engine streams fixed-size records between UDF stages;
the seed implementation models a record as a Python ``bytes`` object and
pays a Python-level loop (md5 / binary search per record) in the shuffle.
``RecordBatch`` packs the same records into a single ``uint8 [n, width]``
JAX array so that key extraction, partitioning (via the Pallas
``bucket_partition`` kernel) and record movement are single vectorised
array operations.

Conventions shared by the bytes reference path and the array path:

* **Range keys** are rows of big-endian ``uint32`` words covering a
  record's key prefix (``key_words`` — the tail word is zero-padded, and
  an optional trailing length word breaks ties exactly like Python's
  shorter-prefix-sorts-first rule).  Comparing word rows
  lexicographically is identical to comparing the byte prefixes, so the
  array path agrees with ``range_partitioner`` record-for-record for
  boundaries of any length (10-byte TeraSort keys use 3 words).
* **Hash keys** are FNV-1a 32-bit over the first ``key_bytes`` bytes —
  ``fnv1a32`` is the scalar reference, ``hash_keys_u32`` the vectorised
  twin.  Both paths then map the hash onto buckets by counting the
  ``uniform_hash_bounds`` thresholds below it, which is exactly the
  comparison the Pallas kernel implements.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

FNV_OFFSET32 = 0x811C9DC5
FNV_PRIME32 = 0x01000193


def fnv1a32(data: bytes) -> int:
    """Scalar FNV-1a 32-bit — the reference for ``hash_keys_u32``."""
    h = FNV_OFFSET32
    for b in data:
        h = ((h ^ b) * FNV_PRIME32) & 0xFFFFFFFF
    return h


def uniform_hash_bounds(n_buckets: int) -> np.ndarray:
    """Sorted uint32 thresholds splitting hash space into n equal ranges.

    ``bucket(h) = #{i : bounds[i] < h}`` — the same "count boundaries
    below the key" rule the bucket_partition kernel computes, so one
    kernel serves both hash and range partitioning.
    """
    return np.array([(((i + 1) << 32) // n_buckets) - 1
                     for i in range(n_buckets - 1)], dtype=np.uint32)


@dataclass(frozen=True)
class RecordBatch:
    """Fixed-width records packed as a uint8 [rows, record_size] array.

    A batch may be *padding-resident*: ``n_valid`` (when set) says only
    the first ``n_valid`` rows are real records and the tail rows are
    shape padding whose CONTENT IS JUNK — never normalised, never
    inspected.  Every consumer of a possibly-padded batch either slices
    the valid prefix (``valid_data`` / the codecs below), masks the tail
    inside a jitted call (pad-stable / mask-aware stage UDFs normalise
    padding to their declared pad byte on device), or routes it to the
    scatter kernel's trash bucket (``scatter_batch``'s dynamic
    ``n_valid``).  This is what lets the engine pass fixed-shape blocks
    between stages and shuffles without a slice-then-repad copy per hop.
    ``n_valid is None`` means every row is real (the pre-existing exact
    batch — all constructors outside the executor produce these).
    """

    data: jax.Array
    n_valid: Optional[int] = None

    def __post_init__(self):
        if self.data.ndim != 2:
            raise ValueError(f"RecordBatch data must be 2-D, "
                             f"got shape {self.data.shape}")
        if self.n_valid is not None:
            if not 0 <= self.n_valid <= self.data.shape[0]:
                raise ValueError(f"n_valid {self.n_valid} outside "
                                 f"[0, {self.data.shape[0]}]")
            if self.n_valid == self.data.shape[0]:
                # a fully-valid batch IS an exact batch — normalising to
                # None keeps "padded" meaning strictly padded (and the
                # concat fast path returning `is`-identical batches)
                object.__setattr__(self, "n_valid", None)

    # ------------------------------------------------------------ shape
    @property
    def num_records(self) -> int:
        """Real (valid) records — NOT the padded row count."""
        return self.n_valid if self.n_valid is not None \
            else self.data.shape[0]

    @property
    def padded_rows(self) -> int:
        """Physical rows of the resident block, padding included."""
        return self.data.shape[0]

    @property
    def record_size(self) -> int:
        return self.data.shape[1]

    @property
    def nbytes(self) -> int:
        """Valid payload bytes — padding is free, so planner movement
        pricing and part sizes agree with the bytes backend exactly."""
        return self.num_records * self.data.shape[1]

    # ---------------------------------------------------- padding views
    @property
    def valid_data(self) -> jax.Array:
        """The [num_records, record_size] valid prefix (zero-copy for
        exact batches)."""
        return self.data if self.n_valid is None else self.data[:self.n_valid]

    def compact(self) -> "RecordBatch":
        """An exact batch holding only the valid rows (self when already
        exact)."""
        return self if self.n_valid is None \
            else RecordBatch(self.data[:self.n_valid])

    def block(self, n_rows: int) -> jax.Array:
        """A [n_rows, record_size] block whose first ``num_records`` rows
        are the valid records — tail content is JUNK (reused resident
        padding, or zeros when the block grows).  This is the no-copy
        hand-off into fixed-shape jitted consumers: same shape reuses the
        resident array as-is, a larger resident block is prefix-sliced.
        """
        n = self.num_records
        if n > n_rows:
            raise ValueError(f"cannot fit {n} records in a {n_rows}-row "
                             f"block")
        rows = self.data.shape[0]
        if rows == n_rows:
            return self.data
        if rows > n_rows:
            return self.data[:n_rows]
        return jnp.pad(self.data, ((0, n_rows - rows), (0, 0)))

    # ------------------------------------------------------------ codecs
    @staticmethod
    def from_bytes(blob: bytes, record_size: int, device=None
                   ) -> "RecordBatch":
        """The records of ``blob`` on ``device`` (the default device
        where None)."""
        if record_size <= 0:
            raise ValueError("array backend needs a fixed record_size > 0")
        if len(blob) % record_size:
            raise ValueError(f"blob of {len(blob)} bytes is not a multiple "
                             f"of record_size {record_size}")
        arr = np.frombuffer(blob, np.uint8).reshape(-1, record_size)
        return RecordBatch(jnp.asarray(arr) if device is None
                           else jax.device_put(arr, device))

    @staticmethod
    def from_records(records: Sequence[bytes]) -> "RecordBatch":
        if not records:
            raise ValueError("cannot infer record_size from zero records")
        width = len(records[0])
        if any(len(r) != width for r in records):
            raise ValueError("RecordBatch requires uniform record size")
        return RecordBatch.from_bytes(b"".join(records), width)

    def to_bytes(self) -> bytes:
        # valid rows only — padding never leaks into materialised output
        return np.asarray(self.data)[:self.num_records].tobytes()

    def to_records(self) -> List[bytes]:
        raw = np.asarray(self.data)
        return [raw[i].tobytes() for i in range(self.num_records)]

    # ------------------------------------------------------ restructuring
    @staticmethod
    def empty(record_size: int) -> "RecordBatch":
        return RecordBatch(jnp.zeros((0, record_size), jnp.uint8))

    @staticmethod
    def concat(batches: Sequence["RecordBatch"]) -> "RecordBatch":
        """Concatenate valid records.  A single non-empty input returns
        ITSELF (no copy — and a padding-resident batch stays resident);
        multi-input concat materialises the valid prefixes."""
        if not batches:
            raise ValueError("cannot concat zero batches")
        nonempty = [b for b in batches if b.num_records]
        if not nonempty:
            return batches[0]
        if len(nonempty) == 1:
            return nonempty[0]
        return RecordBatch(jnp.concatenate([b.valid_data for b in nonempty],
                                           axis=0))

    @staticmethod
    def concat_block(batches: Sequence["RecordBatch"], n_rows: int
                     ) -> "RecordBatch":
        """Concatenate valid records straight into an ``n_rows`` block —
        the concat+pad fusion.  The result is padding-resident (zeros
        tail) at exactly ``n_rows`` rows, so a downstream
        ``block(n_rows)`` hands the array over untouched: one copy total
        where ``concat`` + ``block`` would pay two.  A single non-empty
        input already at ``n_rows`` rows returns ITSELF."""
        if not batches:
            raise ValueError("cannot concat zero batches")
        nonempty = [b for b in batches if b.num_records]
        if len(nonempty) == 1 and nonempty[0].padded_rows == n_rows:
            return nonempty[0]
        nrec = sum(b.num_records for b in nonempty)
        if nrec > n_rows:
            raise ValueError(f"cannot fit {nrec} records in a {n_rows}-row "
                             f"block")
        width = batches[0].record_size
        parts = [b.valid_data for b in nonempty]
        if nrec < n_rows:
            parts.append(jnp.zeros((n_rows - nrec, width), jnp.uint8))
        return RecordBatch(jnp.concatenate(parts, axis=0), n_valid=nrec)

    def take(self, idx) -> "RecordBatch":
        """Gather rows by index.  Valid rows always form the block's
        prefix, so indices < ``num_records`` address the same records on
        exact and padding-resident batches alike."""
        return RecordBatch(jnp.take(self.data, jnp.asarray(idx), axis=0))

    def pad_to(self, n_rows: int, pad_value: int = 0) -> "RecordBatch":
        """Right-pad with MATERIALISED ``pad_value`` rows up to ``n_rows``
        and return an exact batch — the explicit-padding legacy/API path
        (the executor's hot path uses :meth:`block`, whose padding stays
        junk and is normalised on device instead)."""
        n = self.num_records
        if n_rows < n:
            raise ValueError(f"cannot pad {n} records down to {n_rows}")
        if n_rows == n:
            return self.compact() if self.n_valid is not None else self
        return RecordBatch(jnp.pad(self.valid_data,
                                   ((0, n_rows - n), (0, 0)),
                                   constant_values=pad_value))

    # --------------------------------------------------------------- keys
    # Key views are BLOCK-level: they cover every physical row, padding
    # included (the scatter kernel trash-buckets rows >= its dynamic
    # n_valid, and in-jit callers see normalised padding).  Host-side
    # analysis paths compact() a padding-resident batch first.
    def keys_u32(self, width: int = 4) -> jax.Array:
        """Big-endian uint32 of each record's first ``width`` (<= 4) bytes,
        zero-padded — order-isomorphic to lexicographic comparison of the
        same ``width``-byte prefixes.
        """
        w = min(width, 4, self.record_size)
        d = self.data[:, :w]
        if w < 4:
            d = jnp.pad(d, ((0, 0), (0, 4 - w)))
        k = d.astype(jnp.uint32)
        return (k[:, 0] << 24) | (k[:, 1] << 16) | (k[:, 2] << 8) | k[:, 3]

    def hash_keys_u32(self, key_bytes: int) -> jax.Array:
        """Vectorised FNV-1a 32-bit over each record's first key_bytes."""
        d = self.data
        h = jnp.full((d.shape[0],), FNV_OFFSET32, jnp.uint32)
        for j in range(min(key_bytes, d.shape[1])):
            h = (h ^ d[:, j].astype(jnp.uint32)) * jnp.uint32(FNV_PRIME32)
        return h

    def _key_words(self, key_bytes: int) -> List[jax.Array]:
        """Big-endian uint32 words covering the first key_bytes bytes.

        The tail word is zero-padded — payload bytes past key_bytes must
        not leak into the sort key (ties keep the stable input order,
        matching the bytes backend's ``sorted(key=r[:kb])``).
        """
        d = self.data
        kb = min(key_bytes, d.shape[1])
        d = d[:, :kb]
        pad = (-kb) % 4
        if pad:
            d = jnp.pad(d, ((0, 0), (0, pad)))
        words = []
        for i in range(0, kb, 4):
            w = d[:, i:i + 4].astype(jnp.uint32)
            words.append((w[:, 0] << 24) | (w[:, 1] << 16)
                         | (w[:, 2] << 8) | w[:, 3])
        return words

    def key_words(self, key_bytes: int, *, n_words: int | None = None,
                  length_word: int | None = None) -> jax.Array:
        """[n, k] big-endian uint32 key rows for the multi-word kernel.

        The first ``key_bytes`` bytes of each record, zero-padded into
        4-byte words.  ``n_words`` right-pads with zero columns (aligning
        a batch against a wider boundary table); ``length_word`` appends
        one constant trailing word so variable-length boundary strings
        compare exactly like Python ``bytes`` (when the zero-padded words
        tie, the shorter string sorts first).
        """
        words = self._key_words(key_bytes)
        n = self.num_records
        if n_words is not None:
            while len(words) < n_words:
                words.append(jnp.zeros((n,), jnp.uint32))
        if not words:
            words.append(jnp.zeros((n,), jnp.uint32))
        if length_word is not None:
            words.append(jnp.full((n,), length_word, jnp.uint32))
        return jnp.stack(words, axis=1)

    def sort_by_key(self, key_bytes: int) -> "RecordBatch":
        """Stable sort by the full key prefix (lexicographic, any length).

        Sorts the VALID records (junk padding rows must not interleave);
        pad-stable stage UDFs call this on in-jit blocks whose padding
        was already normalised, where compact() is a no-op."""
        base = self.compact()
        words = base._key_words(key_bytes)
        # jnp.lexsort treats the LAST key as primary
        order = jnp.lexsort(tuple(reversed(words)))
        return base.take(order)

    # ------------------------------------------------------- float views
    def to_points(self, dim: int) -> jax.Array:
        """Reinterpret valid records as little-endian float32 [n, dim]
        points (junk padding rows would bitcast to garbage floats)."""
        if self.record_size != 4 * dim:
            raise ValueError(f"record_size {self.record_size} != 4*dim")
        return jax.lax.bitcast_convert_type(
            self.valid_data.reshape(self.num_records, dim, 4), jnp.float32)

    @staticmethod
    def from_points(points: jax.Array) -> "RecordBatch":
        """float32 [n, d] points -> records of d*4 bytes each."""
        n, d = points.shape
        raw = jax.lax.bitcast_convert_type(points.astype(jnp.float32),
                                           jnp.uint8)
        return RecordBatch(raw.reshape(n, d * 4))


def _pow2_rows(n: int, floor: int) -> int:
    """Smallest padded row count >= n from the {2^k, 1.5 * 2^k} ladder,
    floored at ``floor`` — the fixed shapes batches pad to so kernel
    traces are shared across batch sizes.  The half-octave step caps
    padding waste at ~33% (a pure power-of-two ladder can waste ~100%)
    while keeping the number of distinct traced shapes per octave at 2."""
    target = max(floor, 2)
    while target < n:
        if target + target // 2 >= n:
            return target + target // 2
        target *= 2
    return target


def _quarter_rows(n: int, floor: int) -> int:
    """Smallest padded row count >= n from the quarter-octave
    {2^k, 1.25*2^k, 1.5*2^k, 1.75*2^k} ladder, floored at ``floor``.

    Finer than :func:`_pow2_rows` on purpose: the once-per-stage block
    shape is computed a single time from the plan's largest task, so a
    denser ladder costs no extra traces there — and it caps the
    junk-tail at ~25% worst case (typically a few percent) where the
    half-octave ladder allows ~33%.  That junk tail is not free: every
    padding row rides through the segmented scatter's mask, kernel scan
    and destination fetch each round (e.g. 5 000-record stage-0 chunks
    pad to 5 120 here vs 6 144 on the half-octave ladder — an 18%
    shuffle-volume cut at the TeraSort 1M scale).  Ad-hoc batch padding
    (``scatter_batch``) keeps the coarser ladder, where fewer rungs
    means more trace sharing across varying batch sizes."""
    base = max(floor, 4)
    while base * 2 < n:
        base *= 2
    if n <= base:
        return base
    for num in (5, 6, 7):
        cand = base * num // 4
        if cand >= n:
            return cand
    return base * 2


@dataclass(frozen=True)
class StackedBatch:
    """A whole round's worth of batches as ONE device array.

    ``data`` is uint8 [n_slots, block, width]: one slot per task/worker
    of a fused engine round, every slot padded to the same quarter-octave
    ``block`` row count so the stack is a single rectangular array.
    ``n_valid`` is a HOST [n_slots] int32 vector of real row counts —
    slot tails are junk padding exactly as in a padding-resident
    :class:`RecordBatch`, and keeping the counts host-side means shape
    queries (part sizes, plan block shapes) never touch the device.

    This is the unit the fused data plane operates on: one vmapped UDF
    call, one stacked scatter dispatch and one regrouping gather per
    round, instead of a Python loop of per-slot dispatches.  A slot with
    ``n_valid == 0`` is a real (empty) participant — empty workers ride
    through the fused round for free rather than forcing a fallback.
    """

    data: jax.Array
    n_valid: np.ndarray

    def __post_init__(self):
        if self.data.ndim != 3:
            raise ValueError(f"StackedBatch data must be 3-D, "
                             f"got shape {self.data.shape}")
        nv = np.asarray(self.n_valid, dtype=np.int32)
        if nv.shape != (self.data.shape[0],):
            raise ValueError(f"n_valid shape {nv.shape} != "
                             f"({self.data.shape[0]},)")
        if nv.size and (int(nv.min()) < 0
                        or int(nv.max()) > self.data.shape[1]):
            raise ValueError(f"n_valid outside [0, {self.data.shape[1]}]")
        object.__setattr__(self, "n_valid", nv)

    # ------------------------------------------------------------ shape
    @property
    def n_slots(self) -> int:
        return self.data.shape[0]

    @property
    def block_rows(self) -> int:
        """Padded rows per slot (every slot shares one block shape)."""
        return self.data.shape[1]

    @property
    def record_size(self) -> int:
        return self.data.shape[2]

    @property
    def num_records(self) -> int:
        """Real records across all slots."""
        return int(self.n_valid.sum())

    @property
    def nbytes(self) -> int:
        """Valid payload bytes across all slots (padding is free)."""
        return self.num_records * self.record_size

    # ------------------------------------------------------- conversions
    def slot(self, i: int) -> RecordBatch:
        """Slot ``i`` as a padding-resident RecordBatch (device slice)."""
        return RecordBatch(self.data[i], n_valid=int(self.n_valid[i]))

    def unpack(self) -> List[RecordBatch]:
        return [self.slot(i) for i in range(self.n_slots)]

    @staticmethod
    def pack(batches: Sequence[RecordBatch], block: int | None = None,
             pad_block: int = 4096) -> "StackedBatch":
        """Stack batches into one [s, block, width] array.

        ``block`` defaults to the quarter-octave ladder shape of the
        largest batch (floored at ``pad_block``) so every slot shares
        one padded shape; slot tails are junk, never materialised.
        NOTE: this is the eager convenience — the executor's hot path
        stacks inside its jitted UDF call instead, so the concat fuses
        with the stage body (see ``_TracedUDF``)."""
        if not batches:
            raise ValueError("cannot stack zero batches")
        width = batches[0].record_size
        if any(b.record_size != width for b in batches):
            raise ValueError("StackedBatch requires uniform record size")
        n_valid = np.fromiter((b.num_records for b in batches), np.int32,
                              count=len(batches))
        if block is None:
            block = _quarter_rows(max(int(n_valid.max()), 1), pad_block)
        data = jnp.stack([b.block(block) for b in batches])
        return StackedBatch(data, n_valid)


def scatter_by_ids(batch: RecordBatch, ids, hist) -> List[RecordBatch]:
    """Split a batch into per-bucket batches given kernel (ids, hist).

    One stable argsort of the bucket ids, then one contiguous gather per
    bucket — record order within a bucket matches the bytes backend's
    append order.  The argsort runs on the host: numpy's radix sort beats
    XLA:CPU's generic sort by ~20x, and ids are a tiny [n] int32 array.
    """
    ids_np = np.asarray(ids)
    hist_np = np.asarray(hist)
    order = np.argsort(ids_np, kind="stable")
    pieces = np.split(order, np.cumsum(hist_np)[:-1])
    return [batch.take(p.astype(np.int32)) for p in pieces]
