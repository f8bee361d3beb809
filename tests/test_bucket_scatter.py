"""Device-resident scatter parity: ``scatter_batch`` == bytes append order.

The engine's array-backend shuffle (`ArrayExecutor.bucketize` ->
``scatter_batch`` -> ``bucket_scatter``) replaces the per-record bytes
loop, so these tests hold it to the same contract the ids/histogram
parity suite holds ``partition_batch`` to:

- **bucket boundaries**: the strict ``#{bounds < key}`` rule, including
  boundary-equal keys, zero-tail multi-word ties, and variable-length
  boundaries (the trailing length word);
- **stability**: records in the same bucket keep input order — the
  bytes backend's append order, byte for byte;
- **the kernel itself** against the numpy oracle ``bucket_scatter_ref``,
  across block counts, internal padding, and dynamic ``n_valid`` reuse
  of one traced shape.

Everything runs interpret-mode on CPU; ``requires_accelerator`` marks
the compiled (non-interpret) cases, auto-skipped off-TPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.records import RecordBatch
from repro.core.shuffle import (hash_partitioner, range_partitioner,
                                reduce_partitioner, sample_boundaries,
                                scatter_batch, scatter_dispatch,
                                scatter_pieces_dispatch)
from repro.kernels.bucket_partition import bucket_scatter, bucket_scatter_ref

try:
    import hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis is a dev dep; CI installs it
    hypothesis = None

# small pad floor so tests exercise the shape ladder without tracing
# 4096-row interpret-mode kernels per case
PAD = 64


def _random_records(n, rec, seed=0):
    rng = np.random.default_rng(seed)
    blob = rng.integers(0, 256, size=(n, rec), dtype=np.uint8).tobytes()
    return blob, [blob[i:i + rec] for i in range(0, n * rec, rec)]


def _assert_scatter_parity(records, blob, rec, part, n, **kw):
    """scatter_batch pieces must equal the bytes backend's buckets."""
    kw.setdefault("pad_block", PAD)
    batch = RecordBatch.from_bytes(blob, rec)
    pieces = scatter_batch(batch, part, n, **kw)
    assert len(pieces) == max(n, 1)
    want = [[] for _ in range(max(n, 1))]
    for r in records:
        want[part(r, n)].append(r)
    for piece, wb in zip(pieces, want):
        assert piece.to_bytes() == b"".join(wb)
    assert sum(p.num_records for p in pieces) == len(records)


@pytest.mark.parametrize("n_buckets", [1, 2, 5, 16])
@pytest.mark.parametrize("n_records,record_size", [(1, 8), (97, 100),
                                                   (256, 12)])
def test_hash_scatter_matches_bytes(n_records, record_size, n_buckets):
    blob, records = _random_records(n_records, record_size,
                                    seed=n_records + n_buckets)
    _assert_scatter_parity(records, blob, record_size,
                           hash_partitioner(key_bytes=8), n_buckets)


@pytest.mark.parametrize("key_bytes", [4, 10])
@pytest.mark.parametrize("n_buckets", [2, 6])
@pytest.mark.parametrize("n_records,record_size", [(97, 100), (333, 10)])
def test_range_scatter_matches_bytes(n_records, record_size, n_buckets,
                                     key_bytes):
    blob, records = _random_records(n_records, record_size,
                                    seed=7 * n_records + n_buckets)
    bounds = sample_boundaries(records[:200], n_buckets, key_bytes=key_bytes)
    _assert_scatter_parity(records, blob, record_size,
                           range_partitioner(bounds), n_buckets)


def test_scatter_stability_duplicate_keys():
    """Duplicate keys with distinct payloads: the scattered bucket must
    preserve input order exactly (counting scatter stability), not just
    bucket membership."""
    keys = [b"\x40" * 10, b"\x80" * 10, b"\x40" * 10, b"\x10" * 10]
    records = [k + bytes([i]) * 6 for i, k in enumerate(keys * 25)]
    part = range_partitioner([b"\x40" * 10, b"\x80" * 10])
    _assert_scatter_parity(records, b"".join(records), 16, part, 3)


def test_scatter_boundary_strictness_multiword():
    """Keys equal to a 3-word boundary, keys differing only in the
    zero-padded tail word, and heavy duplicates — the strict
    #{bounds < key} rule must agree with bytes on every one."""
    b1 = b"\x40" * 10
    b2 = b"\x80" * 9 + b"\x00"
    part = range_partitioner([b1, b2])
    keys = ([b1] * 4 + [b1[:9] + b"\x3f"] * 3 + [b1[:9] + b"\x41"] * 3
            + [b2] * 4 + [b2[:9] + b"\x01"] * 2
            + [b"\x00" * 10] * 2 + [b"\xff" * 10] * 2)
    records = [k + b"pp" for k in keys]
    _assert_scatter_parity(records, b"".join(records), 12, part, 3)


def test_scatter_variable_length_boundaries():
    """Boundaries of differing byte lengths, one a zero-tailed prefix of
    another: the kernel's trailing length word must reproduce Python's
    shorter-prefix-sorts-first bytes ordering."""
    bounds = [b"\x10\x20", b"\x10\x20\x00", b"\x10\x20\x00\x00\x00\x01",
              b"\x90\x10\x20\x30\x40"]
    part = range_partitioner(bounds)
    prefixes = [b"\x00\x00", b"\x10\x1f", b"\x10\x20", b"\x10\x21",
                b"\x90\x10", b"\xff\xff"]
    records = [p + bytes([i]) * 4 for i, p in enumerate(prefixes)]
    records += [b"\x10\x20\x00\x00\x00\x00", b"\x10\x20\x00\x00\x00\x01",
                b"\x90\x10\x20\x30\x40\x00"]
    _assert_scatter_parity(records, b"".join(records), 6, part, 5)


def test_scatter_degenerate_paths():
    blob, records = _random_records(50, 10, seed=5)
    batch = RecordBatch.from_bytes(blob, 10)
    # n == 1: the batch passes through untouched
    (only,) = scatter_batch(batch, hash_partitioner(4), 1)
    assert only.to_bytes() == blob
    # empty batch: n empty pieces of the right record size
    empty = RecordBatch.empty(10)
    pieces = scatter_batch(empty, hash_partitioner(4), 4)
    assert [p.num_records for p in pieces] == [0] * 4
    assert all(p.record_size == 10 for p in pieces)
    # reduce partitioner: single-bucket short circuit, no kernel call
    pieces = scatter_batch(batch, reduce_partitioner(), 3)
    assert pieces[0].to_bytes() == blob
    assert [p.num_records for p in pieces[1:]] == [0, 0]
    # arbitrary Python partitioner: host-loop fallback, same contract
    _assert_scatter_parity(records, blob, 10, lambda r, n: r[0] % n, 3)


def _padded_junk_batch(blob, rec, n, pad_rows, seed=0):
    """A padding-resident batch: valid records up front, JUNK tail rows
    that must never influence any result."""
    rng = np.random.default_rng(seed)
    junk = rng.integers(0, 256, size=(pad_rows - n, rec), dtype=np.uint8)
    block = np.concatenate(
        [np.frombuffer(blob, np.uint8).reshape(n, rec), junk])
    return RecordBatch(jnp.asarray(block), n_valid=n)


def test_scatter_padded_resident_input_parity():
    """A padding-resident batch (dynamic n_valid, junk tail) scatters
    identically to the exact batch of its valid records — on the kernel
    path AND the host-loop fallback (which must slice, not leak junk)."""
    n, rec, nb = 90, 12, 5
    blob, records = _random_records(n, rec, seed=31)
    for part in (range_partitioner(sample_boundaries(records, nb,
                                                     key_bytes=10)),
                 hash_partitioner(key_bytes=8),
                 lambda r, k: r[0] % k):
        for pad_rows in (96, 128, 256):
            padded = _padded_junk_batch(blob, rec, n, pad_rows, seed=pad_rows)
            pieces = scatter_batch(padded, part, nb, pad_block=PAD)
            want = [[] for _ in range(nb)]
            for r in records:
                want[part(r, nb)].append(r)
            for piece, wb in zip(pieces, want):
                assert piece.to_bytes() == b"".join(wb)
            assert sum(p.num_records for p in pieces) == n


def test_scatter_dispatch_defers_the_histogram_sync():
    """The dispatch half returns with the kernel merely enqueued — no
    pieces yet — and harvest() with externally synced metadata (the
    executor's one-barrier-per-round path) resolves the same pieces as
    the self-syncing scatter_batch."""
    nb = 4
    blob, records = _random_records(120, 16, seed=5)
    part = range_partitioner(sample_boundaries(records, nb, key_bytes=10))
    batches = [RecordBatch.from_bytes(blob, 16) for _ in range(3)]
    disps = [scatter_dispatch(b, part, nb, pad_block=PAD) for b in batches]
    assert all(d.pending and d.pieces is None and d.host_syncs == 0
               for d in disps)
    synced = jax.device_get([d.sync_arrays for d in disps])  # ONE barrier
    for d, s in zip(disps, synced):
        pieces = d.harvest(synced=s)
        assert not d.pending
        ref = scatter_batch(RecordBatch.from_bytes(blob, 16), part, nb,
                            pad_block=PAD)
        assert [p.to_bytes() for p in pieces] == [p.to_bytes() for p in ref]


def test_scatter_dispatch_degenerates_resolve_at_dispatch():
    """Shapes with nothing to sync resolve into pieces immediately
    (pending=False, host_syncs=0); the host-loop fallback resolves too
    but reports the sync it already paid."""
    blob, _ = _random_records(40, 8, seed=6)
    batch = RecordBatch.from_bytes(blob, 8)
    for disp in (scatter_dispatch(batch, hash_partitioner(4), 1),
                 scatter_dispatch(RecordBatch.empty(8),
                                  hash_partitioner(4), 4),
                 scatter_dispatch(batch, reduce_partitioner(), 3)):
        assert not disp.pending and disp.host_syncs == 0
    host_loop = scatter_dispatch(batch, lambda r, n: r[0] % n, 3)
    assert not host_loop.pending and host_loop.host_syncs == 1


def _resident_pieces(rec, counts, rows, seed=0):
    """Padding-resident pieces at one ladder shape + their valid records
    in piece order (the executor's per-worker stage output shape)."""
    pieces, records = [], []
    for i, k in enumerate(counts):
        blob, recs = _random_records(k, rec, seed=seed + 17 * i)
        pieces.append(_padded_junk_batch(blob, rec, k, rows, seed=seed + i))
        records.extend(recs)
    return pieces, records


def test_scatter_pieces_segmented_parity():
    """Uniform resident pieces take the fused segmented path — no eager
    concat, host-invert metadata pending — and harvest exactly the
    buckets the bytes backend builds from the pieces' valid records in
    piece order."""
    rec, nb, rows = 16, 5, 96
    pieces, records = _resident_pieces(rec, [60, 11, 90, 1], rows, seed=41)
    part = range_partitioner(sample_boundaries(records, nb, key_bytes=10))
    disp = scatter_pieces_dispatch(pieces, part, nb, pad_block=PAD,
                                   interpret=True)
    assert disp.pending and disp.host_syncs == 0
    assert disp.src is not None and disp.dest is not None
    out = disp.harvest()
    want = [[] for _ in range(nb)]
    for r in records:
        want[part(r, nb)].append(r)
    for piece, wb in zip(out, want):
        assert piece.to_bytes() == b"".join(wb)
    assert sum(p.num_records for p in out) == len(records)


def test_scatter_pieces_ragged_and_single_fall_through():
    """Ragged piece shapes concatenate and fall through to the per-batch
    dispatch; a single piece delegates outright — identical buckets
    either way."""
    rec, nb = 16, 4
    ragged, records = [], []
    for i, (k, rows) in enumerate([(50, 64), (20, 96), (33, 48)]):
        blob, recs = _random_records(k, rec, seed=91 + i)
        ragged.append(_padded_junk_batch(blob, rec, k, rows, seed=i))
        records.extend(recs)
    part = range_partitioner(sample_boundaries(records, nb, key_bytes=10))
    want = [[] for _ in range(nb)]
    for r in records:
        want[part(r, nb)].append(r)
    out = scatter_pieces_dispatch(ragged, part, nb, pad_block=PAD,
                                  interpret=True).harvest()
    for piece, wb in zip(out, want):
        assert piece.to_bytes() == b"".join(wb)
    single = scatter_pieces_dispatch(ragged[:1], part, nb, pad_block=PAD,
                                     interpret=True).harvest()
    ref = scatter_batch(ragged[0], part, nb, pad_block=PAD, interpret=True)
    assert [p.to_bytes() for p in single] == [p.to_bytes() for p in ref]


def test_scatter_pieces_reduce_and_single_bucket_resolve_eagerly():
    """Degenerate rounds through the pieces API still resolve at
    dispatch with zero syncs (the host_syncs == shuffle_rounds
    accounting counts only real barriers)."""
    rec = 8
    pieces, records = _resident_pieces(rec, [30, 10], 48, seed=3)
    for part, n in ((reduce_partitioner(), 3), (hash_partitioner(4), 1)):
        disp = scatter_pieces_dispatch(pieces, part, n, pad_block=PAD,
                                       interpret=True)
        assert not disp.pending and disp.host_syncs == 0
        got = b"".join(p.to_bytes() for p in disp.harvest())
        assert got == b"".join(records)


@pytest.mark.requires_accelerator
def test_scatter_batch_defaults_to_compiled_on_accelerator():
    """With interpret unspecified, a TPU backend must take the compiled
    Pallas lowering (Mosaic) — and still match bytes."""
    from repro.utils.backend import pallas_interpret
    assert not pallas_interpret()
    n, rec, nb = 3000, 16, 6
    blob, records = _random_records(n, rec, seed=8)
    part = range_partitioner(sample_boundaries(records, nb, key_bytes=10))
    _assert_scatter_parity(records, blob, rec, part, nb)


def _lexsorted_rows(rows: np.ndarray) -> np.ndarray:
    return rows[np.lexsort(rows.T[::-1])]


def _kernel_case(n, k, n_buckets, seed):
    rng = np.random.default_rng(seed)
    # low-entropy words force duplicate keys and boundary-equal keys
    keys = rng.integers(0, 4, size=(n, k), dtype=np.uint32)
    bounds = _lexsorted_rows(
        rng.integers(0, 4, size=(n_buckets - 1, k), dtype=np.uint32))
    # payload carries a row counter so stability violations are visible
    data = np.zeros((n, 8), np.uint8)
    data[:, :4] = rng.integers(0, 256, size=(n, 4), dtype=np.uint8)
    data[:, 4] = np.arange(n) % 256
    data[:, 5] = np.arange(n) // 256
    return (jnp.asarray(data), jnp.asarray(keys), jnp.asarray(bounds))


@pytest.mark.parametrize("block_n", [7, 32, 101])
def test_kernel_scatter_vs_ref_blocks(block_n):
    """Direct kernel vs the numpy oracle across block counts, including
    block sizes that do not divide n (internal padded tail)."""
    n, nb = 101, 5
    data, keys, bounds = _kernel_case(n, 3, nb, seed=block_n)
    out, hist = bucket_scatter(data, keys, bounds, n, n_buckets=nb,
                               block_n=block_n, interpret=True)
    ref_out, ref_hist = bucket_scatter_ref(data, keys, bounds, nb)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(ref_hist))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))


@pytest.mark.parametrize("block_n", [32, 128, 512])
def test_kernel_rank_scan_width(block_n):
    """Blocks narrower than, equal to and four times the rank scan's
    128-lane sub-tile (the last carries counts across sub-tiles) give
    the oracle's result, over several blocks each."""
    n, nb = 1000, 6
    data, keys, bounds = _kernel_case(n, 3, nb, seed=block_n)
    out, hist = bucket_scatter(data, keys, bounds, n, n_buckets=nb,
                               block_n=block_n, interpret=True)
    ref_out, ref_hist = bucket_scatter_ref(data, keys, bounds, nb)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(ref_hist))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))


def test_kernel_dynamic_n_valid_reuse():
    """One padded shape, different n_valid values: rows past n_valid
    must scatter to the tail (trash bucket) and never enter the
    histogram — the contract that lets one trace serve every record
    count."""
    data, keys, bounds = _kernel_case(128, 3, 4, seed=9)
    for nv in (128, 101, 50, 1):
        out, hist = bucket_scatter(data, keys, bounds, nv, n_buckets=4,
                                   block_n=32, interpret=True)
        ref_out, ref_hist = bucket_scatter_ref(data[:nv], keys[:nv],
                                               bounds, 4)
        assert int(np.asarray(hist).sum()) == nv
        np.testing.assert_array_equal(np.asarray(hist),
                                      np.asarray(ref_hist))
        np.testing.assert_array_equal(np.asarray(out)[:nv],
                                      np.asarray(ref_out))


@pytest.mark.requires_accelerator
def test_kernel_scatter_compiled():
    """The same oracle check through the compiled (non-interpret) kernel
    — exercises the real Mosaic lowering on TPU."""
    n, nb = 5000, 7
    data, keys, bounds = _kernel_case(n, 3, nb, seed=1)
    out, hist = bucket_scatter(data, keys, bounds, n, n_buckets=nb,
                               interpret=False)
    ref_out, ref_hist = bucket_scatter_ref(data, keys, bounds, nb)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(ref_hist))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))


def _range_case(records, rng, bound_len, n_buckets):
    """Boundaries biased toward record prefixes, zero tails, duplicates."""
    raw = []
    for _ in range(max(n_buckets - 1, 0)):
        if records and rng.random() < 0.5:
            b = records[rng.integers(len(records))][:bound_len]
            if rng.random() < 0.3:
                b = b[:max(1, bound_len // 2)] + b"\x00"
        else:
            b = rng.bytes(bound_len)
        raw.append(b)
    return range_partitioner(sorted(raw))


if hypothesis is not None:
    @settings(max_examples=25, deadline=None)
    @given(data=st.binary(min_size=0, max_size=400),
           rec=st.sampled_from([8, 16]),
           n_buckets=st.integers(1, 5),
           bound_len=st.sampled_from([4, 10]),
           seed=st.integers(0, 2**31 - 1))
    def test_scatter_property(data, rec, n_buckets, bound_len, seed):
        """Random records vs random variable-length boundaries: the
        scattered pieces equal the bytes buckets byte-for-byte (order
        included). Shapes are constrained so interpret-mode traces are
        shared across examples."""
        n = max(1, len(data) // rec)
        blob = (data + bytes(n * rec))[:n * rec]
        records = [blob[i:i + rec] for i in range(0, n * rec, rec)]
        part = _range_case(records, np.random.default_rng(seed),
                           bound_len, n_buckets)
        _assert_scatter_parity(records, blob, rec, part, n_buckets,
                               block_n=32)


def test_scatter_randomized():
    """Non-hypothesis twin of the property test (runs even without the
    hypothesis dev dep), 25 rounds."""
    rng = np.random.default_rng(77)
    for _ in range(25):
        rec = int(rng.choice([8, 16]))
        n = int(rng.integers(1, 60))
        blob = rng.bytes(n * rec)
        records = [blob[i:i + rec] for i in range(0, n * rec, rec)]
        nb = int(rng.integers(1, 6))
        part = _range_case(records, rng, int(rng.choice([4, 10])), nb)
        _assert_scatter_parity(records, blob, rec, part, nb, block_n=32)
