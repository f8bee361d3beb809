"""Fused worker-axis shuffle rounds: stacked-round parity vs the bytes
reference.

The fused data plane only gets to replace the per-worker dispatch loop
because it agrees with the reference record-for-record: for every bucket,
the same records in the same order (slot-major, input order within a
slot), regrouped onto the same destination workers, with identical
origin-byte accounting.  These tests drive :func:`scatter_round_dispatch`
and the shard_map twin ``spmd.fused_scatter_round`` over
ragged rounds — empty slots, empty workers, boundary-colliding keys —
against a per-record Python reference, plus a hypothesis property test
over ragged loads when hypothesis is installed.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.records import RecordBatch, StackedBatch
from repro.core.shuffle import (hash_partitioner, range_partitioner,
                                sample_boundaries, scatter_round_dispatch)

try:
    import hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis is a dev dep; CI installs it
    hypothesis = None


def _ragged_round(loads, rec, seed=0):
    """One slot of random records per entry of ``loads`` (0 = empty)."""
    rng = np.random.default_rng(seed)
    return [[rng.integers(0, 256, rec, dtype=np.uint8).tobytes()
             for _ in range(k)]
            for k in loads]


def _pack(slots, rec, pad_block=8):
    batches = [RecordBatch.from_records(s) if s else RecordBatch.empty(rec)
               for s in slots]
    return StackedBatch.pack(batches, pad_block=pad_block)


def _reference(slots, slot_workers, worker_names, part, n):
    """The bytes backend's answer: per-bucket append order (slot-major,
    input order), bucket b -> worker b % W, buckets ascending within a
    worker, origins as per-bucket per-origin-worker byte counts."""
    W = len(worker_names)
    buckets = [[] for _ in range(n)]
    origins = [{} for _ in range(n)]
    for s, recs in enumerate(slots):
        src = worker_names[slot_workers[s]]
        for r in recs:
            b = part(r, n)
            buckets[b].append(r)
            origins[b][src] = origins[b].get(src, 0) + len(r)
    parts = [b"" for _ in range(W)]
    counts = [0] * W
    for b in range(n):
        parts[b % W] += b"".join(buckets[b])
        counts[b % W] += len(buckets[b])
    return parts, counts, origins


def _assert_round_parity(slots, slot_workers, worker_names, part, n, rec,
                         **kw):
    stacked = _pack(slots, rec)
    rd = scatter_round_dispatch(stacked, part, n,
                                worker_names=worker_names,
                                slot_workers=slot_workers, pad_block=8,
                                **kw)
    assert rd is not None
    result = rd.harvest()
    want_parts, want_counts, want_origins = _reference(
        slots, slot_workers, worker_names, part, n)
    assert result.counts.tolist() == want_counts
    assert result.origins == want_origins
    if result.data is None:
        assert sum(want_counts) == 0
        return result
    got = np.asarray(result.data)
    assert got.shape[:1] == (len(worker_names),)
    for w in range(len(worker_names)):
        assert got[w, :want_counts[w]].tobytes() == want_parts[w]
    return result


WORKERS = [f"s{i}" for i in range(4)]


@pytest.mark.parametrize("loads,n_buckets", [
    ([5, 3, 7, 2], 4),            # one slot per worker
    ([9, 0, 4, 0], 6),            # empty slots / empty workers
    ([0, 0, 0, 0], 4),            # fully empty round
    ([30, 1, 1, 1, 17, 8], 3),    # more slots than workers (multi-task)
    ([12], 9),                    # single slot, buckets > records
])
@pytest.mark.parametrize("which", ["hash", "range"])
def test_stacked_round_matches_reference(loads, n_buckets, which):
    rec = 12
    slots = _ragged_round(loads, rec, seed=len(loads) * 7 + n_buckets)
    slot_workers = np.arange(len(loads)) % len(WORKERS)
    slot_workers.sort()           # worker-major ordering contract
    allrec = [r for s in slots for r in s]
    if which == "hash":
        part = hash_partitioner(key_bytes=8)
    else:
        part = range_partitioner(
            sample_boundaries(allrec or [b"\x00" * rec], n_buckets,
                              key_bytes=10))
    _assert_round_parity(slots, slot_workers, WORKERS, part, n_buckets, rec)


def test_vmapped_lowering_matches_segmented():
    """A ragged hash round (an empty slot, more slots than workers, a
    short-key hash) through the one vmapped scatter matches the
    reference: regrouped partitions, counts and origins."""
    rec, n = 16, 5
    slots = _ragged_round([11, 0, 6, 23, 2, 9], rec, seed=3)
    slot_workers = np.sort(np.arange(6) % len(WORKERS))
    part = hash_partitioner(key_bytes=4)
    _assert_round_parity(slots, slot_workers, WORKERS, part, n, rec,
                         interpret=True)


def test_round_dispatch_is_o1_in_slots():
    """A round costs exactly two dispatches — the stacked scatter and
    the harvest's regroup gather — however many slots it stacks."""
    rec = 8
    part = hash_partitioner(key_bytes=4)
    disp = []
    for s in (2, 16, 64):
        slots = _ragged_round([3] * s, rec, seed=s)
        stacked = _pack(slots, rec)
        rd = scatter_round_dispatch(stacked, part, 4,
                                    worker_names=WORKERS,
                                    slot_workers=np.sort(
                                        np.arange(s) % len(WORKERS)),
                                    pad_block=8)
        result = rd.harvest()
        disp.append(rd.dispatches + result.dispatches)
    assert disp == [2, 2, 2]


def test_grouped_harvest_matches_reference():
    """A many-worker round regroups in ONE gather: every destination
    worker's partition is a slot of the one [W, block2, width] stack,
    record-for-record the reference's."""
    rec, n = 12, 16
    workers = [f"s{i}" for i in range(8)]
    slots = _ragged_round([9, 17, 4, 0, 22, 6, 13, 1, 30, 5], rec, seed=13)
    slot_workers = np.sort(np.arange(10) % len(workers))
    part = hash_partitioner(key_bytes=8)
    result = _assert_round_parity(slots, slot_workers, workers, part, n,
                                  rec)
    assert result.dispatches == 1
    assert result.data.shape[2] == rec


def test_ineligible_rounds_return_none():
    from repro.core.shuffle import ReducePartitioner
    rec = 8
    stacked = _pack(_ragged_round([4, 4], rec, seed=1), rec)
    # single bucket
    assert scatter_round_dispatch(stacked, hash_partitioner(4), 1,
                                  worker_names=WORKERS) is None
    # reduce shuffle
    assert scatter_round_dispatch(stacked, ReducePartitioner(), 4,
                                  worker_names=WORKERS) is None
    # host-loop partitioner (no scatter_spec)
    assert scatter_round_dispatch(stacked, lambda r, n: 0, 4,
                                  worker_names=WORKERS) is None


@pytest.mark.requires_accelerator
def test_vmapped_round_compiles_on_accelerator():
    """The vmapped stacked scatter must lower through the compiled
    (non-interpret) kernel on a real TPU backend."""
    rec, n = 16, 4
    slots = _ragged_round([7, 5, 0, 12], rec, seed=5)
    slot_workers = np.arange(4)
    part = range_partitioner(
        sample_boundaries([r for s in slots for r in s], n, key_bytes=10))
    _assert_round_parity(slots, slot_workers, WORKERS, part, n, rec,
                         interpret=False)


def test_mesh_fused_round_matches_host_harvest():
    """``spmd.fused_scatter_round`` on a 1-device mesh: the shard_map +
    all_to_all lowering shares the host harvest's ordering contract
    exactly (multi-device meshes are covered in
    test_spmd_subprocess.py)."""
    from jax.sharding import Mesh
    from repro.core.spmd import fused_scatter_round

    rec, n, W = 12, 6, 4
    slots = _ragged_round([8, 3, 0, 14], rec, seed=9)
    slot_workers = np.arange(4)
    part = hash_partitioner(key_bytes=8)
    stacked = _pack(slots, rec)
    key_spec, bounds = part.scatter_spec(RecordBatch.empty(rec), n)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    parts_dev, counts_dev, hist_sb, over = fused_scatter_round(
        stacked.data, jnp.asarray(stacked.n_valid, jnp.int32),
        bounds, key_spec=key_spec, n_buckets=n, n_workers=W, mesh=mesh)
    want_parts, want_counts, _ = _reference(slots, slot_workers, WORKERS,
                                            part, n)
    assert not np.asarray(over).any()
    counts = np.asarray(counts_dev)
    assert counts.tolist() == want_counts
    got = np.asarray(parts_dev)
    for w in range(W):
        assert got[w, :want_counts[w]].tobytes() == want_parts[w]
    # the synced histogram is the per-slot truth movement pricing needs
    hist = np.asarray(hist_sb)
    for s, recs in enumerate(slots):
        ref = [part(r, n) for r in recs]
        assert hist[s].tolist() == [ref.count(b) for b in range(n)]


if hypothesis is not None:
    @settings(max_examples=30, deadline=None)
    @given(loads=st.lists(st.integers(0, 40), min_size=1, max_size=10),
           n_buckets=st.integers(2, 9),
           rec_pow=st.integers(2, 4),
           which=st.sampled_from(["hash", "range"]),
           seed=st.integers(0, 2**31 - 1))
    def test_stacked_round_parity_property(loads, n_buckets, rec_pow,
                                           which, seed):
        rec = 1 << rec_pow
        slots = _ragged_round(loads, rec, seed=seed)
        slot_workers = np.sort(np.arange(len(loads)) % len(WORKERS))
        allrec = [r for s in slots for r in s]
        if which == "hash":
            part = hash_partitioner(key_bytes=min(rec, 8))
        else:
            part = range_partitioner(
                sample_boundaries(allrec or [b"\x00" * rec], n_buckets,
                                  key_bytes=min(rec, 10)))
        _assert_round_parity(slots, slot_workers, WORKERS, part,
                             n_buckets, rec)
