"""Engine fault-tolerance/speculation on the ARRAY backend, and
cross-backend report agreement.

The planner only sees task sizes, never record data — so for the same
job every scheduling counter and simulated second must agree between the
bytes reference and the device-resident array executor.  These tests
exercise the paths PR 1 only covered via bytes (stragglers, dead-worker
retries) on the array backend, and pin the planner-purity guarantee by
diffing SphereReports across backends."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_cloud
from repro.core import SphereEngine, SphereJob, SphereStage, Tracer
from repro.core.executor import (SLAB_MIN_BYTES, ArrayExecutor, _SlotRef,
                                 _as_batch, _slab)
from repro.core.records import RecordBatch, StackedBatch
from repro.core.shuffle import (reduce_partitioner, sample_boundaries,
                                terasort_stages)

REC = 100


def _upload(client, name, n, seed=0, replication=2):
    rng = np.random.default_rng(seed)
    data = rng.bytes(n * REC)
    client.upload(name, data, replication=replication)
    return data


def _identity_job(backend):
    return SphereJob("id", "f",
                     [SphereStage("id", lambda rs: list(rs),
                                  batch_udf=lambda b: b, pad_value=0xFF)],
                     record_size=REC, backend=backend)


@pytest.mark.parametrize("backend", ["bytes", "array"])
def test_straggler_speculation(tmp_path, backend):
    """One 50x-slow worker, full replication: speculation must win tasks
    back onto the fast replica — on both record backends."""
    master, servers, client = make_cloud(tmp_path, chunk_size=1000,
                                         n_servers=2)
    _upload(client, "f", n=400, replication=2)
    slow = {servers[0].server_id: 0.02, servers[1].server_id: 1.0}
    eng = SphereEngine(master, client, speeds=slow, speculate_factor=1.5)
    outs, rep = eng.run(_identity_job(backend))
    assert rep.speculated > 0
    assert rep.speculation_wins > 0
    assert sum(len(o) for o in outs) == 400 * REC  # nothing lost


@pytest.mark.parametrize("backend", ["bytes", "array"])
def test_worker_failure_retry(tmp_path, backend):
    master, servers, client = make_cloud(tmp_path, chunk_size=1000)
    data = _upload(client, "f", n=50, replication=3)
    servers[1].kill()
    master.deregister("s1")
    outs, rep = SphereEngine(master, client).run(_identity_job(backend))
    assert len(b"".join(outs)) == len(data)


def _report_key(rep):
    """The backend-independent slice of a SphereReport (partition_seconds
    and udf_traces are real wall-clock / array-only, so excluded)."""
    return (rep.tasks, rep.retried, rep.speculated, rep.speculation_wins,
            rep.bytes_local, rep.bytes_moved, rep.partitioned_records,
            pytest.approx(rep.sim_seconds),
            [pytest.approx(s) for s in rep.stage_seconds])


def _run_both_backends(tmp_path, n, make_job, *, speeds=None, kill=None):
    reports, outputs = {}, {}
    for backend in ("bytes", "array"):
        sub = tmp_path / backend
        sub.mkdir()
        master, servers, client = make_cloud(sub, chunk_size=1000)
        data = _upload(client, "f", n=n, replication=3)
        if kill is not None:
            servers[kill].kill()
            master.deregister(servers[kill].server_id)
        eng = SphereEngine(master, client, speeds=speeds)
        outs, rep = eng.run(make_job(backend, data))
        reports[backend] = rep
        outputs[backend] = outs
    return reports, outputs


def test_report_counters_agree_across_backends(tmp_path):
    """Same TeraSort job on both backends: byte-identical outputs AND an
    identical scheduling report — locality, movement (charged from real
    shuffle origins), speculation and simulated time all match because
    the planner is pure over task sizes."""
    def make_job(backend, data):
        sample = [data[i:i + REC] for i in range(0, 100 * REC, REC)]
        bounds = sample_boundaries(sample, 4, key_bytes=10)
        return SphereJob("sort", "f", terasort_stages(bounds, backend, 4),
                         record_size=REC, backend=backend)

    reports, outputs = _run_both_backends(tmp_path, 100, make_job)
    assert outputs["bytes"] == outputs["array"]
    assert _report_key(reports["array"]) == _report_key(reports["bytes"])
    assert reports["bytes"].sim_seconds > 0
    assert reports["bytes"].bytes_moved > 0  # the shuffle moved something


def test_report_counters_agree_with_failure(tmp_path):
    """Retry counters agree too: chunk reads hit the same dead replicas
    on both backends."""
    reports, outputs = _run_both_backends(
        tmp_path, 60, lambda backend, data: _identity_job(backend), kill=1)
    assert outputs["bytes"] == outputs["array"]
    assert _report_key(reports["array"]) == _report_key(reports["bytes"])


def test_array_udf_traced_once_per_stage(tmp_path):
    """Pad-stable stage UDFs compile once: every task is padded to the
    same block multiple, so rep.udf_traces reports 1 per stage."""
    master, servers, client = make_cloud(tmp_path, chunk_size=1000)
    data = _upload(client, "f", n=120, replication=2)
    sample = [data[i:i + REC] for i in range(0, 120 * REC, REC)]
    bounds = sample_boundaries(sample, 4, key_bytes=10)
    job = SphereJob("sort", "f", terasort_stages(bounds, "array", 4),
                    record_size=REC, backend="array")
    _, rep = SphereEngine(master, client).run(job)
    assert rep.udf_traces == {"partition": 1, "sort": 1}


def test_array_terasort_stays_on_kernel_path(tmp_path, monkeypatch):
    """10-byte range splitters must take the multi-word kernel — the
    per-record host fallback would be a silent perf regression, so make
    it an error for the whole job."""
    import repro.core.shuffle as shuffle_mod

    def boom(*a, **k):
        raise AssertionError("RangePartitioner fell back to _host_partition")

    monkeypatch.setattr(shuffle_mod, "_host_partition", boom)
    master, servers, client = make_cloud(tmp_path, chunk_size=1000)
    data = _upload(client, "f", n=100, replication=2)
    sample = [data[i:i + REC] for i in range(0, 100 * REC, REC)]
    bounds = sample_boundaries(sample, 4, key_bytes=10)
    assert len(bounds[0]) == 10
    job = SphereJob("sort", "f", terasort_stages(bounds, "array", 4),
                    record_size=REC, backend="array")
    outs, rep = SphereEngine(master, client).run(job)
    allrec = [r for blob in outs
              for r in (blob[i:i + REC] for i in range(0, len(blob), REC))]
    keys = [r[:10] for r in allrec]
    assert keys == sorted(keys) and len(allrec) == 100


def test_same_named_stages_keep_their_own_udfs(tmp_path):
    """The traced-UDF cache is keyed by stage identity, not name — two
    pad-stable stages sharing a name must each run their own batch_udf."""
    master, servers, client = make_cloud(tmp_path, chunk_size=1000)
    rng = np.random.default_rng(3)
    vals = rng.integers(0, 100, size=50).astype("<u4")
    client.upload("nums", vals.tobytes(), replication=2)

    def add(k):
        return lambda b: type(b)(b.data + np.uint8(k))

    job = SphereJob("dup", "nums", [
        SphereStage("x", batch_udf=add(1), pad_value=0),
        SphereStage("x", batch_udf=add(2), pad_value=0),
    ], record_size=4, backend="array")
    outs, _ = SphereEngine(master, client).run(job)
    got = np.sort(np.frombuffer(b"".join(outs), np.uint8))
    want = np.sort((np.frombuffer(vals.tobytes(), np.uint8) + 3)
                   .astype(np.uint8))
    np.testing.assert_array_equal(got, want)


def _reduce_jobs(backend):
    """An emit job (identity + reduce shuffle to bucket 0) and a chained
    fold job (sum the float32 columns of all records into one record) —
    the k-means-shaped reduce pipeline on tiny inputs."""
    emit = SphereJob(
        "emit", "f",
        [SphereStage("emit", lambda rs: list(rs), batch_udf=lambda b: b,
                     pad_value=0, partitioner=reduce_partitioner())],
        record_size=8, backend=backend)

    def fold_bytes(records):
        tot = np.sum([np.frombuffer(r, "<f4") for r in records], axis=0,
                     dtype=np.float32)
        return [tot.astype("<f4").tobytes()]

    # array fold: bitcast rows to f32, zero out padding via mask, sum
    import jax

    def fold_masked(batch, mask, _params):
        arr = jax.lax.bitcast_convert_type(
            batch.data.reshape(batch.num_records, -1, 4), jnp.float32)
        arr = arr * mask.astype(jnp.float32)[:, None]
        raw = jax.lax.bitcast_convert_type(arr.sum(0, keepdims=True),
                                           jnp.uint8)
        return RecordBatch(raw.reshape(1, -1))

    fold = SphereJob(
        "fold", "f",
        [SphereStage("fold", fold_bytes, masked_udf=fold_masked)],
        record_size=8, backend=backend)
    return emit, fold


def test_chained_reduce_tiny_batch_backend_parity(tmp_path, monkeypatch):
    """The reduce path must not silently drop to the per-record host loop
    (the bytes-path fallback) — even when a chained job's whole input is
    a single tiny batch of partials.  reduce_partitioner stays on the
    array path, the mask-aware fold stays at its fixed block shape, and
    both backends agree on outputs AND scheduling reports."""
    import repro.core.shuffle as shuffle_mod

    def boom(*a, **k):
        raise AssertionError("reduce path fell back to _host_partition")

    monkeypatch.setattr(shuffle_mod, "_host_partition", boom)
    # integer-valued floats: sums are exact in f4 and f8 alike, so the
    # two backends' outputs are byte-identical
    rng = np.random.default_rng(7)
    vals = rng.integers(0, 1000, size=(40, 2)).astype("<f4")

    results = {}
    for backend in ("bytes", "array"):
        sub = tmp_path / backend
        sub.mkdir()
        master, servers, client = make_cloud(sub, chunk_size=1000)
        client.upload("f", vals.tobytes(), replication=2)
        emit, fold = _reduce_jobs(backend)
        sess = SphereEngine(master, client).session("f", record_size=8,
                                                    backend=backend)
        sess.run(emit)
        outs, rep = sess.run(fold, input="chained")
        results[backend] = (outs, rep)
        assert len(outs) == 1  # one folded record
        np.testing.assert_allclose(np.frombuffer(outs[0], "<f4"),
                                   vals.sum(0))
    assert results["bytes"][0] == results["array"][0]
    assert _report_key(results["array"][1]) == _report_key(results["bytes"][1])
    assert results["array"][1].udf_traces["fold"] == 1


def _terasort_job(backend, data, n_buckets=4):
    sample = [data[i:i + REC] for i in range(0, min(len(data), 100 * REC),
                                             REC)]
    bounds = sample_boundaries(sample, n_buckets, key_bytes=10)
    return SphereJob("sort", "f", terasort_stages(bounds, backend,
                                                  n_buckets),
                     record_size=REC, backend=backend)


def test_host_syncs_one_per_shuffle_round(tmp_path):
    """The dispatch-then-sync invariant: an array kernel-path shuffle
    round costs exactly ONE host sync (the batched histogram barrier),
    never one per worker batch — and the bytes backend, which never puts
    data on device, reports zero while agreeing on the round count."""
    for backend, sub in (("bytes", "b"), ("array", "a")):
        d = tmp_path / sub
        d.mkdir()
        master, servers, client = make_cloud(d, chunk_size=1000)
        data = _upload(client, "f", n=200, replication=2)
        _, rep = SphereEngine(master, client).run(
            _terasort_job(backend, data))
        assert rep.shuffle_rounds == 1       # one non-final stage
        if backend == "array":
            assert rep.host_syncs == rep.shuffle_rounds
        else:
            assert rep.host_syncs == 0


def test_host_syncs_reduce_round_is_free(tmp_path):
    """Reduce rounds resolve at dispatch (single-bucket short circuit):
    the round counts in shuffle_rounds but syncs nothing — host_syncs
    stays <= shuffle_rounds in general, equal only on kernel rounds."""
    master, servers, client = make_cloud(tmp_path, chunk_size=1000)
    rng = np.random.default_rng(11)
    client.upload("f", rng.integers(0, 1000, size=(40, 2)).astype("<f4")
                  .tobytes(), replication=2)
    emit, fold = _reduce_jobs("array")
    sess = SphereEngine(master, client).session("f", record_size=8,
                                                backend="array")
    _, rep = sess.run(emit)
    assert rep.shuffle_rounds == 1 and rep.host_syncs == 0
    _, rep2 = sess.run(fold, input="chained")
    assert rep2.host_syncs == 0


def test_host_syncs_chained_terasort_rounds(tmp_path):
    """A chained session re-running the sort keeps the one-sync-per-round
    invariant on every job in the chain."""
    master, servers, client = make_cloud(tmp_path, chunk_size=1000)
    data = _upload(client, "f", n=150, replication=2)
    sess = SphereEngine(master, client).session("f", record_size=REC,
                                                backend="array")
    job = _terasort_job("array", data)
    _, rep1 = sess.run(job)
    _, rep2 = sess.run(job, input="chained")
    for rep in (rep1, rep2):
        assert rep.shuffle_rounds == 1
        assert rep.host_syncs == rep.shuffle_rounds


@pytest.mark.parametrize("backend", ["bytes", "array"])
def test_prefetch_matches_synchronous_path(tmp_path, backend):
    """Stage-0 decode prefetch is result-identical: same outputs, same
    report (including retry counters) as prefetch=False — with a dead
    server in the mix so the failure-replay path is exercised."""
    results = {}
    for prefetch in (True, False):
        sub = tmp_path / f"{backend}-{prefetch}"
        sub.mkdir()
        master, servers, client = make_cloud(sub, chunk_size=1000)
        data = _upload(client, "f", n=120, replication=3)
        servers[2].kill()
        master.deregister(servers[2].server_id)
        eng = SphereEngine(master, client, prefetch=prefetch)
        outs, rep = eng.run(_terasort_job(backend, data))
        results[prefetch] = (outs, rep)
    assert results[True][0] == results[False][0]
    assert _report_key(results[True][1]) == _report_key(results[False][1])
    assert results[True][1].retried == results[False][1].retried


def test_stream_windows_backend_parity_with_overlap(tmp_path):
    """Two sliding windows of a TeraSort stream: byte-identical window
    outputs across backends under the dispatch-then-sync shuffle and
    prefetch, with the one-sync-per-round invariant holding per window
    on the array side."""
    from repro.core import WindowPolicy

    outs = {}
    for backend in ("bytes", "array"):
        sub = tmp_path / backend
        sub.mkdir()
        master, servers, client = make_cloud(sub, chunk_size=1000)
        eng = SphereEngine(master, client)
        stream = eng.stream("s/", window=WindowPolicy.sliding(2),
                            record_size=REC, backend=backend)
        datas = [_upload(client, f"s/{i}", n=60, seed=i, replication=2)
                 for i in range(3)]
        sample = [datas[0][i:i + REC] for i in range(0, 60 * REC, REC)]
        bounds = sample_boundaries(sample, 4, key_bytes=10)
        job = SphereJob("sort", "s/", terasort_stages(bounds, backend, 4),
                        record_size=REC, backend=backend)
        # 3 arrivals under sliding(2): the trailing window (s/1, s/2) is
        # current — run the job against it
        assert stream.windows_formed == 2
        o, rep = stream.run(job)
        outs[backend] = [(o, rep)]
        if backend == "array":
            assert rep.shuffle_rounds == 1
            assert rep.host_syncs == rep.shuffle_rounds
    assert outs["bytes"][0][0] == outs["array"][0][0]
    assert (_report_key(outs["bytes"][0][1])
            == _report_key(outs["array"][0][1]))


def test_fused_rounds_match_unfused_and_bytes(tmp_path):
    """The fused worker-axis round (stacked UDF apply + one-round scatter
    + device regrouping) is only allowed to exist because it agrees with
    both the per-worker array loop and the bytes reference —
    byte-identical outputs AND identical scheduling reports."""
    results = {}
    for label, backend, fused in (("bytes", "bytes", False),
                                  ("array", "array", False),
                                  ("fused", "array", True)):
        sub = tmp_path / label
        sub.mkdir()
        master, servers, client = make_cloud(sub, chunk_size=1000)
        data = _upload(client, "f", n=200, replication=3)
        eng = SphereEngine(master, client, fused_rounds=fused)
        outs, rep = eng.run(_terasort_job(backend, data, n_buckets=6))
        results[label] = (outs, rep)
    assert results["fused"][0] == results["array"][0]
    assert results["fused"][0] == results["bytes"][0]
    assert _report_key(results["fused"][1]) == _report_key(results["bytes"][1])
    assert _report_key(results["fused"][1]) == _report_key(results["array"][1])
    # and the fused round kept the one-sync-per-round invariant
    assert results["fused"][1].host_syncs == results["fused"][1].shuffle_rounds


def test_fused_dispatches_constant_in_workers_and_tasks(tmp_path):
    """The tentpole invariant: a fused round costs O(1) compiled
    dispatches — one stacked UDF call, a bounded shard fan of scatter
    calls, one regrouping gather — regardless of worker count or task
    count, where the per-task/per-worker loop grows linearly."""
    from repro.core.shuffle import _ROUND_MAX_SHARDS

    def run(n_servers, n_records, fused):
        sub = tmp_path / f"{n_servers}-{n_records}-{fused}"
        sub.mkdir()
        master, servers, client = make_cloud(sub, chunk_size=1000,
                                             n_servers=n_servers)
        data = _upload(client, "f", n=n_records, replication=2)
        eng = SphereEngine(master, client, fused_rounds=fused)
        _, rep = eng.run(_terasort_job("array", data))
        return rep

    # ceiling: stacked apply + shard fan + harvest gather + next stage
    cap = _ROUND_MAX_SHARDS + 4
    small = run(2, 100, True)
    wide = run(6, 100, True)
    many = run(6, 400, True)     # 4x the tasks
    for rep in (small, wide, many):
        assert 0 < rep.device_dispatches <= cap
        assert rep.shuffle_rounds == 1
    assert wide.device_dispatches == small.device_dispatches
    assert many.device_dispatches <= small.device_dispatches + \
        _ROUND_MAX_SHARDS - 1    # shard fan may widen, nothing else may
    # the per-task loop's count grows with tasks (the contrast the
    # fused invariant is measured against)
    loopy = run(6, 400, False)
    assert loopy.device_dispatches > cap


def test_prefetch_depth_reports_bit_identical(tmp_path):
    """Deeper stage-0 prefetch pipelines are a pure latency knob: every
    depth (and prefetch off) yields byte-identical outputs and identical
    reports, including retry counters under a dead server."""
    results = {}
    for depth in (0, 1, 3, 8):
        sub = tmp_path / f"d{depth}"
        sub.mkdir()
        master, servers, client = make_cloud(sub, chunk_size=1000)
        data = _upload(client, "f", n=120, replication=3)
        servers[2].kill()
        master.deregister(servers[2].server_id)
        eng = SphereEngine(master, client, prefetch=depth > 0,
                           prefetch_depth=max(depth, 1))
        outs, rep = eng.run(_terasort_job("array", data))
        results[depth] = (outs, rep)
    base = results[0]
    for depth in (1, 3, 8):
        assert results[depth][0] == base[0]
        assert _report_key(results[depth][1]) == _report_key(base[1])
        assert results[depth][1].retried == base[1].retried


def test_pad_unstable_udf_is_rejected(tmp_path):
    """A batch_udf that changes the row count while declaring pad_value
    violates the pad-stability contract and must fail loudly."""
    master, servers, client = make_cloud(tmp_path, chunk_size=1000)
    _upload(client, "f", n=20, replication=2)
    job = SphereJob("bad", "f",
                    [SphereStage("halve",
                                 batch_udf=lambda b: b.take(
                                     np.arange(b.num_records // 2)),
                                 pad_value=0xFF)],
                    record_size=REC, backend="array")
    with pytest.raises(ValueError, match="pad-stable"):
        SphereEngine(master, client).run(job)


# ------------------------- output copy-out as a slab ------------------------

def _junk_stack(width):
    """Three slots whose padded rows fill just over SLAB_MIN_BYTES, every
    byte non-zero, padded tails included."""
    block = -(-SLAB_MIN_BYTES // width) + 17
    data = np.random.default_rng(width).integers(
        1, 256, (3, block, width), dtype=np.uint8)
    return StackedBatch(jnp.asarray(data),
                        np.array([block - 5, 7, block // 2], np.int32))


@pytest.mark.parametrize("kind", ["slot", "concat"])
@pytest.mark.parametrize("width", [100, 80, 13])
def test_slab_copy_out_matches_rows(width, kind):
    """A stack slot above the threshold crosses as a uint32 slab, and its
    output bytes are the valid rows', byte for byte: the junk tails and
    the slab's own zero padding never leak (width 13 makes a padded slot
    that is not a whole number of 512-byte slab rows).  A worker that
    owns several slots arrives as a concatenated batch and crosses as
    its rows, with the same bytes."""
    st = _junk_stack(width)
    if kind == "slot":
        parts = {f"w{i}": _SlotRef(st, i) for i in range(st.n_slots)}
    else:  # a worker that owns several slots
        parts = {"w0": RecordBatch.concat([st.slot(0), st.slot(1)])}
    want = [np.asarray(_as_batch(p).data)[:p.num_records].tobytes()
            for p in parts.values()]
    tracer = Tracer()
    got = ArrayExecutor(None, list(parts), tracer=tracer).outputs(parts)
    assert got == want
    layouts = [e.attrs["layout"] for e in tracer.snapshot()
               if e.name == "d2h"]
    assert layouts == ["slab" if kind == "slot" else "rows"] * len(parts)


def test_slab_compiles_once_per_stack_shape():
    """The pack is keyed on the stack's padded shape, never on a record
    count: slots of two stacks with different valid counts share one
    executable, and concatenated batches (whose row count the data sets)
    add none, while their bytes stay the valid rows'."""
    width = 52  # a width no other test packs, so the count is this test's
    st = _junk_stack(width)
    other = StackedBatch(st.data, np.array([3, st.block_rows, 11], np.int32))
    before = _slab._cache_size()
    for stack in (st, other):
        slots = {f"w{i}": _SlotRef(stack, i) for i in range(stack.n_slots)}
        concat = {"w0": RecordBatch.concat([stack.slot(0), stack.slot(2)])}
        for parts in (slots, concat):
            want = [np.asarray(_as_batch(p).data)[:p.num_records].tobytes()
                    for p in parts.values()]
            assert ArrayExecutor(None, list(parts)).outputs(parts) == want
    assert _slab._cache_size() == before + 1


def test_small_slot_crosses_as_rows():
    """A slot below the threshold keeps the row copy, so the pack never
    compiles for it."""
    data = np.random.default_rng(1).integers(1, 256, (2, 64, 100), np.uint8)
    st = StackedBatch(jnp.asarray(data), np.array([40, 64], np.int32))
    assert st.block_rows * 100 < SLAB_MIN_BYTES
    parts = {"w0": _SlotRef(st, 0), "w1": _SlotRef(st, 1)}
    tracer = Tracer()
    got = ArrayExecutor(None, list(parts), tracer=tracer).outputs(parts)
    assert got == [data[0, :40].tobytes(), data[1].tobytes()]
    assert [e.attrs["layout"] for e in tracer.snapshot()
            if e.name == "d2h"] == ["rows", "rows"]


@pytest.mark.parametrize("width", [100, 80, 13])
def test_slab_host_array_is_c_contiguous(width):
    st = _junk_stack(width)
    host = np.asarray(_slab(st.data, 1))
    assert host.dtype == np.uint32 and host.shape[1] == 128
    assert host.flags.c_contiguous
    flat = host.view(np.uint8).reshape(-1)
    n = st.block_rows * width
    assert flat[:n].tobytes() == np.asarray(st.data[1]).tobytes()
    assert flat.size - n < 512 and not flat[n:].any()


def test_terasort_slot_packs_without_padding():
    slot = jax.ShapeDtypeStruct((6, 1835008, 100), jnp.uint8)
    out = jax.eval_shape(_slab, slot, 0)
    assert (out.shape, out.dtype) == ((358400, 128), jnp.uint32)
