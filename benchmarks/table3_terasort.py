"""Table 3 — TeraSort: Sphere vs Hadoop-style execution (paper §5.4).

Paper result: Sphere sorts 10GB/node ~2-3x faster than Hadoop on the same
6-node cluster (and Hadoop used 4 cores/node vs Sphere's 1). The structural
reasons, reproduced at three levels:

1. **Host level** (the paper's actual setting): the Sphere engine runs
   generate/partition/sort as UDF stages over Sector chunks with locality
   and pipelined shuffle; the Hadoop-style run disables locality (tasks go
   round-robin regardless of replica placement, charging WAN movement) and
   pays a materialisation barrier between map and reduce. Reported time is
   the engine's deterministic cost model over the Teraflow topology. Runs
   on BOTH record backends (bytes reference and the array backend built on
   the Pallas bucket-partition kernel) and checks their outputs agree
   byte-for-byte.

2. **Partition microbench**: the shuffle hot loop in isolation at >= 1M
   records — per-record Python binary search vs the analysis kernel +
   argsort/gather vs the device-resident ``scatter_batch`` path the
   engine runs. This is the records/sec speedup the array backend
   exists for. An engine-level scale sweep (``host_scales``) reports
   the same bytes-vs-array comparison through the whole engine at every
   scale, warm and cold.

3. **Device level** (the TPU twin): ``distributed_sort`` (sample ->
   bucketize -> all_to_all -> local sort) vs ``barrier_sort`` (all-gather
   everything, sort, slice). On 1 physical CPU core wall-time is not
   meaningful, so the headline is exchanged bytes: all_to_all moves each
   key once; the barrier moves it n times.
"""
from __future__ import annotations

import gc
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.core import SphereEngine, SphereJob, TaskSpec, Tracer
from repro.core.records import RecordBatch, scatter_by_ids
from repro.core.shuffle import (partition_batch, range_partitioner,
                                sample_boundaries, terasort_stages)
from repro.sector import ChunkServer, SectorClient, SectorMaster

RECORD = 100   # TeraSort: 100-byte records, 10-byte keys
KEY = 10


def _make_cloud():
    tmp = tempfile.mkdtemp(prefix="t3_")
    # record-aligned chunk size (fixed-size records must not straddle chunks)
    master = SectorMaster(chunk_size=5000 * RECORD)
    for i, site in enumerate(master.topology.sites):
        master.register(ChunkServer(f"s{i}", site, tmp))
    master.acl.add_member("bench")
    master.acl.grant_write("bench")
    client = SectorClient(master, "bench", "chicago")
    return master, client


def _gen_records(n: int, seed: int = 0) -> bytes:
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, size=(n, KEY), dtype=np.uint8)
    payload = np.full((n, RECORD - KEY), ord("v"), np.uint8)
    return np.concatenate([keys, payload], axis=1).tobytes()


class _NoLocalityEngine(SphereEngine):
    """Hadoop-style comparison: ignore replica placement when scheduling
    (data always moves to the compute), and double-materialise at the
    shuffle barrier."""

    def _schedule_view(self, tasks):
        return [TaskSpec(t.key, t.nbytes, ()) for t in tasks]

    def _stage_barrier_seconds(self, stage_output_nbytes):
        # barrier materialisation: write + read back the stage output
        return 2 * stage_output_nbytes / 400e6  # disk at 400 MB/s


def _terasort_job(bounds, backend: str) -> SphereJob:
    return SphereJob("terasort", "tera",
                     terasort_stages(bounds, backend, 6, key_bytes=KEY),
                     record_size=RECORD, backend=backend)


def _check_sorted(outputs, n_records: int) -> bytes:
    """Assert every output blob is key-sorted and return the joined
    blob for byte-exact cross-backend parity.  Checked in numpy (the
    10-byte key as a big-endian u64+u16 pair): the old per-record
    Python check left millions of small bytes objects alive across the
    sweep's timed runs, and that allocator pressure alone cost the 1M
    array timing ~10% in the full-suite process."""
    total = 0
    for blob in outputs:
        arr = np.frombuffer(blob, np.uint8).reshape(-1, RECORD)
        total += arr.shape[0]
        k1 = arr[:, :8].copy().view(">u8").ravel()
        k2 = arr[:, 8:KEY].copy().view(">u2").ravel()
        assert np.all((k1[:-1] < k1[1:])
                      | ((k1[:-1] == k1[1:]) & (k2[:-1] <= k2[1:])))
    assert total == n_records
    return b"".join(outputs)


def _sample_bounds(data: bytes, n_buckets: int = 6):
    sample = [data[i:i + RECORD]
              for i in range(0, min(len(data), 200 * RECORD), RECORD)]
    # full 10-byte TeraSort splitters: the multi-word kernel compare keeps
    # the array backend on the kernel path (see core/shuffle.py)
    return sample_boundaries(sample, n_buckets, key_bytes=KEY)


def _engine_run(engine_cls, backend: str, data: bytes, bounds,
                n_records: int, *, warm_runs: int = 0, best_of: int = 1):
    """Upload + run one TeraSort config; returns (sorted records, report).

    ``warm_runs`` extra identical runs execute first and are discarded —
    the array backend's steady-state number (the engine's real serving
    regime: sessions/streams re-run jobs against compiled kernels), with
    the one-off Pallas trace per padded block shape excluded, exactly
    like the partition microbench warms its jit before timing.
    ``best_of`` measured runs then execute and the report with the
    smallest ``partition_seconds`` wins — the partition microbench's
    min-of-N policy applied at engine level, so a single scheduler
    stall on a one-core host doesn't masquerade as a shuffle
    regression.

    ``timing_sync=True`` keeps the engine's ``partition_seconds`` honest
    under the dispatch-then-sync shuffle: the clock only stops after
    every shuffled piece is device-complete (see docs/BENCHMARKS.md,
    "timing policy")."""
    master, client = _make_cloud()
    client.upload("tera", data, replication=3)
    eng = engine_cls(master, client, timing_sync=True)
    # ONE job object reused across warm + measured runs: stage UDF jit
    # caches key on the callable's identity, so rebuilding the job per
    # run (fresh lambdas) would retrace every stage and the warm runs
    # would never actually warm anything.
    job = _terasort_job(bounds, backend)
    for _ in range(warm_runs):
        eng.run(job)
    gc.collect()   # cloud-build + warm-run garbage stays out of timing
    best = None
    for _ in range(max(best_of, 1)):
        outputs, rep = eng.run(job)
        if best is None or rep.partition_seconds < best[1].partition_seconds:
            best = (outputs, rep)
    outputs, rep = best
    return _check_sorted(outputs, n_records), rep


def _rec_per_s(rep) -> int:
    return round(rep.partitioned_records / max(rep.partition_seconds, 1e-9))


def run_host_level(n_records: int = 50_000) -> dict:
    """Sphere vs Hadoop-style on the bytes backend, plus the same Sphere
    job on the array backend (outputs must agree byte-for-byte)."""
    data = _gen_records(n_records)
    bounds = _sample_bounds(data)

    out = {}
    baseline = None
    for label, engine_cls, backend in (
            ("sphere", SphereEngine, "bytes"),
            ("hadoop_style", _NoLocalityEngine, "bytes"),
            ("sphere_array", SphereEngine, "array")):
        warm = 1 if backend == "array" else 0
        allrec, rep = _engine_run(engine_cls, backend, data, bounds,
                                  n_records, warm_runs=warm)
        if engine_cls is SphereEngine:
            if baseline is None:
                baseline = allrec
            else:
                assert allrec == baseline, "backends disagree"
        out[label] = {
            "backend": backend,
            "sim_seconds": round(rep.sim_seconds, 3),
            "locality": round(rep.locality_fraction, 3),
            "bytes_moved": rep.bytes_moved,
            "partition_seconds": round(rep.partition_seconds, 4),
            "partition_rec_per_s": _rec_per_s(rep),
            # array backend: distinct traced shapes per pad-stable stage
            # UDF (1 per stage = the jit-once guarantee held)
            "udf_traces": dict(rep.udf_traces),
            # dispatch-then-sync accounting: the array backend harvests
            # one shuffle round behind ONE host barrier, so
            # rounds_per_sync sits at 1.0 (a per-worker-sync regression
            # drags it toward 1/workers); bytes never syncs a device.
            "shuffle_rounds": rep.shuffle_rounds,
            "host_syncs": rep.host_syncs,
            "rounds_per_sync": round(rep.shuffle_rounds
                                     / rep.host_syncs, 3)
                               if rep.host_syncs else None,
            # fused worker-axis round accounting: hot-loop compiled calls
            # across the job's rounds.  The fused round holds
            # dispatches_per_round at a small constant (stacked apply +
            # bounded scatter shards + harvest gather) at any worker or
            # task count; a climb toward O(tasks + workers) means rounds
            # fell back to the per-worker loop (gated, lower is better).
            "device_dispatches": rep.device_dispatches,
            "dispatches_per_round": round(rep.device_dispatches
                                          / rep.shuffle_rounds, 2)
                                    if rep.shuffle_rounds else None,
        }
    out["speedup"] = round(out["hadoop_style"]["sim_seconds"]
                           / out["sphere"]["sim_seconds"], 2)
    return out


def run_engine_scales(scales) -> list:
    """Engine-level partition throughput, bytes vs array, at every scale.

    This is the metric the device-resident scatter exists for: the whole
    engine shuffle — per-worker RecordBatch in, bucket-sliced
    RecordBatches out — not the standalone kernel.  The array number is
    steady-state (one warm run first, then best-of-5 measured runs, see
    :func:`_engine_run`); the cold first run is also reported so the
    one-off trace cost stays visible.  ``array_over_bytes`` should be
    >= 1 at every scale — the flagship-scale engine throughput is what
    ``check_regression.py`` gates.
    """
    rows = []
    for n in scales:
        data = _gen_records(n)
        bounds = _sample_bounds(data)
        rec_b, rep_b = _engine_run(SphereEngine, "bytes", data, bounds, n)
        rec_cold, rep_cold = _engine_run(SphereEngine, "array", data,
                                         bounds, n)
        rec_a, rep_a = _engine_run(SphereEngine, "array", data, bounds, n,
                                   warm_runs=1, best_of=5)
        assert rec_a == rec_b == rec_cold, "backends disagree"
        rows.append({
            "records": n,
            "bytes_rec_per_s": _rec_per_s(rep_b),
            "array_rec_per_s": _rec_per_s(rep_a),
            "array_cold_rec_per_s": _rec_per_s(rep_cold),
            "array_over_bytes": round(_rec_per_s(rep_a)
                                      / max(_rec_per_s(rep_b), 1), 2),
        })
    return rows


def run_partition_bench(n_records: int = 1_000_000, n_buckets: int = 16,
                        repeats: int = 3) -> dict:
    """The shuffle hot loop at scale, three ways: per-record Python
    partitioning, the analysis kernel + argsort/gather, and the
    device-resident ``scatter_batch`` path the engine actually runs
    (one fused kernel pass + device epilogue, one host sync for the
    histogram).  Min-of-N wall time each; array paths are warmed once
    so jit compile is excluded — every row is steady-state throughput.
    Splitters are full 10-byte TeraSort keys: the kernel compares them
    as 3-word rows, so the headline is the multi-word path end-to-end."""
    import jax

    from repro.core.shuffle import scatter_batch

    blob = _gen_records(n_records)
    records = [blob[i:i + RECORD] for i in range(0, len(blob), RECORD)]
    bounds = sample_boundaries(records[:1000], n_buckets, key_bytes=KEY)
    part = range_partitioner(bounds)

    def bytes_run():
        buckets = [[] for _ in range(n_buckets)]
        for r in records:
            buckets[part(r, n_buckets)].append(r)
        return buckets

    batch = RecordBatch.from_bytes(blob, RECORD)

    def array_run():
        ids, hist = partition_batch(batch, part, n_buckets)
        pieces = scatter_by_ids(batch, ids, hist)
        jax.block_until_ready([p.data for p in pieces])
        return pieces

    def scatter_run():
        pieces = scatter_batch(batch, part, n_buckets)
        jax.block_until_ready([p.data for p in pieces])
        return pieces

    def _timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return time.perf_counter() - t0, out

    runs = [_timed(bytes_run) for _ in range(repeats)]
    t_bytes, buckets = min(runs, key=lambda r: r[0])
    array_run()  # warm: jit compile + constant folding
    runs = [_timed(array_run) for _ in range(repeats)]
    t_array, pieces = min(runs, key=lambda r: r[0])
    scatter_run()  # warm
    runs = [_timed(scatter_run) for _ in range(repeats)]
    t_scat, spieces = min(runs, key=lambda r: r[0])

    # parity spot-check on the timed outputs: identical per-bucket counts
    assert [len(b) for b in buckets] == [p.num_records for p in pieces]
    assert [len(b) for b in buckets] == [p.num_records for p in spieces]

    return {
        "records": n_records,
        "n_buckets": n_buckets,
        "key_bytes": KEY,
        "bytes_seconds": round(t_bytes, 3),
        "array_seconds": round(t_array, 3),
        "scatter_seconds": round(t_scat, 3),
        "bytes_rec_per_s": round(n_records / t_bytes),
        "array_rec_per_s": round(n_records / t_array),
        "scatter_rec_per_s": round(n_records / t_scat),
        "speedup": round(t_bytes / t_array, 1),
        "scatter_speedup": round(t_bytes / t_scat, 1),
    }


def run_tracing(n_records: int = 50_000, *, best_of: int = 7,
                out_dir: str | None = None) -> dict:
    """The tracing plane's two promises, measured: enabled-mode overhead
    on the array TeraSort stays small (``overhead_ratio``, CI-gated at
    <5% over the untraced baseline via ``check_regression.py``), and the
    traced run exports a Chrome/Perfetto timeline
    (``TRACE_terasort.json`` when ``out_dir`` is given — the artifact
    ``scripts/check_trace.py`` validates in CI).

    Both arms use the engine-level timing policy (``timing_sync=True``,
    one warm run, best-of-N minimum on the whole-job wall time) so the
    ratio compares steady-state runs, not compile noise — and the timed
    runs interleave the two arms so clock drift or background load
    lands on both equally instead of skewing the ratio."""
    data = _gen_records(n_records)
    bounds = _sample_bounds(data)

    def setup(tracer):
        master, client = _make_cloud()
        client.upload("tera", data, replication=3)
        eng = SphereEngine(master, client, timing_sync=True, tracer=tracer)
        job = _terasort_job(bounds, "array")
        eng.run(job)   # warm: trace UDFs + shuffle kernels once
        return eng, job

    eng_off, job_off = setup(None)
    tracer = Tracer()
    eng_on, job_on = setup(tracer)
    gc.collect()
    best_off = best_on = None
    rep_off = rep_on = None
    for _ in range(max(best_of, 1)):
        t0 = time.perf_counter()
        _, rep_off = eng_off.run(job_off)
        dt = time.perf_counter() - t0
        best_off = dt if best_off is None else min(best_off, dt)
        t0 = time.perf_counter()
        _, rep_on = eng_on.run(job_on)
        dt = time.perf_counter() - t0
        best_on = dt if best_on is None else min(best_on, dt)
    out = {
        "records": n_records,
        "untraced_job_seconds": round(best_off, 4),
        "traced_job_seconds": round(best_on, 4),
        "overhead_ratio": round(best_on / max(best_off, 1e-9), 3),
        # tracing must ride the existing harvest: same sync count on/off
        "untraced_host_syncs": rep_off.host_syncs,
        "traced_host_syncs": rep_on.host_syncs,
        "spans": tracer.count(),
    }
    if out_dir is not None:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, "TRACE_terasort.json")
        doc = tracer.export_chrome(path)
        out["trace_path"] = path
        out["trace_events"] = len(doc["traceEvents"])
    return out


_DEVICE_BENCH = """
import jax, jax.numpy as jnp, numpy as np
from repro.core.spmd import data_mesh, distributed_sort, barrier_sort
mesh = data_mesh()
N = {n}
keys = jax.random.randint(jax.random.PRNGKey(0), (N,), 0, 1 << 30,
                          dtype=jnp.uint32)
out, valid = jax.jit(lambda k: distributed_sort(k, mesh))(keys)
per = np.asarray(out).reshape(mesh.devices.size, -1)
got = np.concatenate([p[p != 0xFFFFFFFF] for p in per])
assert np.array_equal(got, np.sort(np.asarray(keys)))
outb = jax.jit(lambda k: barrier_sort(k, mesh))(keys)
assert np.array_equal(np.asarray(outb).reshape(-1), np.sort(np.asarray(keys)))
n = mesh.devices.size
print(f"{{N*4}},{{N*4*n}}")
"""


def run_device_level(n_keys: int = 1 << 18) -> dict:
    """Exchanged-byte counts of the two sorts on 8 virtual CPU devices.
    The child is pinned to the CPU backend: it counts bytes only, and on
    a TPU host the chip already belongs to this process."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _DEVICE_BENCH.format(n=n_keys)],
        capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    b_s, b_h = out.stdout.strip().split("\n")[-1].split(",")
    return {"bytes_all_to_all": int(b_s), "bytes_barrier": int(b_h),
            "traffic_ratio": round(int(b_h) / int(b_s), 1),
            "correct": True}


def main(smoke: bool = False, out_dir: str = ".") -> dict:
    host = run_host_level(5_000 if smoke else 50_000)
    print("level,metric,value")
    for label in ("sphere", "hadoop_style", "sphere_array"):
        for k, v in host[label].items():
            print(f"host:{label},{k},{v}")
    print(f"host,speedup,{host['speedup']}  (paper band: 2-3x)")
    scales = run_engine_scales([5_000, 20_000] if smoke
                               else [5_000, 50_000, 200_000, 1_000_000])
    for row in scales:
        print(f"host_scales:{row['records']},bytes_rec_per_s,"
              f"{row['bytes_rec_per_s']}")
        print(f"host_scales:{row['records']},array_rec_per_s,"
              f"{row['array_rec_per_s']} ({row['array_over_bytes']}x bytes)")
    part = run_partition_bench(100_000 if smoke else 1_000_000,
                               repeats=2 if smoke else 5)
    for k, v in part.items():
        print(f"partition,{k},{v}")
    dev = run_device_level(1 << 14 if smoke else 1 << 18)
    for k, v in dev.items():
        print(f"device,{k},{v}")
    trc = run_tracing(20_000 if smoke else 50_000, out_dir=out_dir)
    for k, v in trc.items():
        print(f"tracing,{k},{v}")
    return {"host": host, "host_scales": scales, "partition": part,
            "device": dev, "tracing": trc}


if __name__ == "__main__":
    try:
        from benchmarks.bench_out import write_bench
    except ImportError:
        from bench_out import write_bench
    smoke = "--smoke" in sys.argv
    write_bench("table3_terasort", main(smoke=smoke), smoke=smoke)
