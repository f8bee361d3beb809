"""Bring-up smoke: Sphere's TeraSort and angle k-means on a TPU.

Drives the engine's main path the way a user does — upload to Sector
chunk servers, then ``SphereEngine.run`` / ``kmeans_sphere`` on the
array backend with the Mosaic-compiled Pallas kernels — at full record
width, and checks every output against a plain numpy reference:

* **terasort** — GraySort-shaped 100-byte records (10-byte random key)
  through the partition stage, the fused shuffle round (``bucket_scatter``
  kernel) and the sort stage.  The output must equal a stable numpy sort
  of the same records byte for byte, every shuffle round must have run on
  the fused path, and each round must cost one host sync.
* **kmeans** — float32 points (DIM=8, K=10) through a ``SphereSession``
  chain over the ``kmeans_assign`` kernel.  The centroids must match a
  numpy Lloyd's loop from the same init, and each stage UDF must have
  traced once.

Usage (from the checkout root, on a TPU host)::

    python chip_smoke.py              # one chip: both phases
    python chip_smoke.py --chips 4    # 2x2 host: TeraSort on the mesh

``--chips 4`` first checks a few-KB ``all_to_all`` over the 4-device
``data`` mesh against numpy, then runs TeraSort with 8 chunk servers on
that mesh (each chunk put on its slot's chip, the ``shard_map`` +
``all_to_all`` round, the sort stage on each chip) and requires the
stable numpy sort and every shuffle round on the mesh path.
A run killed by SIGTERM (a time limit) first prints every thread's
Python stack to stderr, to show where a stalled run waited.

The timings printed are bring-up evidence, not benchmark numbers.  The
last line of stdout is one JSON object naming the device; the exit code
is non-zero, with no such line, when JAX finds no TPU (there is no CPU
fallback) or when any phase fails.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import signal
import sys
import tempfile
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

RECORD, KEY = 100, 10          # TeraSort / GraySort record and key bytes
DIM, K, ITERS = 8, 10, 5       # angle k-means shape (benchmarks/table2)
KMEANS_ATOL = 1e-4             # centroid tolerance against numpy Lloyd's
TAG = "[bring-up smoke, not a benchmark]"


def _log(msg: str) -> None:
    print(f"{TAG} {msg}", flush=True)


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def gen_records(n: int, seed: int) -> np.ndarray:
    """uint8 [n, 100] GraySort-shaped records: a 10-byte random key, then
    gensort's layout — break bytes, the record number as 32 hex digits,
    filler and end bytes.  Every record is unique."""
    rng = np.random.default_rng(seed)
    rec = np.empty((n, RECORD), np.uint8)
    rec[:, :KEY] = rng.integers(0, 256, size=(n, KEY), dtype=np.uint8)
    rec[:, 10:12] = (0x00, 0x11)
    hexd = np.frombuffer(b"0123456789ABCDEF", np.uint8)
    idx = np.arange(n, dtype=np.uint64)
    rec[:, 12:28] = ord("0")
    for j in range(16):
        rec[:, 28 + j] = hexd[(idx >> np.uint64(4 * (15 - j)))
                              & np.uint64(15)]
    rec[:, 44:48] = (0x88, 0x99, 0xAA, 0xBB)
    rec[:, 48:96] = hexd[idx & np.uint64(15)][:, None]
    rec[:, 96:100] = (0xCC, 0xDD, 0xEE, 0xFF)
    return rec


def key_words(rec: np.ndarray):
    """The 10-byte key as (big-endian uint64, uint16) columns."""
    return (np.ascontiguousarray(rec[:, :8]).view(">u8").ravel(),
            np.ascontiguousarray(rec[:, 8:KEY]).view(">u2").ravel())


def stable_key_sort(rec: np.ndarray) -> np.ndarray:
    """The reference: a stable sort of the records by their 10-byte key."""
    k1, k2 = key_words(rec)
    return rec[np.lexsort((k2, k1))]


def lloyd(pts: np.ndarray, init: np.ndarray, iters: int) -> np.ndarray:
    """The reference: plain Lloyd's k-means in float64 with the engine's
    update rule (float32 centroids, empty clusters keep theirs)."""
    c = init.astype(np.float32).copy()
    for _ in range(iters):
        cd = c.astype(np.float64)
        sums = np.zeros_like(cd)
        counts = np.zeros(len(cd))
        for lo in range(0, len(pts), 1 << 18):
            xb = pts[lo:lo + (1 << 18)].astype(np.float64)
            a = ((xb[:, None, :] - cd[None]) ** 2).sum(-1).argmin(1)
            counts += np.bincount(a, minlength=len(cd))
            sums += np.stack([np.bincount(a, weights=xb[:, d],
                                          minlength=len(cd))
                              for d in range(pts.shape[1])], axis=1)
        nz = counts > 0
        c[nz] = (sums[nz] / counts[nz, None]).astype(np.float32)
    return c


def make_cloud(root: str, n_servers: int, record_size: int):
    """A Sector cloud of ``n_servers`` chunk servers spread round-robin
    over the Teraflow sites, storing under ``root``.  Chunks are the
    repo's 64 MiB default trimmed to whole records."""
    from repro.sector import ChunkServer, SectorClient, SectorMaster
    from repro.sector.chunk import CHUNK_SIZE

    master = SectorMaster(chunk_size=CHUNK_SIZE - CHUNK_SIZE % record_size)
    sites = master.topology.sites
    for i in range(n_servers):
        master.register(ChunkServer(f"s{i}", sites[i % len(sites)], root))
    master.acl.add_member("smoke")
    master.acl.grant_write("smoke")
    return master, SectorClient(master, "smoke", "chicago")


def _round_counters(rep) -> dict:
    return {
        "shuffle_rounds": rep.shuffle_rounds,
        "rounds_per_sync": (rep.shuffle_rounds / rep.host_syncs
                            if rep.host_syncs else None),
        "dispatches_per_round": (rep.device_dispatches / rep.shuffle_rounds
                                 if rep.shuffle_rounds else None),
    }


def run_terasort(n_records: int, seed: int, root: str, *, n_servers: int,
                 mesh=None) -> dict:
    """Upload, sort through the engine (on ``mesh``, where given),
    compare with the numpy sort.  Raises AssertionError on any
    mismatch."""
    from repro.core import SphereEngine, SphereJob, Tracer
    from repro.core.shuffle import sample_boundaries, terasort_stages

    t0 = time.perf_counter()
    rec = gen_records(n_records, seed)
    master, client = make_cloud(root, n_servers, RECORD)
    client.upload("tera", rec.tobytes(), replication=3)
    step = max(1, n_records // 100_000)
    bounds = sample_boundaries([r.tobytes() for r in rec[::step]],
                               n_servers, key_bytes=KEY)
    setup_s = time.perf_counter() - t0
    _log(f"terasort set-up done in {setup_s} s; running the engine")

    tracer = Tracer()
    eng = SphereEngine(master, client, tracer=tracer, mesh=mesh)
    stages = terasort_stages(bounds, "array", n_servers, key_bytes=KEY)
    job = SphereJob("terasort", "tera", stages,
                    record_size=RECORD, backend="array")
    t0 = time.perf_counter()
    outputs, rep = eng.run(job)
    run_s = time.perf_counter() - t0

    got = np.frombuffer(b"".join(outputs), np.uint8).reshape(-1, RECORD)
    spans = tracer.snapshot()
    rounds = [s.attrs.get("path") for s in spans if s.name == "shuffle-round"]
    # where the wall time went, per job phase (compiles included: the
    # first dispatch of each program compiles synchronously)
    phases = {}
    for s in spans:
        if s.clock == "wall" and s.track == "control":
            phases[s.name] = phases.get(s.name, 0.0) + s.wall_seconds
    info = {"records": n_records, "bytes": rec.nbytes,
            "setup_s": setup_s, "wall_s": run_s,
            **_round_counters(rep), "rounds": rounds,
            "phase_s": phases}
    _log("terasort " + " ".join(f"{k}={v}" for k, v in info.items()))
    _require(got.shape == rec.shape,
             f"terasort output shape {got.shape} != {rec.shape}")
    _require(np.array_equal(got, stable_key_sort(rec)),
             "terasort output differs from the stable numpy sort")
    want = "mesh" if mesh is not None else "fused"
    _require(bool(rounds) and all(r == want for r in rounds),
             f"shuffle rounds took {rounds}, expected all {want}")
    # a mesh round redone at a larger exchange capacity syncs once more
    _require(len(rounds) == rep.shuffle_rounds
             == rep.host_syncs - rep.exchange_regrows,
             f"{rep.shuffle_rounds} rounds with {rep.host_syncs} host syncs "
             f"and {rep.exchange_regrows} regrows")
    return info


def check_exchange(mesh) -> None:
    """A few-KB ``all_to_all`` through the engine's shuffle combinator,
    against numpy, before the mesh job: it tells a mesh whose exchange
    fails or stalls apart from a fault in the job.  Shard ``i`` sends
    its row ``j`` to shard ``j``, which keeps the rows in source order."""
    import jax.numpy as jnp

    from repro.core.spmd import sphere_shuffle

    d = mesh.shape["data"]
    x = np.arange(d * d * 8, dtype=np.int32).reshape(d * d, 8)
    got = np.asarray(sphere_shuffle(jnp.asarray(x), None, mesh))
    want = x.reshape(d, d, 8).transpose(1, 0, 2).reshape(d * d, 8)
    _require(np.array_equal(got, want),
             "all_to_all over the mesh differs from numpy")
    _log(f"exchange check passed: all_to_all over {d} devices")


def run_kmeans(n_points: int, seed: int, root: str) -> dict:
    """A 5-iteration k-means session through the engine vs numpy Lloyd's.
    Raises AssertionError on any mismatch."""
    from repro.core import SphereEngine
    from repro.core.kmeans import encode_points, kmeans_sphere

    t0 = time.perf_counter()
    pts = np.random.default_rng(seed + 1).normal(size=(n_points, DIM)) \
        .astype(np.float32)
    init = np.random.default_rng(seed).normal(size=(K, DIM)) \
        .astype(np.float32)
    master, client = make_cloud(root, 6, 4 * DIM)
    client.upload("pts", encode_points(pts), replication=2)
    setup_s = time.perf_counter() - t0

    eng = SphereEngine(master, client)
    t0 = time.perf_counter()
    cents, rep = kmeans_sphere(eng, "pts", dim=DIM, k=K, iters=ITERS,
                               backend="array", session=True, init=init)
    run_s = time.perf_counter() - t0

    ref = lloyd(pts, init, ITERS)
    err = float(np.abs(cents - ref).max())
    info = {"records": n_points, "bytes": pts.nbytes, "iters": ITERS,
            "setup_s": setup_s, "wall_s": run_s, **_round_counters(rep),
            "udf_traces": dict(rep.udf_traces), "max_abs_err": err}
    _log("kmeans " + " ".join(f"{k}={v}" for k, v in info.items()))
    # a v5e run with HIGHEST-precision distances lands within 1.5e-5 of
    # the reference; one bf16 MXU pass (the default) moves boundary
    # points and lands 5.4e-3 off on these points (numpy emulation)
    np.testing.assert_allclose(cents, ref, rtol=0, atol=KMEANS_ATOL)
    _require(bool(rep.udf_traces)
             and all(v == 1 for v in rep.udf_traces.values()),
             f"stage UDFs retraced: {rep.udf_traces}")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Sphere TeraSort + k-means bring-up smoke on a TPU")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--records", type=int, default=10_000_000,
                    help="TeraSort records (100 bytes each)")
    ap.add_argument("--points", type=int, default=10_000_000,
                    help="k-means points (32 bytes each)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: TeraSort alone, on the 4-chip mesh")
    args = ap.parse_args(argv)

    import jax

    from repro.core.spmd import data_mesh
    from repro.utils.backend import pallas_interpret, use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or pallas_interpret():
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    use_compile_cache(ROOT)
    # a time limit's SIGTERM first prints where every thread waits; the
    # handler already installed (the TPU runtime's) still runs after it
    faulthandler.register(signal.SIGTERM, chain=True)
    _log(f"device {dev.device_kind} x{len(devices)}")

    if args.chips == 4:
        mesh = data_mesh()
        phases = [("exchange", lambda tmp: check_exchange(mesh)),
                  ("terasort-mesh", lambda tmp: run_terasort(
                      args.records, args.seed, tmp, n_servers=8,
                      mesh=mesh))]
    else:
        phases = [
            ("terasort", lambda tmp: run_terasort(
                args.records, args.seed, tmp, n_servers=6)),
            ("kmeans", lambda tmp: run_kmeans(args.points, args.seed, tmp)),
        ]
    failed = []
    for name, phase in phases:
        with tempfile.TemporaryDirectory(prefix=f"chip_smoke_{name}_") as tmp:
            try:
                phase(tmp)
            except Exception:
                traceback.print_exc()
                failed.append(name)
    if failed:
        print(f"chip_smoke: FAILED {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
