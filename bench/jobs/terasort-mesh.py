"""Job driver: one-shot TeraSort jobs on a four-rack Sector cloud, one
chip per rack.

Set-up builds the Open Cloud Testbed's racks (``config["racks"]``, each
with ``chunk_servers / racks`` chunk servers), generates the records
from the seed, uploads them with the configuration's replication,
samples the range boundaries and builds a ``data`` mesh over one chip a
rack.  A job is ``SphereEngine.run`` of the two TeraSort stages on that
mesh: each chunk put on the chip that holds its slot, the partition
stage, the ``all_to_all`` exchange and regroup (one ``shard_map``
program), the sort stage on each chip's workers, and the sharded copy
to the host.  Its result is the per-bucket output bytes, which must
equal a stable sort of the records by their key; after the window every
replica of the upload is read back.
"""
from __future__ import annotations

import atexit
import os
import sys
from types import SimpleNamespace

import numpy as np

# The mesh data plane this cell runs.  A program without it fails here,
# as this module loads, before any device is touched.
from repro.core.spmd import data_mesh

from bench import cloud
from bench.registry import load_module

FILE = "gray"
SITE = "starlight"        # the client's rack


def make_cloud(root: str, config: dict):
    """The Open Cloud Testbed's racks with ``config["chunk_servers"]``
    chunk servers spread evenly over them, storing under ``root``;
    chunks of ``config["chunk_bytes"]`` trimmed to whole records.
    Returns ``(master, client)``."""
    from repro.sector import ChunkServer, SectorClient, SectorMaster
    from repro.sector.topology import OPEN_CLOUD_TESTBED

    sites = OPEN_CLOUD_TESTBED.sites
    racks, servers = config["racks"], config["chunk_servers"]
    if racks != len(sites) or servers % racks:
        raise ValueError(f"{servers} chunk servers over {racks} racks do "
                         f"not fit the testbed's {len(sites)}")
    record = config["record_bytes"]
    chunk = config["chunk_bytes"] - config["chunk_bytes"] % record
    master = SectorMaster(topology=OPEN_CLOUD_TESTBED, chunk_size=chunk)
    for i in range(servers):
        master.register(ChunkServer(f"s{i}", sites[i * racks // servers],
                                    root))
    master.acl.add_member(cloud.USER)
    master.acl.grant_write(cloud.USER)
    return master, SectorClient(master, cloud.USER, SITE)


def _exit_before_runtime_teardown() -> None:
    """At a clean exit, end the process before the TPU client is torn
    down.  After a profiled run on several chips that teardown
    (``TpuHal::TearDown``) blocks for good: on a 2x2 v5e a profiled
    program that only adds to an array sharded over the four chips
    printed its last line, then hung until it was killed.  By then the
    result line is out; after an uncaught exception the normal exit,
    and its exit code, stand."""
    if getattr(sys, "last_value", None) is None:
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)


def set_up(config: dict, traffic: dict, seed: int, store: str, tracer
           ) -> SimpleNamespace:
    from repro.core import SphereEngine, SphereJob
    from repro.core.shuffle import sample_boundaries, terasort_stages

    mesh = data_mesh(config["racks"])        # fails on fewer chips
    atexit.register(_exit_before_runtime_teardown)
    records = load_module("gen", config["data"]).generate(config, seed)
    master, client = make_cloud(store, config)
    client.upload(FILE, records.tobytes(), replication=config["replication"])
    sample = records[::config["sample_every"]]
    bounds = sample_boundaries([r.tobytes() for r in sample],
                               config["buckets"], key_bytes=config["key_bytes"])
    engine = SphereEngine(master, client, tracer=tracer, mesh=mesh)
    job = SphereJob("terasort", FILE,
                    terasort_stages(bounds, "array", config["buckets"],
                                    key_bytes=config["key_bytes"]),
                    record_size=config["record_bytes"], backend="array")
    return SimpleNamespace(config=config, records=records, master=master,
                           engine=engine, job=job)


def run_one(state) -> SimpleNamespace:
    outputs, report = state.engine.run(state.job)
    return SimpleNamespace(result=outputs, report=report, iter_seconds=None)


def same(a, b) -> bool:
    return a == b


def close(state) -> None:
    """Nothing stays on the devices between one-shot jobs."""


def round_checks(outs, spans) -> dict:
    """The window's shuffle rounds, each number with its limit: rounds
    whose ``shuffle-round`` span does not say ``path=mesh``, host syncs
    beyond one a round (a round redone at a larger exchange capacity
    counts as one more), and stage UDFs traced more than once a job."""
    rounds = sum(o.report.shuffle_rounds for o in outs)
    redone = sum(o.report.exchange_regrows for o in outs)
    syncs = sum(o.report.host_syncs for o in outs)
    mesh = sum(1 for s in spans if s.name == "shuffle-round"
               and (s.attrs or {}).get("path") == "mesh")
    retraced = sum(1 for o in outs for v in o.report.udf_traces.values()
                   if v != 1)
    return {"off_mesh_rounds": (rounds - mesh, 0),
            "syncs_over_rounds": (max(0, syncs - rounds - redone), 0),
            "retraced_udfs": (retraced, 0)}


def check(state, results, outs, spans):
    """``(checks, wrong)``: each number compared with its limit, and for
    each distinct result whether it differs from the plain reference's
    answer, the records in a stable key sort."""
    ref = load_module("references", state.config["reference"])
    want = ref.reference(state.records)
    width = state.config["record_bytes"]
    worst, wrong = 0, []
    for outputs in results:
        blob = b"".join(outputs)
        if len(blob) % width:
            bad = len(want)
        else:
            got = np.frombuffer(blob, np.uint8).reshape(-1, width)
            bad = ref.misplaced(got, want)
        wrong.append(bad > 0)
        worst = max(worst, bad)
    short = cloud.under_replicated(state.master, FILE, state.records,
                                   state.config["replication"])
    return {"misplaced_records": (worst, 0), **round_checks(outs, spans),
            "under_replicated_chunks": (short, 0)}, wrong
