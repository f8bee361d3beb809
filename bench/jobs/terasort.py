"""Job driver: one-shot TeraSort jobs over one uploaded GraySort file.

Set-up generates the records from the seed, uploads them to Sector with
the configuration's replication and samples the range boundaries.  A job
is ``SphereEngine.run`` of the two TeraSort stages on the array backend:
chunk fetch and decode, the partition stage, the fused ``bucket_scatter``
round and its regroup, the sort stage and output materialisation.  Its
result is the per-bucket output bytes, which must equal a stable sort of
the records by their key; after the window every replica of the upload
is read back.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from bench import cloud
from bench.registry import load_module

FILE = "gray"


def set_up(config: dict, traffic: dict, seed: int, store: str, tracer, *,
           key_bytes: int | None = None) -> SimpleNamespace:
    """``key_bytes`` below the configuration's is the control: the
    program's own shorter-key path, which breaks the stable sort by the
    full key."""
    from repro.core import SphereEngine, SphereJob
    from repro.core.shuffle import sample_boundaries, terasort_stages

    records = load_module("gen", config["data"]).generate(config, seed)
    master, client = cloud.make_cloud(store, config)
    client.upload(FILE, records.tobytes(), replication=config["replication"])
    kb = config["key_bytes"] if key_bytes is None else key_bytes
    sample = records[::config["sample_every"]]
    bounds = sample_boundaries([r.tobytes() for r in sample],
                               config["buckets"], key_bytes=kb)
    engine = SphereEngine(master, client, tracer=tracer)
    job = SphereJob("terasort", FILE,
                    terasort_stages(bounds, "array", config["buckets"],
                                    key_bytes=kb),
                    record_size=config["record_bytes"], backend="array")
    return SimpleNamespace(config=config, records=records, master=master,
                           engine=engine, job=job)


def run_one(state) -> SimpleNamespace:
    outputs, report = state.engine.run(state.job)
    return SimpleNamespace(result=outputs, report=report, iter_seconds=None)


def same(a, b) -> bool:
    return a == b


def close(state) -> None:
    """Nothing stays on the device between one-shot jobs."""


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
    rounds = sum(o.report.shuffle_rounds for o in outs)
    syncs = sum(o.report.host_syncs for o in outs)
    fused = sum(1 for s in spans if s.name == "shuffle-round"
                and (s.attrs or {}).get("path") == "fused")
    retraced = sum(1 for o in outs for v in o.report.udf_traces.values()
                   if v != 1)
    short = cloud.under_replicated(state.master, FILE, state.records,
                                   state.config["replication"])
    return {"misplaced_records": (worst, 0),
            "unfused_rounds": (rounds - fused, 0),
            "syncs_over_rounds": (max(0, syncs - rounds), 0),
            "retraced_udfs": (retraced, 0),
            "under_replicated_chunks": (short, 0)}, wrong
