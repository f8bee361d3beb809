"""Job driver: k-means fits over one uploaded file of points.

Set-up generates the points and the initial centroids from the seed and
uploads the points to Sector.  A job is ``kmeans_sphere`` on the array
backend for the configuration's ``max_iteration`` iterations from that
same init, with no early stop: each iteration runs the mask-aware assign
stage over ``kmeans_assign``, the reduce round to bucket 0, the fold
stage and the host centroid update.  The traffic's ``session`` is
``"shared"`` (one ``SphereSession`` opened in set-up serves every job,
so chunks are fetched once) or ``"per_job"`` (each job opens its own,
so every fit reads the file through Sector again).  The result is the
fitted centroids, compared with float64 Lloyd's from the same init.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from bench import cloud
from bench.registry import load_module

FILE = "points"
SESSIONS = ("shared", "per_job")


def set_up(config: dict, traffic: dict, seed: int, store: str, tracer
           ) -> SimpleNamespace:
    from repro.core import SphereEngine
    from repro.core.kmeans import encode_points

    if traffic["session"] not in SESSIONS:
        raise ValueError(f"session {traffic['session']!r} not in {SESSIONS}")
    if config["record_bytes"] != 4 * config["dimensions"]:
        raise ValueError("a record is one float32 point")
    points, init = load_module("gen", config["data"]).generate(config, seed)
    master, client = cloud.make_cloud(store, config)
    client.upload(FILE, encode_points(points),
                  replication=config["replication"])
    engine = SphereEngine(master, client, tracer=tracer)
    session = None
    if traffic["session"] == "shared":
        session = engine.session(FILE, record_size=config["record_bytes"],
                                 backend="array")
    return SimpleNamespace(config=config, traffic=traffic, points=points,
                           init=init, master=master, engine=engine,
                           session=session)


def run_one(state) -> SimpleNamespace:
    from repro.core.kmeans import kmeans_sphere

    cfg = state.config
    its: list = []
    cents, report = kmeans_sphere(
        state.engine, FILE, dim=cfg["dimensions"], k=cfg["k"],
        iters=cfg["max_iteration"], backend="array",
        session=state.session or True, init=state.init, iter_seconds=its)
    return SimpleNamespace(result=cents, report=report, iter_seconds=its)


def same(a, b) -> bool:
    return np.array_equal(a, b)


def close(state) -> None:
    if state.session is not None:
        state.session.close()
        state.session = None


def _err(got, want) -> float:
    """Largest centroid difference; inf for a wrong shape or a NaN."""
    if np.shape(got) != want.shape:
        return float("inf")
    err = float(np.abs(np.asarray(got, np.float64) - want).max())
    return err if np.isfinite(err) else float("inf")


def reference(state) -> np.ndarray:
    """The plain reference's answer: float64 Lloyd's from the same init."""
    ref = load_module("references", state.config["reference"])
    return ref.reference(state.points, state.init,
                         state.config["max_iteration"])


def check(state, results, outs, spans):
    """``(checks, wrong)``: each number compared with its limit, and for
    each distinct result whether it is out of the limit."""
    limit = state.config["limits"]["centroid_err"]
    want = reference(state)
    errs = [_err(r, want) for r in results]
    retraced = sum(1 for o in outs for v in o.report.udf_traces.values()
                   if v != 1)
    short = cloud.under_replicated(state.master, FILE, state.points,
                                   state.config["replication"])
    return {"centroid_err": (max(errs), limit),
            "retraced_udfs": (retraced, 0),
            "under_replicated_chunks": (short, 0)}, [e > limit for e in errs]
