"""Sphere tracing plane + metrics registry (ISSUE 10).

Covers the tracer's recording contract (spans, parents, instants, two
clock domains, Chrome export), the zero-cost disabled path, the metrics
registry's instrument semantics, and the two reconciliation guarantees:
``SphereReport`` fields equal the registry series the report mirrors
into, and the bytes and array backends emit identical span *counts* for
every shared (non-device) span name on the same job.
"""
import gc
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_cloud
from repro.core import (MetricsRegistry, NULL_TRACER, SphereEngine,
                        SphereJob, Tracer)
from repro.core import trace as trace_mod
from repro.core.kmeans import encode_points, kmeans_sphere
from repro.core.planner import _MIRRORED_COUNTERS
from repro.core.shuffle import sample_boundaries, terasort_stages
from repro.core.trace import NullTracer, link_track

RECORD, KEY = 100, 10


# ------------------------------ tracer core ---------------------------------

def test_span_nesting_and_parent_links():
    t = Tracer()
    with t.span("outer", track="control") as outer:
        with t.span("inner", track="control") as inner:
            pass
        t.instant("mark", track="control")
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert t.count("outer") == 1 and t.count("inner") == 1
    assert t.count("mark") == 1
    assert t.counts_by_name() == {"outer": 1, "inner": 1, "mark": 1}


def test_span_measures_wall_seconds():
    t = Tracer()
    with t.span("timed") as sp:
        pass
    assert sp.wall_seconds >= 0.0
    assert sp.t1 >= sp.t0


def test_parent_stack_is_thread_local():
    t = Tracer()
    seen = {}

    def worker():
        with t.span("child-thread") as sp:
            seen["parent"] = sp.parent_id

    with t.span("main-thread"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    # the producer thread's span must NOT parent to the main thread's
    assert seen["parent"] is None


def test_add_span_and_instant_validate_clock():
    t = Tracer()
    t.add_span("sim-task", track="worker:w0", t0=1.0, t1=2.5, clock="sim")
    with pytest.raises(ValueError, match="unknown clock"):
        t.add_span("bad", track="x", t0=0, t1=1, clock="gps")
    with pytest.raises(ValueError, match="unknown clock"):
        t.instant("bad", track="x", clock="gps")


def test_set_attrs_merges():
    t = Tracer()
    with t.span("s", attrs={"a": 1}) as sp:
        sp.set_attrs(b=2)
    assert sp.attrs == {"a": 1, "b": 2}


def test_null_tracer_is_timer_only():
    with NULL_TRACER.span("anything", track="shuffle") as sp:
        pass
    assert sp.wall_seconds >= 0.0          # the one timing idiom still works
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.add_span("x", track="t", t0=0, t1=1) is None
    assert NULL_TRACER.instant("x", track="t") is None
    with pytest.raises(RuntimeError, match="tracing is disabled"):
        NullTracer().export_chrome("/tmp/never.json")


# ----------------------------- chrome export --------------------------------

def test_export_chrome_structure(tmp_path):
    t = Tracer()
    with t.span("outer", track="control"):
        with t.span("inner", track="control"):
            pass
    t.add_span("task:a", track="worker:w0", t0=0.0, t1=2.0, clock="sim")
    t.add_span("xfer:a", track=link_track(("x", "y")), t0=0.5, t1=1.0,
               clock="sim")
    t.instant("host-sync", track="host-sync")
    path = tmp_path / "trace.json"
    doc = t.export_chrome(str(path))
    assert path.exists()
    assert doc["otherData"]["open_spans"] == 0
    assert doc["otherData"]["spans"] == 4
    assert doc["otherData"]["instants"] == 1

    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"process_name", "thread_name", "outer", "inner", "task:a",
            "host-sync"} <= names
    # sim and wall events live in distinct processes
    pid_of = {e["name"]: e["pid"] for e in evs if e.get("ph") == "X"}
    assert pid_of["task:a"] != pid_of["outer"]
    # per-track timestamps are monotonic in document order
    last = {}
    for e in evs:
        if e.get("ph") == "M":
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last.get(key, float("-inf"))
        last[key] = e["ts"]


def test_export_passes_check_trace(tmp_path):
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "check_trace", os.path.join(os.path.dirname(__file__), "..",
                                    "scripts", "check_trace.py"))
    check_trace = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check_trace)

    _, rep, _, tracer = _run_terasort(tmp_path, "bytes", Tracer())
    doc = tracer.export_chrome()
    assert check_trace.check(doc, expect=["worker:", "event:", "job:"]) == []
    # a violated expectation is reported
    assert check_trace.check(doc, expect=["no-such-span"])


# ----------------------------- metrics registry -----------------------------

def test_registry_instruments():
    m = MetricsRegistry()
    m.counter("c", run="r1").inc()
    m.counter("c", run="r1").inc(2.5)
    m.counter("c", run="r2").inc(10)       # distinct labels = distinct series
    assert m.value("c", run="r1") == 3.5
    assert m.value("c", run="r2") == 10
    assert m.value("never-written") == 0.0

    m.gauge("g").set(4)
    m.gauge("g").set(7)
    assert m.value("g") == 7.0

    h = m.histogram("h")
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    assert h.stats() == {"count": 3, "sum": 6.0, "min": 1.0, "max": 3.0}
    with pytest.raises(TypeError, match="histogram"):
        m.value("h")


def test_registry_kind_collision():
    m = MetricsRegistry()
    m.counter("x", a="1")
    with pytest.raises(TypeError, match="already registered as a counter"):
        m.gauge("x", a="1")
    m.gauge("x", a="2")                    # different labels: fine


def test_registry_snapshot_and_series():
    m = MetricsRegistry()
    m.counter("a").inc(5)
    m.histogram("b").observe(1.0)
    snap = {row["name"]: row for row in m.snapshot()}
    assert snap["a"]["value"] == 5.0 and snap["a"]["kind"] == "counter"
    assert snap["b"]["count"] == 1
    assert [i.name for i in m.series("a")] == ["a"]
    assert m.next_run_labels() != m.next_run_labels()


# --------------------------- engine integration -----------------------------

def _gen_records(n, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, size=(n, KEY), dtype=np.uint8)
    payload = np.full((n, RECORD - KEY), ord("v"), np.uint8)
    return np.concatenate([keys, payload], axis=1).tobytes()


def _run_terasort(tmp_path, backend, tracer=None, n=1500, prefetch=True):
    master, _, client = make_cloud(tmp_path / backend,
                                   chunk_size=500 * RECORD)
    data = _gen_records(n)
    client.upload("tera", data)
    recs = [data[i:i + RECORD] for i in range(0, 200 * RECORD, RECORD)]
    bounds = sample_boundaries(recs, 4, key_bytes=KEY)
    metrics = MetricsRegistry()
    eng = SphereEngine(master, client, tracer=tracer, metrics=metrics,
                       prefetch=prefetch)
    job = SphereJob("tsort", "tera",
                    terasort_stages(bounds, backend, 4, key_bytes=KEY),
                    record_size=RECORD, backend=backend)
    out, rep = eng.run(job)
    return out, rep, metrics, eng.tracer


def test_report_equals_registry(tmp_path):
    _, rep, metrics, _ = _run_terasort(tmp_path, "bytes")
    labels = rep.metric_labels
    assert labels.get("backend") == "bytes" and "run" in labels
    for name in sorted(_MIRRORED_COUNTERS):
        assert metrics.value(f"sphere.{name}", **labels) == \
            pytest.approx(getattr(rep, name)), name
    assert metrics.value("sphere.locality_fraction", **labels) == \
        pytest.approx(rep.locality_fraction)
    h = metrics.histogram("sphere.stage_seconds", **labels)
    assert h.count == len(rep.stage_seconds)
    assert h.total == pytest.approx(sum(rep.stage_seconds))


def test_report_equals_registry_array(tmp_path):
    _, rep, metrics, _ = _run_terasort(tmp_path, "array")
    labels = rep.metric_labels
    for name in sorted(_MIRRORED_COUNTERS):
        assert metrics.value(f"sphere.{name}", **labels) == \
            pytest.approx(getattr(rep, name)), name
    for stage, traces in rep.udf_traces.items():
        assert metrics.value("sphere.udf_traces", stage=stage,
                             **labels) == traces


ARRAY_ONLY = {"host-sync", "h2d-put", "output-wait", "d2h", "d2h-issue",
              "d2h-transfer", "d2h-tobytes"}


def _shared_span_counts(tracer):
    """Span counts for names both backends emit: device-only names
    (``host-sync`` markers, the stage-0 device put, the output wait and
    copy-out) and compile spans (what the process had not yet compiled)
    excluded."""
    return {name: c for name, c in tracer.counts_by_name().items()
            if name not in ARRAY_ONLY and not name.startswith("jit-")}


def test_span_count_parity_bytes_vs_array(tmp_path):
    out_b, _, _, t_bytes = _run_terasort(tmp_path, "bytes", Tracer())
    out_a, _, _, t_array = _run_terasort(tmp_path, "array", Tracer())
    assert b"".join(out_b) == b"".join(out_a)
    counts_b = _shared_span_counts(t_bytes)
    counts_a = _shared_span_counts(t_array)
    assert counts_b == counts_a
    # the taxonomy's control spans are all present
    for name in ("job:tsort", "plan:partition", "exec:partition",
                 "shuffle:partition", "plan:sort", "exec:sort",
                 "shuffle-round", "fetch-chunk", "planner:plan-stage"):
        assert counts_b.get(name, 0) >= 1, name


def test_tracing_changes_no_counters(tmp_path):
    """Tracing must ride the existing data plane: identical report
    counters (host syncs above all) with the tracer on and off."""
    _, rep_off, _, _ = _run_terasort(tmp_path / "off", "array")
    _, rep_on, _, _ = _run_terasort(tmp_path / "on", "array", Tracer())
    for name in ("host_syncs", "shuffle_rounds", "device_dispatches",
                 "tasks", "sim_seconds", "bytes_moved", "bytes_local"):
        assert getattr(rep_on, name) == getattr(rep_off, name), name


def test_attach_bus_replays_history(tmp_path):
    master, _, client = make_cloud(tmp_path, chunk_size=500 * RECORD)
    client.upload("tera", _gen_records(600))
    tracer = Tracer()
    # attach AFTER the cloud was built: the bounded history replays, so
    # the timeline still shows the joins/uploads that already happened
    tracer.attach_bus(master.events)
    assert tracer.count("event:server-joined") == 6
    assert tracer.count("event:file-created") == 1
    before = tracer.count("event:chunk-replicated")
    client.upload("tera2", _gen_records(600, seed=1))
    assert tracer.count("event:chunk-replicated") > before  # live too


def test_master_instants_and_repair_span(tmp_path):
    from repro.sector.replication import ReplicationDaemon

    master, servers, client = make_cloud(tmp_path, chunk_size=500 * RECORD)
    tracer = Tracer()
    SphereEngine(master, client, tracer=tracer)  # wires master.tracer
    assert master.tracer is tracer
    client.upload("tera", _gen_records(600))
    assert tracer.count("master:placement") >= 1
    daemon = ReplicationDaemon(master, client)
    master.deregister(servers[0].server_id)
    assert tracer.count("replication-repair") == 1
    rep_span = [e for e in tracer.snapshot()
                if e.name == "replication-repair"][0]
    assert rep_span.attrs["died"] == servers[0].server_id
    assert "repaired" in rep_span.attrs
    assert tracer.count("master:repair-plan") >= 1
    assert daemon.event_repairs == rep_span.attrs["repaired"]


# ------------------------- spans inside the job -----------------------------

def _children(tracer, parent, name):
    return [e for e in tracer.snapshot()
            if e.name == name and e.parent_id == parent.span_id]


@pytest.mark.parametrize("backend,layout",
                         [("bytes", None), ("array", "rows"),
                          ("array", "slab")],
                         ids=["bytes", "array", "array-slab"])
def test_one_materialise_per_job_run(tmp_path, backend, layout):
    # 1,500 records leave each output slot a few hundred KB padded, below
    # executor.SLAB_MIN_BYTES; 48,000 leave each above it
    n = 48000 if layout == "slab" else 1500
    out, _, _, tracer = _run_terasort(tmp_path, backend, Tracer(), n=n)
    assert tracer.count("materialise") == 1
    if backend == "bytes":
        assert tracer.count("d2h") == tracer.count("output-wait") == 0
        return
    (mat,) = [e for e in tracer.snapshot() if e.name == "materialise"]
    d2h = _children(tracer, mat, "d2h")
    assert len(d2h) == len(_children(tracer, mat, "output-wait")) \
        == len(out)
    # the copy-out's bytes are the output's, padding left behind
    assert sum(e.attrs["bytes"] for e in d2h) == sum(map(len, out))
    # each copy-out is the transfer to a host array, then its bytes
    for e in d2h:
        (xfer,) = _children(tracer, e, "d2h-transfer")
        (pack,) = _children(tracer, e, "d2h-tobytes")
        assert e.t0 <= xfer.t0 <= xfer.t1 <= pack.t0 <= pack.t1 <= e.t1
        assert e.attrs["layout"] == layout


def test_kmeans_session_outputs_cross_as_rows(tmp_path):
    """A k-means fold's output is one row of partial sums, far below the
    slab threshold: its copy-out keeps the row layout."""
    master, _, client = make_cloud(tmp_path, chunk_size=800 * 16)
    pts = np.random.default_rng(0).normal(size=(3000, 4)).astype(np.float32)
    client.upload("pts", encode_points(pts))
    eng = SphereEngine(master, client, tracer=Tracer())
    sess = eng.session("pts", record_size=16, backend="array")
    kmeans_sphere(eng, "pts", dim=4, k=3, iters=2, backend="array",
                  session=sess)
    d2h = [e for e in eng.tracer.snapshot() if e.name == "d2h"]
    assert len(d2h) == 2
    assert all(e.attrs["layout"] == "rows" for e in d2h)


@pytest.mark.parametrize("prefetch", [True, False])
@pytest.mark.parametrize("backend", ["bytes", "array"])
def test_fetch_chunk_splits_into_read_and_put(tmp_path, backend, prefetch):
    _, rep, _, tracer = _run_terasort(tmp_path, backend, Tracer(),
                                      prefetch=prefetch)
    fetches = [e for e in tracer.snapshot() if e.name == "fetch-chunk"]
    assert len(fetches) == 3 and rep.retried == 0
    assert {e.track for e in fetches} == \
        {"prefetch" if prefetch else "fetch"}
    for f in fetches:
        (read,) = _children(tracer, f, "sector-read")
        assert read.track == f.track
        puts = _children(tracer, f, "h2d-put")
        if backend == "bytes":
            assert puts == []
        else:
            (put,) = puts
            assert put.track == f.track and put.attrs["bytes"] == 500 * RECORD
            assert read.t1 <= put.t0
    # the consumer waits once per prefetched stage-0 task
    assert tracer.count("prefetch-wait") == (3 if prefetch else 0)


def test_no_dispatch_spans(tmp_path):
    _, _, _, tracer = _run_terasort(tmp_path, "array", Tracer())
    assert not [n for n in tracer.counts_by_name()
                if n.startswith("dispatch:")]


def test_engine_binds_no_registry_unless_given(tmp_path):
    master, _, client = make_cloud(tmp_path, chunk_size=500 * RECORD)
    client.upload("tera", _gen_records(600))
    eng = SphereEngine(master, client)
    assert eng.metrics is None
    job = SphereJob("tsort", "tera", terasort_stages([], "bytes", 1),
                    record_size=RECORD)
    _, rep = eng.run(job)
    assert rep.__dict__.get("_metrics") is None
    assert rep.metric_labels == {}


def _double(x):
    return x * 2 + 1


def test_jit_spans_on_first_call_only():
    tracer = Tracer()
    f = jax.jit(_double)
    x = jnp.arange(11, dtype=jnp.float32)
    with tracer.span("first") as first:
        f(x)
    traces = [e for e in tracer.snapshot() if e.name == "jit-trace"]
    assert [e.attrs["fun_name"] for e in traces].count("_double") == 1
    lowers = [e for e in tracer.snapshot() if e.name == "jit-lower"]
    assert any("_double" in e.attrs["fun_name"] for e in lowers)
    for e in traces + lowers:
        assert e.clock == "wall" and e.parent_id == first.span_id
        assert first.t0 <= e.t0 <= e.t1 <= first.t1 + 1e-3
    before = tracer.count()
    with tracer.span("second"):
        f(x)
    assert tracer.count() == before + 1      # the "second" span alone
    # outside any of its spans a tracer records no compile
    jax.jit(_double)(jnp.arange(13, dtype=jnp.float32))
    assert tracer.count() == before + 1


def _listening():
    """Whether the compile listeners are registered with JAX, once every
    unreachable Tracer has been collected."""
    from jax._src import monitoring

    gc.collect()
    watch = trace_mod._COMPILE_EVENTS
    spans = watch._on_span in monitoring.get_event_time_span_listeners()
    durations = watch._on_duration in monitoring.get_event_duration_listeners()
    assert spans == durations == watch.listening
    return spans


def test_compile_listener_lives_with_the_tracers():
    assert not _listening()
    a, b = Tracer(), Tracer()
    assert _listening()
    del a
    assert _listening()
    del b
    assert not _listening()
    # many tracers, built and dropped, leave nothing behind
    for _ in range(50):
        Tracer()
    assert not _listening()


def test_null_tracer_records_nothing_and_listens_to_nothing(tmp_path):
    assert not _listening()
    master, _, client = make_cloud(tmp_path, chunk_size=800 * 16)
    pts = np.random.default_rng(0).normal(size=(3000, 4)).astype(np.float32)
    client.upload("pts", encode_points(pts))
    eng = SphereEngine(master, client)
    assert eng.tracer is NULL_TRACER
    kmeans_sphere(eng, "pts", dim=4, k=3, iters=2, backend="array")
    assert not _listening()
    assert trace_mod._COMPILE_EVENTS._live() == []
