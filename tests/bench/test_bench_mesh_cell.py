"""The four-chip TeraSort cell: its files resolve, its job driver's
round checks, its three per-layer readers on synthetic contexts, and
the cell run on four CPU devices from a copy of the benchmark."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))  # the checkout root, for ``bench``

import json
import shutil
import subprocess
from types import SimpleNamespace

import pytest

from bench import run
from bench.registry import ROOT, load_module
from bench.trace_reduce import TraceSummary

CELL = "terasort-gray-mesh4"
PEAKS = {"hbm_bytes_per_s": 819e9}


def _span(name, **attrs):
    return SimpleNamespace(name=name, t0=0.0, t1=1.0, attrs=attrs or None,
                           track="shuffle")


def _report(rounds=1, syncs=1, regrows=0, traces=None):
    return SimpleNamespace(shuffle_rounds=rounds, host_syncs=syncs,
                           exchange_regrows=regrows,
                           udf_traces=traces or {"partition": 1, "sort": 1})


def _summary(ops, devices=4):
    text = {k: f"%{k.split('/')[1]} = u8[8] op()" for k in ops}
    return TraceSummary(busy_s=1.0, window_s=2.0, op_seconds=ops,
                        op_text=text, idle_by_label={}, devices=devices)


def test_the_cell_resolves_to_four_chips_of_racks():
    spec = run.resolve(CELL)
    assert spec.workload["chips"] == 4
    assert spec.traffic == {"job": "terasort-mesh"}
    cfg = spec.config
    assert cfg["racks"] == 4 and cfg["chunk_servers"] % cfg["racks"] == 0
    assert cfg["records"] == 40_000_000
    assert {m["name"] for m in spec.per_layer} == {
        "collective_s", "mesh_round_roofline", "exchange_regrows"}
    assert {m["name"] for m in spec.end_to_end} == {"setup_s", "job_s",
                                                    "peak_hbm_gib"}


def test_round_checks_count_a_fused_round_as_off_the_mesh():
    job = load_module("jobs", "terasort-mesh")
    outs = [SimpleNamespace(report=_report()),
            SimpleNamespace(report=_report(syncs=2, regrows=1))]
    mesh = [_span("shuffle-round", path="mesh"),
            _span("shuffle-round", path="mesh", regrow=True)]
    checks = job.round_checks(outs, mesh)
    # a redone round pays one more sync, and that is not over the limit
    assert checks == {"off_mesh_rounds": (0, 0), "syncs_over_rounds": (0, 0),
                      "retraced_udfs": (0, 0)}
    fused = [mesh[0], _span("shuffle-round", path="fused")]
    assert job.round_checks(outs, fused)["off_mesh_rounds"] == (1, 0)
    extra = [SimpleNamespace(report=_report(syncs=3, regrows=1))]
    assert job.round_checks(extra, mesh[:1])["syncs_over_rounds"] == (1, 0)


def test_exchange_regrows_reads_the_report_counter():
    read = load_module("metrics", "exchange_regrows").read
    outs = [SimpleNamespace(report=_report(regrows=r)) for r in (0, 2, 1)]
    assert read(SimpleNamespace(outs=outs, n_jobs=3)) == pytest.approx(1.0)
    # a program without the counter reads nothing
    old = [SimpleNamespace(report=SimpleNamespace(shuffle_rounds=1))]
    assert read(SimpleNamespace(outs=old, n_jobs=1)) is None


def test_collective_s_is_one_chips_all_to_all_time_per_job():
    read = load_module("metrics", "collective_s").read
    ops = {"jit_mesh_round/all_to_all.7": 1.6,        # summed over 4 chips
           "jit_mesh_round/all_to_all.8": 0.4,
           "jit_mesh_round/fusion.3": 9.0,
           "jit_stage_sort_stacked/sort.0": 5.0}
    summary = _summary(ops)
    summary.op_text["jit_mesh_round/all_to_all.7"] = (
        "%all_to_all.7 = u8[4,8,100] all-to-all(%copy.39), channel_id=1")
    summary.op_text["jit_mesh_round/all_to_all.8"] = (
        "%all_to_all.8 = s32[4,1,8] all-to-all(%fusion), channel_id=1")
    summary.op_text["jit_mesh_round/fusion.3"] = (
        "%fusion.3 = u8[8] fusion(%all_to_all.7), kind=kLoop")
    ctx = SimpleNamespace(trace=summary, n_jobs=2)
    assert read(ctx) == pytest.approx((1.6 + 0.4) / 4 / 2)
    assert read(SimpleNamespace(trace=_summary({"m/fusion": 1.0}),
                                n_jobs=1)) is None
    assert read(SimpleNamespace(trace=None, n_jobs=1)) is None


def _roofline_ctx(seconds, n_jobs=3, records=40_000_000):
    return SimpleNamespace(
        trace=_summary({"jit_mesh_round/fusion": seconds,
                        "jit_stage_sort_stacked/sort.0": 7.0}),
        n_jobs=n_jobs, peaks=PEAKS,
        config={"records": records, "record_bytes": 100})


def test_mesh_round_roofline_is_at_most_100_at_the_least_bytes():
    m = load_module("metrics", "mesh_round_roofline")
    least = 3 * m.mesh_round_bytes(40_000_000, 100) / PEAKS["hbm_bytes_per_s"]
    assert m.read(_roofline_ctx(least)) == pytest.approx(100.0)
    assert m.read(_roofline_ctx(4 * least)) == pytest.approx(25.0)
    # a round timed under its least bytes is refused, not capped
    with pytest.raises(ValueError, match="above"):
        m.read(_roofline_ctx(least / 2))
    assert m.read(SimpleNamespace(trace=_summary({"m/fusion": 1.0}),
                                  n_jobs=1, peaks=PEAKS,
                                  config={})) is None


RUN = """
import json, sys
sys.path.insert(0, "src"); sys.path.insert(0, ".")
from bench import run
spec = run.resolve("terasort-gray-mesh4")
res = run.run_cell(spec, 2**31 + 13, 0.5, False,
                   peaks=run.peaks_for("TPU v5 lite"))
line = run.result_line(spec, res, False, {"platform": "cpu", "kind": "x",
                                          "count": 4})
line["per_layer"] = run.read_metrics(spec.per_layer, res["ctx"])
print(json.dumps(line))
"""


def test_the_mesh_cell_runs_on_four_cpu_devices(tmp_path):
    """``terasort-gray-mesh4`` as committed, with its records cut to a
    CPU's size in a copy: every round rides the mesh and every job
    equals the stable key sort."""
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    os.symlink(os.path.join(ROOT, "src"), copy / "src")
    path = copy / "bench/configs/terasort-gray-oct4.json"
    cfg = json.loads(path.read_text())
    cfg.update(records=20000, chunk_bytes=64 * 1024, sample_every=2)
    path.write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", RUN], cwd=copy, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 1, line["checks"]
    assert {k: c["value"] for k, c in line["checks"].items()} == {
        "misplaced_records": 0, "off_mesh_rounds": 0,
        "syncs_over_rounds": 0, "retraced_udfs": 0,
        "under_replicated_chunks": 0}
    assert set(line["metrics"]) == {"setup_s", "job_s"}
    # the device-trace readers need a traced run on a chip
    assert line["per_layer"] == {"exchange_regrows": {"value": 0.0,
                                                      "unit": "count"}}
