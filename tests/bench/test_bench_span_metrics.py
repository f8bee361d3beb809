"""The readers of the spans and device modules named inside the
program, on synthetic contexts, and the ``kmeans-hibench-fetch`` cell
run on the CPU from a copy of the benchmark."""
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


def _span(name, t0, t1, **attrs):
    return SimpleNamespace(name=name, t0=t0, t1=t1, attrs=attrs or None,
                           track="control")


def _ctx(spans, n_jobs=2, trace=None):
    return SimpleNamespace(spans=spans, n_jobs=n_jobs, trace=trace)


@pytest.mark.parametrize("metric, span", [("materialise_s", "d2h"),
                                          ("fetch_wait_s", "prefetch-wait")])
def test_span_sum_per_job(metric, span):
    read = load_module("metrics", metric).read
    spans = [_span(span, 0.0, 0.5), _span(span, 1.0, 1.25),
             _span("fetch-chunk", 0.0, 3.0), _span("job:x", 0.0, 9.0)]
    assert read(_ctx(spans)) == pytest.approx(0.375)
    assert read(_ctx(spans[2:])) is None


def test_compile_s_is_the_union_of_nested_compile_spans():
    m = load_module("metrics", "compile_s")
    spans = [_span("jit-trace", 0.0, 1.0, fun_name="stage_assign_masked"),
             _span("jit-trace", 0.2, 0.4, fun_name="add"),  # nested
             _span("jit-lower", 1.0, 1.5, fun_name="jit(f)"),
             _span("jit-compile", 2.0, 3.0, fun_name="jit(f)"),
             _span("jit-cache-load", 2.1, 2.9),               # inside it
             _span("jit-trace", 5.0, 5.5, fun_name="stage_assign_masked"),
             _span("d2h", 0.0, 10.0)]
    assert m.read(_ctx(spans)) == pytest.approx(3.0 / 2)
    # nothing compiled in the window reads 0, not nothing
    assert m.read(_ctx(spans[-1:])) == 0.0


def test_compile_s_reads_nothing_from_a_program_without_compile_spans(
        monkeypatch):
    from repro.core import trace
    monkeypatch.delattr(trace, "COMPILE_SPANS")
    read = load_module("metrics", "compile_s").read
    assert read(_ctx([_span("jit-trace", 0.0, 1.0)])) is None


def test_sort_device_s_sums_the_sort_stage_modules():
    read = load_module("metrics", "sort_device_s").read
    ops = {"jit_stage_sort_pieces/sort.0": 0.3,
           "jit_stage_sort_pieces/fusion": 0.5,
           "jit_stage_sort_stacked/copy.1": 0.2,
           "jit_stage_partition_pieces/fusion": 4.0,
           "jit__scatter_stacked/fusion.2": 9.0}
    text = {k: f"%{k.split('/')[1]} = u8[8] op()" for k in ops}
    summary = TraceSummary(busy_s=1.0, window_s=2.0, op_seconds=ops,
                           op_text=text, idle_by_label={})
    assert read(_ctx([], trace=summary)) == pytest.approx(0.5)
    # the parent's stages run as ``jit__call_stack_pieces``: no reading
    old = {"jit__call_stack_pieces/sort.0": 0.3}
    assert read(_ctx([], trace=TraceSummary(1.0, 2.0, old, {}, {}))) is None
    assert read(_ctx([])) is None


CELL = """
import json, sys
sys.path.insert(0, "src"); sys.path.insert(0, ".")
from bench import run
spec = run.resolve("kmeans-hibench-fetch")
res = run.run_cell(spec, 2**31 + 11, 0.5, False,
                   peaks=run.peaks_for("TPU v5 lite"))
line = run.result_line(spec, res, False, {"platform": "cpu", "kind": "x",
                                          "count": 1})
line["per_layer"] = run.read_metrics(spec.per_layer, res["ctx"])
print(json.dumps(line))
"""


def test_the_fetch_cell_runs_on_a_copy_of_the_benchmark(tmp_path):
    """``kmeans-hibench-fetch`` as committed, with its configuration cut
    to a CPU's size in the copy: every fit reads its chunks through
    Sector, and the span readers the cell lists all read."""
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    os.symlink(os.path.join(ROOT, "src"), copy / "src")
    path = copy / "bench/configs/kmeans-hibench.json"
    cfg = json.loads(path.read_text())
    cfg.update(samples=20000, chunk_bytes=64 * 1024, max_iteration=2,
               limits={"centroid_err": 1e-2})
    path.write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", CELL], cwd=copy, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 1
    # the CPU reports no device memory, so peak_hbm_gib is left out
    assert set(line["metrics"]) == {"setup_s", "job_s"}
    # the device-trace readers need a traced run on a chip
    assert set(line["per_layer"]) == {"materialise_s", "fetch_wait_s",
                                      "compile_s", "fetch_s", "plan_s",
                                      "round_s", "dispatches_per_job"}
    assert all(v["value"] > 0 for v in line["per_layer"].values())
