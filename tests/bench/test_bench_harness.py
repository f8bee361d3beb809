"""The harness finds every cell's files by name, runs a cell added as
data alone, and refuses to run without a TPU."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))  # the checkout root, for ``bench``

import json
import re
import shutil
import subprocess

import pytest

from bench import run
from bench.registry import ROOT, load_module

BENCH = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_workload_resolves_to_its_files(cell):
    spec = run.resolve(cell)
    assert spec.workload["name"] == cell
    job = load_module("jobs", spec.traffic["job"])
    for fn in ("set_up", "run_one", "same", "close", "check"):
        assert callable(getattr(job, fn))
    assert callable(load_module("gen", spec.config["data"]).generate)
    assert callable(load_module("references",
                                spec.config["reference"]).reference)
    for m in spec.end_to_end + spec.per_layer:
        assert callable(load_module("metrics", m["name"]).read)
    assert {m["name"] for m in spec.end_to_end} >= {"setup_s", "job_s"}
    assert spec.per_layer


def test_benchmark_json_follows_the_contract():
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in BENCH["paths"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = run.load_json(os.path.join(ROOT, c["file"]))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in layers
        assert set(m["workloads"]) <= set(CELLS)
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


def test_unknown_device_kind_is_an_error():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        run.peaks_for("TPU v99")


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert "{" not in p.stdout


NEW_CELL = """
import json, sys
sys.path.insert(0, "src"); sys.path.insert(0, ".")
from bench import run
spec = run.resolve("kmeans-tiny-fetch")
res = run.run_cell(spec, 2**31 + 5, 0.5, False,
                   peaks=run.peaks_for("TPU v5 lite"))
line = run.result_line(spec, res, False, {"platform": "cpu", "kind": "x",
                                          "count": 1})
line["per_layer"] = run.read_metrics(spec.per_layer, res["ctx"])
print(json.dumps(line))
"""


def test_a_cell_added_as_data_runs_with_no_code_edit(tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix and
    a workload entry — files and entries only — and runs that cell."""
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), copy / "src")
    cfg = run.load_json(os.path.join(ROOT, "bench/configs/kmeans-hibench.json"))
    cfg.update(name="kmeans-tiny", samples=20000, chunk_bytes=64 * 1024,
               max_iteration=2, limits={"centroid_err": 1e-2})
    (copy / "bench/configs/kmeans-tiny.json").write_text(json.dumps(cfg))
    (copy / "bench/traffic/kmeans-per-job.json").write_text(json.dumps(
        {"job": "kmeans", "session": "per_job"}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "kmeans-tiny", "source": "test",
                             "file": "bench/configs/kmeans-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "kmeans-tiny-fetch",
                               "config": "kmeans-tiny",
                               "traffic": "kmeans-per-job", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "kmeans-hibench-session" in m.get("workloads", ()):
            m["workloads"].append("kmeans-tiny-fetch")
    fetch = next(m for m in bench["per_layer"] if m["name"] == "fetch_s")
    fetch["workloads"].append("kmeans-tiny-fetch")
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    before = {p: p.read_bytes() for p in (copy / "bench").rglob("*.py")}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", NEW_CELL], cwd=copy, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 1
    # the CPU reports no device memory, so peak_hbm_gib is left out
    assert set(line["metrics"]) == {"setup_s", "job_s", "iter_p95_s"}
    # every job opens its own session, so every job fetches its chunks
    assert line["per_layer"]["fetch_s"]["value"] > 0
    assert {p: p.read_bytes() for p in before} == before
