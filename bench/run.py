"""Sphere chip benchmark: one cell, one process, one chip.

Usage, from the root of a checkout on a TPU host::

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The workload's entry in ``BENCHMARK.json`` names a configuration (its
``file``) and a traffic mix (``bench/traffic/<traffic>.json``); the
traffic names a job driver (``bench/jobs/<job>.py``) and the
configuration names its data generator (``bench/gen/<data>.py``) and its
plain reference (``bench/references/<reference>.py``).  Every metric is
read by ``bench/metrics/<metric>.py``.  A new cell or metric is new files
and entries; no file here changes.

A run: set up (data from ``--seed``, Sector upload, one warm-up job that
loads or compiles every program), then whole jobs back to back for
``--seconds``, then the plain reference over every distinct result of
the window.  With ``--trace 1`` the window runs under the JAX profiler
and the per-layer metrics are printed instead of the end-to-end ones.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit).  The exit
code is non-zero, with no such line, when JAX finds no TPU or fewer
chips than the cell asks for; there is no CPU fallback.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace_reduce  # noqa: E402
from bench.registry import load_json, load_module  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- resolving

def resolve(workload: str, bench: dict | None = None) -> SimpleNamespace:
    """The cell's workload entry, configuration, traffic and metrics."""
    if bench is None:
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))

    def mine(kind):
        return [m for m in bench[kind]
                if workload in m.get("workloads", [workload])]

    return SimpleNamespace(workload=cell, config=config, traffic=traffic,
                           end_to_end=mine("end_to_end"),
                           per_layer=mine("per_layer"))


def tpu_devices(chips: int):
    """The TPU devices, or NoChip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def peaks_for(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


# ------------------------------------------------------------- the run

class _Distinct:
    """Distinct job results with how many jobs gave each — equal results
    need one reference comparison, and 1 GB outputs are not all kept."""

    def __init__(self, same):
        self.same = same
        self.results: list = []
        self.counts: list = []

    def add(self, result, counted: bool) -> None:
        for i, r in enumerate(self.results):
            if self.same(r, result):
                self.counts[i] += counted
                return
        self.results.append(result)
        self.counts.append(int(counted))


class _TraceCounter:
    """Counts, while registered, JAX traces and the compiles that missed
    the persistent cache (an XLA compile inside the window)."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.traces = self.compiles = 0

    def duration(self, event, duration, **_kw):
        self.traces += event == self.TRACE

    def event(self, event, **_kw):
        self.compiles += event == self.MISS


def run_cell(spec: SimpleNamespace, seed: int, seconds: float, trace: bool,
             *, t_start: float = T_START, devices=None,
             peaks: dict | None = None) -> dict:
    """Set up, warm, measure, check.  Returns what the result line is
    made of: the metric readers' context, the checks and the failures."""
    import jax
    from repro.core import Tracer

    job = load_module("jobs", spec.traffic["job"])
    tracer = Tracer()
    counter = _TraceCounter()
    trace_dir = tempfile.mkdtemp(prefix="sphere-bench-trace-") \
        if trace else None
    try:
        with tempfile.TemporaryDirectory(prefix="sphere-bench-") as store:
            state = job.set_up(spec.config, spec.traffic, seed, store, tracer)
            try:
                kept = _Distinct(job.same)
                kept.add(job.run_one(state).result, counted=False)
                setup_s = time.perf_counter() - t_start
                obs = _window(job, state, seconds, tracer, kept, counter,
                              trace_dir)
                obs.peak_bytes = _peak_bytes(devices or jax.devices())
            finally:
                job.close(state)
            # the reference runs once the program's state is freed
            t_ref = time.perf_counter()
            checks, wrong = job.check(state, kept.results, obs.outs,
                                      obs.spans)
            ref_s = time.perf_counter() - t_ref
        summary = None
        if trace:
            summary = trace_reduce.summarize(
                trace_reduce.find_xplane(trace_dir), obs.spans,
                anchor=obs.anchor)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    failed = sum(c for c, w in zip(kept.counts, wrong) if w)
    ctx = SimpleNamespace(config=spec.config, traffic=spec.traffic,
                          workload=spec.workload, setup_s=setup_s,
                          trace=summary, peaks=peaks, **vars(obs))
    return {"ctx": ctx, "failed": failed, "checks": checks, "ref_s": ref_s,
            "window_traces": counter.traces,
            "window_compiles": counter.compiles}


def _annotation(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def _window(job, state, seconds, tracer, kept, counter, trace_dir):
    """Whole jobs back to back until ``seconds`` have passed; the window
    ends with the last whole job.  Time spent keeping results (the
    harness's own output check) is left out of the jobs' time."""
    import jax

    traced = trace_dir is not None
    anchor = None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench-anchor"):
            anchor = tracer.instant("bench-anchor", track="bench").t0
    jax.monitoring.register_event_duration_secs_listener(counter.duration)
    jax.monitoring.register_event_listener(counter.event)
    outs, check_s = [], 0.0
    try:
        with _annotation(traced, "bench-window"):
            w0 = tracer.instant("bench-window", track="bench").t0
            t0 = time.perf_counter()
            while True:
                with _annotation(traced, "bench-job"):
                    out = job.run_one(state)
                t_end = time.perf_counter()
                if t_end - t0 >= seconds:
                    w1 = tracer.instant("bench-window-end", track="bench").t0
                    kept.add(out.result, counted=True)
                    out.result = None
                    outs.append(out)
                    break
                with _annotation(traced, "bench-check"):
                    kept.add(out.result, counted=True)
                out.result = None
                outs.append(out)
                check_s += time.perf_counter() - t_end
    finally:
        jax.monitoring.unregister_event_duration_listener(counter.duration)
        jax.monitoring.unregister_event_listener(counter.event)
        if traced:
            jax.profiler.stop_trace()
    spans = [s for s in tracer.snapshot()
             if s.kind == "span" and s.clock == "wall"
             and s.t0 >= w0 and s.t1 <= w1]
    return SimpleNamespace(window_s=t_end - t0, check_s=check_s,
                           n_jobs=len(outs), outs=outs, spans=spans,
                           anchor=anchor, peak_bytes=None)


def _peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_metrics(metric_specs, ctx) -> dict:
    """Each metric from its own reader; one that finds nothing is left out."""
    out = {}
    for m in metric_specs:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _written_bytes():
    """Bytes this process caused to be written to storage, where the
    host reports it (Linux ``/proc/self/io``)."""
    try:
        with open("/proc/self/io") as f:
            fields = dict(line.split(": ") for line in f.read().splitlines())
    except OSError:
        return None
    return int(fields["write_bytes"])


def result_line(spec, res: dict, trace: bool, device: dict) -> dict:
    ctx = res["ctx"]
    metrics = read_metrics(spec.per_layer if trace else spec.end_to_end, ctx)
    ok = res["failed"] == 0 and all(v <= lim
                                    for v, lim in res["checks"].values())
    line = {"correct": bool(ok), "attempted": ctx.n_jobs,
            "failed": res["failed"], "metrics": metrics,
            "device": dict(device, memory_peak_bytes=ctx.peak_bytes),
            "window_traces": res["window_traces"],
            "window_compiles": res["window_compiles"],
            "reference_s": res["ref_s"],
            "host_write_bytes": _written_bytes()}
    if trace and ctx.trace is not None:
        line["device"]["busy_s"] = ctx.trace.busy_s
        line["device"]["window_s"] = ctx.trace.window_s
        line["breakdown"] = ctx.trace.breakdown()
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in res["checks"].items()}
    return line


def configure_jax() -> None:
    """Persistent compile cache at a fixed path in the checkout (or
    ``JAX_COMPILATION_CACHE_DIR``), every program kept, so only a
    checkout's first run compiles."""
    import jax
    from repro.utils.backend import use_compile_cache

    use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = resolve(args.workload)
    try:
        devs = tpu_devices(spec.workload["chips"])
    except NoChip as err:
        print(f"bench/run.py: {err}", file=sys.stderr)
        return 2
    configure_jax()
    kind = devs[0].device_kind
    peaks = peaks_for(kind)
    res = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                   devices=devs, peaks=peaks)
    line = result_line(spec, res, bool(args.trace),
                       {"platform": devs[0].platform, "kind": kind,
                        "count": len(devs)})
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
