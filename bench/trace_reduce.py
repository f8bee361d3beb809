"""From a JAX profiler trace to device busy time, op time and idle gaps.

The trace is the ``.xplane.pb`` the profiler writes.  Device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation run on that chip, named by its HLO text, and the ``XLA
Modules`` line the program each op ran in.  The harness brackets its
window with a ``bench-window`` annotation and records one
``bench-anchor`` annotation together with a tracer instant, which puts
the program's tracer spans (``time.perf_counter`` based) on the
profiler's clock, so idle time can be labelled with what the host was
doing: the innermost tracer span, Python-level trace event (such as
``np.asarray(jax.Array)``, a device-to-host copy) or harness annotation
covering it.

Busy time is the union of a chip's operation intervals inside the
window, averaged over the chips that ran anything; an operation's time
is the sum of its events' durations inside the window.  A roofline share
above 105% is refused: its operations or bytes were counted too high,
or its time misses part of the work.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench-"           # the harness's own annotations
PYTHON_LINE = "python"           # host lines of Python threads
ROOFLINE_CEILING = 105.0         # percent

# (name, start_ns, duration_ns)
Event = Tuple[str, float, float]


@dataclass
class Trace:
    """The parts of a trace the reduction reads.  Device ops are named
    ``<module>/<op>``; ``op_text`` keeps each op's full HLO text."""
    devices: Dict[str, List[Event]]
    op_text: Dict[str, str]
    host: List[Event]            # bench-* annotations
    python: List[Event] = field(default_factory=list)  # host TraceMes


@dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    op_seconds: Dict[str, float]
    op_text: Dict[str, str]
    idle_by_label: Dict[str, float]
    devices: int = 1

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, pattern: str) -> Optional[float]:
        """Summed device time of the ops whose name or HLO text matches
        ``pattern``; None when none does."""
        rx = re.compile(pattern)
        hits = [s for k, s in self.op_seconds.items()
                if rx.search(k) or rx.search(self.op_text.get(k, ""))]
        return sum(hits) if hits else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_label.items(),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _module_name(name: str) -> str:
    """``jit_f(123)`` -> ``jit_f``."""
    return name.split("(", 1)[0]


def _op_name(text: str) -> str:
    """``%fusion.2 = u8[...] fusion(...)`` -> ``fusion.2``."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    op_text: Dict[str, str] = {}
    host: List[Event] = []
    python: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           _module_name(e.name))
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ()))
            starts = [m[0] for m in mods]
            evs: List[Event] = []
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] \
                    else "?"
                key = f"{mod}/{_op_name(e.name)}"
                op_text.setdefault(key, e.name)
                evs.append((key, e.start_ns, e.duration_ns))
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                py = line.name.startswith(PYTHON_LINE)
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, e.start_ns, e.duration_ns))
                    elif py:
                        python.append((e.name, e.start_ns, e.duration_ns))
    return Trace(devices, op_text, host, python)


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _one(host: List[Event], name: str) -> Event:
    hits = [h for h in host if h[0] == name]
    if len(hits) != 1:
        raise ValueError(f"expected one {name!r} annotation, found "
                         f"{len(hits)}")
    return hits[0]


def reduce(trace: Trace, spans=(), anchor: Optional[float] = None
           ) -> TraceSummary:
    """Busy time, op time and labelled idle time inside the window.

    ``spans`` are tracer spans (``name``, ``track``, ``t0``, ``t1`` on the
    tracer's clock); ``anchor`` is the tracer time of the
    ``bench-anchor`` annotation's start."""
    _, w0, wdur = _one(trace.host, "bench-window")
    w1 = w0 + wdur
    busy, ops = [], {}
    gaps: List[Tuple[float, float]] = []
    for evs in trace.devices.values():
        clipped = []
        for key, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            ops[key] = ops.get(key, 0.0) + (b - a) / 1e9
        if not clipped:
            continue
        merged = union(clipped)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        edge = w0
        for a, b in merged:
            if a > edge:
                gaps.append((edge, a))
            edge = b
        if w1 > edge:
            gaps.append((edge, w1))
    if not busy:
        raise ValueError("no device operation ran inside the window")
    marks = []                   # (start_ns, end_ns, rank, name)
    if anchor is not None:
        a_ns = _one(trace.host, "bench-anchor")[1]
        for sp in spans:
            rank = 2 if sp.track == "prefetch" else 0
            marks.append((round(a_ns + (sp.t0 - anchor) * 1e9),
                          round(a_ns + (sp.t1 - anchor) * 1e9), rank,
                          sp.name))
    for name, s, d in trace.python:
        marks.append((s, s + d, 0, name))
    for name, s, d in trace.host:
        if name in ("bench-job", "bench-check"):
            marks.append((s, s + d, 1, name))
    idle: Dict[str, float] = {}
    for label, a, b in _overlay(sorted(gaps), _segments(marks)):
        idle[label] = idle.get(label, 0.0) + (b - a) / 1e9
    return TraceSummary(busy_s=sum(busy) / len(busy), window_s=wdur / 1e9,
                        op_seconds=ops, op_text=dict(trace.op_text),
                        idle_by_label=idle, devices=len(busy))


def _segments(marks):
    """The host timeline as ``(start, end, label)`` pieces: in each, the
    innermost activity — lowest rank (program spans and Python events,
    then harness annotations, then the prefetch thread), then shortest."""
    edges = sorted({t for m in marks for t in m[:2]})
    by_start = sorted(marks)
    out, active, nxt = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while nxt < len(by_start) and by_start[nxt][0] <= a:
            active.append(by_start[nxt])
            nxt += 1
        active = [m for m in active if m[1] > a]
        if active:
            best = min(active, key=lambda m: (m[2], m[1] - m[0]))
            out.append((a, b, best[3]))
    return out


def _overlay(gaps, segments):
    """Each gap cut by the labelled host segments; time no segment
    covers is ``untraced``."""
    out, j = [], 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        t, k = a, j
        while t < b:
            if k < len(segments) and segments[k][0] < b:
                s0, s1, label = segments[k]
                if s0 > t:
                    out.append(("untraced", t, s0))
                    t = s0
                else:
                    end = min(s1, b)
                    out.append((label, t, end))
                    t = end
                    k += 1
            else:
                out.append(("untraced", t, b))
                t = b
    return out


def summarize(path: str, spans=(), anchor: Optional[float] = None
              ) -> TraceSummary:
    return reduce(read_xplane(path), spans, anchor)


def roofline_share(least_seconds: float, kernel_seconds: float) -> float:
    """Percent of the roofline a kernel reached: the least time its work
    needs on the chip over the time it took."""
    if kernel_seconds <= 0:
        raise ValueError("kernel time must be positive")
    share = 100.0 * least_seconds / kernel_seconds
    if share > ROOFLINE_CEILING:
        raise ValueError(
            f"roofline share {share:.1f}% is above {ROOFLINE_CEILING}%: "
            f"the work is counted too high or the time misses part of it")
    return share
