"""The trace reduction on a small synthetic trace."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))  # the checkout root, for ``bench``

from types import SimpleNamespace

import pytest

from bench.kernel_cost import (bucket_scatter_bytes, kmeans_assign_cost,
                               least_seconds)
from bench.trace_reduce import (Trace, reduce, roofline_share, union)

MS = 1_000_000  # ns


def _trace():
    # window 0..100 ms; anchor at 0 ms.  Device 0 runs a kernel 10..30
    # overlapping a copy 20..45, and a sort 60..70 that starts before a
    # clipped op -5..5 is counted only from 0.
    dev = [("m/bucket_scatter.1", 10 * MS, 20 * MS),
           ("m/copy.1", 20 * MS, 25 * MS),
           ("m/sort.2", 60 * MS, 10 * MS),
           ("m/early", -5 * MS, 10 * MS)]
    text = {"m/bucket_scatter.1": '%bucket_scatter.1 = s32[8] custom-call('
                                  's32[8] %x), custom_call_target='
                                  '"tpu_custom_call"'}
    host = [("bench-window", 0, 100 * MS),
            ("bench-anchor", 0, 1000),
            ("bench-check", 75 * MS, 20 * MS)]
    return Trace({"/device:TPU:0": dev}, text, host)


def _span(name, t0, t1, track="control"):
    return SimpleNamespace(name=name, t0=t0, t1=t1, track=track)


def test_union_merges_overlaps():
    assert union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]


def test_busy_idle_and_op_sums():
    s = reduce(_trace())
    # busy: 0..5, 10..45, 60..70 = 50 ms of a 100 ms window
    assert s.busy_s == pytest.approx(0.05)
    assert s.window_s == pytest.approx(0.1)
    assert s.idle_share == pytest.approx(0.5)
    assert s.op_seconds["m/early"] == pytest.approx(0.005)
    assert s.kernel_seconds(r"bucket_scatter") == pytest.approx(0.02)
    assert s.kernel_seconds(r"tpu_custom_call") == pytest.approx(0.02)
    assert s.kernel_seconds(r"copy|sort") == pytest.approx(0.035)
    assert s.kernel_seconds(r"kmeans_assign") is None


def test_idle_time_labelled_by_innermost_host_activity():
    # tracer clock = trace clock + 7 s (anchor instant at 7.0)
    spans = [_span("job:terasort", 7.0, 7.074),
             _span("shuffle-round", 7.040, 7.060, track="shuffle"),
             _span("fetch-chunk", 7.0, 7.1, track="prefetch")]
    s = reduce(_trace(), spans, anchor=7.0)
    # idle 5..10 and 70..74 inside the job, 45..60 in the round,
    # 75..95 the harness's check, 74..75 and 95..100 the prefetch thread
    assert s.idle_by_label == pytest.approx(
        {"job:terasort": 0.009, "shuffle-round": 0.015,
         "bench-check": 0.02, "fetch-chunk": 0.006})
    assert sum(s.idle_by_label.values()) == pytest.approx(
        s.window_s - s.busy_s)
    bd = s.breakdown(top=2)
    assert bd["device_ops"][0] == ["m/copy.1", pytest.approx(0.025)]
    assert [k for k, _ in bd["idle_gaps"]] == ["bench-check",
                                               "shuffle-round"]


def test_python_events_label_and_the_rest_is_untraced():
    t = _trace()
    t.python = [("np.asarray(jax.Array)", 50 * MS, 5 * MS)]
    s = reduce(t)                       # no anchor: no tracer spans
    assert s.idle_by_label == pytest.approx(
        {"np.asarray(jax.Array)": 0.005, "bench-check": 0.02,
         "untraced": 0.025})


def test_window_without_device_work_is_refused():
    t = _trace()
    t.devices["/device:TPU:0"] = [("x", 200 * MS, MS)]
    with pytest.raises(ValueError, match="no device operation"):
        reduce(t)


def test_roofline_arithmetic_and_ceiling():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 10M records, 10-byte keys: 3 words read, id and rank written
    assert bucket_scatter_bytes(10_000_000, 10) == 10_000_000 * 20
    flops, nbytes = kmeans_assign_cost(1000, 20, 10)
    assert (flops, nbytes) == (2 * 1000 * 10 * 20, 1000 * 84)
    least, bound = least_seconds(flops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(84_000 / 819e9)
    assert least_seconds(1e12, 1.0, peaks)[1] == "compute"
    assert roofline_share(1.0, 4.0) == pytest.approx(25.0)
    assert roofline_share(1.04, 1.0) == pytest.approx(104.0)
    with pytest.raises(ValueError, match="above 105"):
        roofline_share(1.06, 1.0)
