"""Each plain reference against the program's bytes backend (the
per-record reference data plane) at a tiny size."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))  # the checkout root, for ``bench``

import numpy as np

from bench import cloud
from bench.registry import load_json, load_module, ROOT


def _config(name, **tiny):
    cfg = load_json(os.path.join(ROOT, "bench", "configs", name + ".json"))
    cfg.update(tiny)
    return cfg


def test_stable_key_sort_matches_the_bytes_backend(tmp_path):
    from repro.core import SphereEngine, SphereJob
    from repro.core.shuffle import sample_boundaries, terasort_stages

    cfg = _config("terasort-gray", records=3000, chunk_bytes=32 * 1024)
    rec = load_module("gen", "gensort").generate(cfg, 2**31 + 7)
    # duplicate keys: stability decides their order
    rec[1::50, :10] = rec[::50, :10][:len(rec[1::50])]
    master, client = cloud.make_cloud(str(tmp_path), cfg)
    client.upload("f", rec.tobytes(), replication=cfg["replication"])
    bounds = sample_boundaries([r.tobytes() for r in rec[::10]],
                               cfg["buckets"], key_bytes=10)
    job = SphereJob("ts", "f", terasort_stages(bounds, "bytes",
                                               cfg["buckets"]),
                    record_size=100, backend="bytes")
    outputs, _ = SphereEngine(master, client).run(job)
    got = np.frombuffer(b"".join(outputs), np.uint8).reshape(-1, 100)
    ref = load_module("references", "stable_key_sort")
    want = ref.reference(rec)
    assert ref.misplaced(got, want) == 0
    assert ref.misplaced(got[::-1], want) > 0
    assert ref.misplaced(got[:-1], want) == len(want)


def test_lloyd_matches_the_bytes_backend(tmp_path):
    from repro.core import SphereEngine
    from repro.core.kmeans import encode_points, kmeans_sphere

    cfg = _config("kmeans-hibench", samples=4000, chunk_bytes=16 * 1024)
    pts, init = load_module("gen", "gaussian_clusters").generate(cfg, 11)
    assert pts.shape == (4000, 20) and init.shape == (10, 20)
    master, client = cloud.make_cloud(str(tmp_path), cfg)
    client.upload("p", encode_points(pts), replication=cfg["replication"])
    got, _ = kmeans_sphere(SphereEngine(master, client), "p", dim=20, k=10,
                           iters=3, backend="bytes", init=init)
    want = load_module("references", "lloyd").reference(pts, init, 3)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
    assert not np.allclose(want, init)


def test_generators_follow_the_seed():
    cfg = _config("terasort-gray", records=500)
    gen = load_module("gen", "gensort").generate
    a, b = gen(cfg, 2**32 + 1), gen(cfg, 2**32 + 1)
    assert np.array_equal(a, b) and not np.array_equal(a, gen(cfg, 1))
    assert len({r.tobytes() for r in a}) == 500
    kc = _config("kmeans-hibench", samples=500)
    p1, i1 = load_module("gen", "gaussian_clusters").generate(kc, 5)
    p2, i2 = load_module("gen", "gaussian_clusters").generate(kc, 5)
    assert np.array_equal(p1, p2) and np.array_equal(i1, i2)
    assert p1.dtype == np.float32
