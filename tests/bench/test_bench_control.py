"""The controls come out not correct where the program comes out
correct, at a size a test run holds (on the chip they run at the cells'
own sizes through ``bench/control.py``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))  # the checkout root, for ``bench``

import numpy as np

from bench import control, run


def _spec(cell, **tiny):
    spec = run.resolve(cell)
    spec.config.update(tiny)
    return spec


def test_terasort_short_key_control_fails_the_exact_check():
    # 400k random keys hold ~19 pairs that tie on their first 4 bytes
    spec = _spec("terasort-gray-10m", records=400_000,
                 chunk_bytes=4 * 1024 * 1024)
    got = control.read_seed(spec, 2**31 + 3, jobs=1)
    assert got["sound"]["misplaced_records"] == 0
    assert got["control"]["misplaced_records"] > got["limits"][
        "misplaced_records"]


def test_kmeans_control_is_the_reference_update_rule():
    """With full float32 products the control is Lloyd's reference:
    same init, same update rule (empty clusters keep their centroid)."""
    import jax
    import jax.numpy as jnp

    from bench.registry import load_module

    rng = np.random.default_rng(4)
    centres = np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0], [50.0, 50.0]])
    pts = (centres[:3][rng.integers(0, 3, 3000)]
           + rng.standard_normal((3000, 2))).astype(np.float32)
    init = centres.astype(np.float32) + 0.5   # the 4th cluster stays empty
    want = load_module("references", "lloyd").reference(pts, init, 4)

    def exact(a, b):
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)

    got = control.lloyd_control(pts, init, 4, dot=exact)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert np.array_equal(got[3], init[3])
