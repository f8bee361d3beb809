"""HiBench k-means input, from a seed: float32 samples drawn around
``clusters`` Gaussian centres, and ``k`` initial centroids picked from
the samples (Mahout's random seeding)."""
from __future__ import annotations

import numpy as np


def generate(config: dict, seed: int):
    """``(points float32 [samples, dimensions], init float32 [k, dims])``."""
    n, d = config["samples"], config["dimensions"]
    rng = np.random.default_rng(seed % (1 << 64))
    lo, hi = config["centre_range"]
    centres = rng.uniform(lo, hi, size=(config["clusters"], d))
    which = rng.integers(0, config["clusters"], size=n)
    pts = rng.standard_normal((n, d), dtype=np.float32)
    pts *= np.float32(config["cluster_sd"])
    pts += centres.astype(np.float32)[which]
    init = pts[rng.choice(n, size=config["k"], replace=False)].copy()
    return pts, init
