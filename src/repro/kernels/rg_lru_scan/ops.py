"""jit'd wrapper with backend dispatch."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rg_lru_scan.kernel import lru_scan
from repro.utils.backend import pallas_interpret


@partial(jax.jit, static_argnames=("block_w", "interpret"))
def rg_lru_scan(a, b, h0, *, block_w: int = 512,
                interpret: bool | None = None):
    if interpret is None:
        interpret = pallas_interpret()
    return lru_scan(a, b, h0, block_w=block_w, interpret=interpret)
