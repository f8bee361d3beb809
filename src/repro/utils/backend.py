"""Backend-dependent defaults, decided in one place.

* :func:`pallas_interpret` — whether Pallas kernels run in interpret
  mode.  Only the CPU backend interprets; on a TPU every kernel takes its
  Mosaic-compiled lowering.
* :func:`use_compile_cache` — where JAX keeps its persistent compilation
  cache for the entry-point scripts.
"""
from __future__ import annotations

import os

import jax


def pallas_interpret() -> bool:
    """True only on the CPU backend, where Pallas kernels must run in
    interpret mode."""
    return jax.default_backend() == "cpu"


def use_compile_cache(root: str) -> str:
    """Point JAX's persistent compilation cache at a stable directory and
    return it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is changed
    (JAX reads it itself).  Otherwise the cache lives at
    ``<root>/.jax_cache`` — ``root`` is the checkout the calling script
    runs from, so the path is the same on every run (a cache that moves
    never hits)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
