"""Partial-manual ``shard_map``: manual over some mesh axes only."""
from __future__ import annotations

from typing import Iterable, Optional

from jax import shard_map


def shard_map_partial(f, *, mesh, in_specs, out_specs,
                      manual_axes: Optional[Iterable[str]] = None):
    """shard_map, optionally manual over only ``manual_axes`` (the rest
    stay automatic)."""
    if manual_axes is None:
        return shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False, axis_names=frozenset(manual_axes))
