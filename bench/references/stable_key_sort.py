"""Plain reference for TeraSort: the records in a stable sort by key."""
from __future__ import annotations

import numpy as np


def key_words(rec: np.ndarray):
    """The 10-byte key as (big-endian uint64, uint16) columns."""
    return (np.ascontiguousarray(rec[:, :8]).view(">u8").ravel(),
            np.ascontiguousarray(rec[:, 8:10]).view(">u2").ravel())


def reference(rec: np.ndarray) -> np.ndarray:
    """uint8 ``[n, 100]`` records sorted stably by their 10-byte key."""
    if rec.shape[1] < 10:
        raise ValueError("records are shorter than the 10-byte key")
    k1, k2 = key_words(rec)
    return rec[np.lexsort((k2, k1))]


def misplaced(got: np.ndarray, want: np.ndarray) -> int:
    """Records of ``want`` not found at their place in ``got`` (every
    record, when the shapes differ)."""
    if got.shape != want.shape:
        return int(max(len(want), len(got)))
    return int(np.any(got != want, axis=1).sum())
