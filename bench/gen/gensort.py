"""GraySort input in gensort's record layout, from a seed.

100-byte records: a 10-byte uniformly random key, then gensort's body
(break bytes, the record number as 32 hex digits, filler and end
bytes).  The record number makes every record unique.
"""
from __future__ import annotations

import numpy as np


def generate(config: dict, seed: int) -> np.ndarray:
    """uint8 ``[records, record_bytes]``."""
    n, width, key = (config["records"], config["record_bytes"],
                     config["key_bytes"])
    if (width, key) != (100, 10):
        raise ValueError("gensort records are 100 bytes with 10-byte keys")
    rng = np.random.default_rng(seed % (1 << 64))
    rec = np.empty((n, width), np.uint8)
    rec[:, :key] = rng.integers(0, 256, size=(n, key), dtype=np.uint8)
    rec[:, 10:12] = (0x00, 0x11)
    hexd = np.frombuffer(b"0123456789ABCDEF", np.uint8)
    idx = np.arange(n, dtype=np.uint64)
    rec[:, 12:28] = ord("0")
    for j in range(16):
        rec[:, 28 + j] = hexd[(idx >> np.uint64(4 * (15 - j)))
                              & np.uint64(15)]
    rec[:, 44:48] = (0x88, 0x99, 0xAA, 0xBB)
    rec[:, 48:96] = hexd[idx & np.uint64(15)][:, None]
    rec[:, 96:100] = (0xCC, 0xDD, 0xEE, 0xFF)
    return rec
