"""Least work of a kernel call, from the shapes of its problem.

What the algorithm must read and write for the real records or points,
not what an implementation moves: padding rows, extra outputs and
re-reads are the implementation's, and lower its roofline share.
"""
from __future__ import annotations


def bucket_scatter_bytes(records: int, key_bytes: int) -> int:
    """The scatter kernel's pass: read each record's key as 32-bit words,
    write its bucket id and its stable rank within the bucket."""
    words = -(-key_bytes // 4)
    return records * (4 * words + 4 + 4)


def kmeans_assign_cost(points: int, dims: int, k: int):
    """``(flops, bytes)`` of one nearest-centroid pass: the x.c products
    (2 flops per multiply-add), each float32 point read once and its
    int32 centroid id written."""
    return 2 * points * k * dims, points * (4 * dims + 4)


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """``(seconds, bound)``: the larger of the compute and memory times
    at the chip's published peaks, and which one it is."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops,
                                                           "compute")
