"""Sphere-on-SPMD: the paper's stage/shuffle model on the TPU mesh.

A Sphere stage is an embarrassingly-parallel UDF over the chunks resident on
each node; on the device mesh that is exactly a ``shard_map`` body over the
``data`` axis. The Sphere shuffle is ``lax.all_to_all``. The training step
is a two-stage Sphere job (fwd/bwd UDF -> gradient shuffle -> optimizer
UDF); this module exposes the generic combinators plus the distributed sort
(TeraSort, Table 3) built from them.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

SENTINEL = jnp.uint32(0xFFFFFFFF)


def sphere_map(udf: Callable, mesh: Mesh, axis: str = "data"):
    """Lift a per-shard UDF into a distributed Sphere stage.

    Variadic: every argument (and the result) is sharded over ``axis``
    along its leading dimension — e.g. the engine's fused stage apply
    passes (stacked data, per-slot valid counts)."""
    def stage(*xs):
        fn = shard_map(udf, mesh=mesh,
                       in_specs=tuple(P(axis) for _ in xs),
                       out_specs=P(axis))
        return fn(*xs)
    return stage


def sphere_shuffle(x: jax.Array, bucket_of_shard: Callable, mesh: Mesh,
                   axis: str = "data"):
    """all_to_all exchange: element (i, j) of the per-shard [n, cap] send
    buffer goes to shard i."""
    def body(buf):
        return lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    fn = shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    return fn(x)


def fused_scatter_round(data: jax.Array, n_valids: jax.Array, bounds,
                        *, key_spec, n_buckets: int, n_workers: int,
                        mesh: Mesh, axis: str = "data",
                        interpret: bool | None = None):
    """The engine's fused shuffle round lowered through ``shard_map``:
    per-shard key extraction + ``bucket_partition`` kernel, the exchange
    as ``lax.all_to_all``, and on-device regrouping onto destination
    workers — the multi-device twin of the host-driven
    ``scatter_round_dispatch`` harvest, sharing its record ordering
    contract exactly.

    ``data`` is uint8 [S, rows, width] — the engine's stacked round,
    slots ordered worker-major and sharded contiguously over ``axis``
    (S must divide by the mesh size D) — and ``n_valids`` its int32 [S]
    valid-count vector.  ``n_workers`` must divide by D; worker ``w``
    is resident on device ``w // (n_workers // D)`` and owns buckets
    ``{b : b % n_workers == w}``.

    Returns ``(parts, counts, hist_sb)``:

    * ``parts`` uint8 [n_workers, cap, width] (sharded over ``axis``) —
      worker ``w``'s regrouped partition in slot ``w``: its buckets in
      ascending order, records within a bucket in (slot-major, then
      input) order.  ``cap`` is the static all_to_all capacity
      (D * local rows); tails are junk.
    * ``counts`` int32 [n_workers] — valid prefixes of ``parts``.
    * ``hist_sb`` int32 [S, n_buckets] — the per-slot histogram, the ONE
      metadata array the executor syncs for movement accounting.

    Per-shard work stays a single fused program: the send buffer is
    packed with the one-stable-argsort + section-offset idiom of
    :func:`distributed_sort`, with an int32 bucket-id sidecar (−1 =
    empty) exchanged alongside the rows so the receiver can regroup
    without a second metadata round-trip.
    """
    from repro.core.shuffle import _extract_keys, _kernel_partition

    D = mesh.shape[axis]
    if n_workers % D or data.shape[0] % D:
        raise ValueError(f"fused_scatter_round needs S ({data.shape[0]}) "
                         f"and n_workers ({n_workers}) divisible by the "
                         f"mesh size ({D})")
    wpd = n_workers // D
    rows, width = data.shape[1], data.shape[2]
    bounds_np = bounds

    def body(local, nv):
        s_l = local.shape[0]
        m = s_l * rows
        flat = local.reshape(m, width)
        keys = _extract_keys(flat, key_spec)
        ids, _ = _kernel_partition(keys, bounds_np, n_buckets,
                                   interpret=interpret)
        pos = lax.iota(jnp.int32, m)
        slot = pos // rows
        valid = (pos % rows) < nv[slot]
        hist_sb = jnp.zeros((s_l, n_buckets), jnp.int32) \
            .at[slot, ids].add(valid.astype(jnp.int32))
        # --- sender: rows sorted by (dest device, bucket), stable, then
        # scattered into per-destination sections of the send buffer
        e = (ids % n_workers) // wpd                        # dest device
        skey = jnp.where(valid, e * (n_buckets + 1) + ids,
                         D * (n_buckets + 1))               # invalid last
        order = jnp.argsort(skey)                           # stable
        se, sb, sv = e[order], ids[order], valid[order]
        srows = flat[order]
        sec_count = jnp.sum(
            jnp.where(valid[:, None],
                      jax.nn.one_hot(e, D, dtype=jnp.int32), 0), axis=0)
        sec_start = jnp.cumsum(sec_count) - sec_count
        pos_in = lax.iota(jnp.int32, m) - sec_start[se]
        se_ = jnp.where(sv, se, D)                          # D = dropped
        send = jnp.zeros((D, m, width), jnp.uint8) \
            .at[se_, pos_in].set(srows, mode="drop")
        meta = jnp.full((D, m), -1, jnp.int32) \
            .at[se_, pos_in].set(sb, mode="drop")
        recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=True)
        rmeta = lax.all_to_all(meta, axis, split_axis=0, concat_axis=0,
                               tiled=True)
        # --- receiver: one stable sort by (local worker, bucket) lands
        # every incoming row in its worker's bucket-ordered partition;
        # source sections arrive device-major, so ties keep slot-major
        # input order — the host harvest's ordering contract
        n2 = D * m
        rb = rmeta.reshape(n2)
        rr = recv.reshape(n2, width)
        dev = lax.axis_index(axis)
        rkey = jnp.where(rb >= 0,
                         ((rb % n_workers) - dev * wpd) * (n_buckets + 1)
                         + rb,
                         wpd * (n_buckets + 1))
        rorder = jnp.argsort(rkey)                          # stable
        sr = rr[rorder]
        srb = rb[rorder]
        srv = srb >= 0
        sli = jnp.where(srv, (srb % n_workers) - dev * wpd, wpd)
        sli_c = jnp.clip(sli, 0, wpd - 1)
        wcount = jnp.sum(
            jnp.where(srv[:, None],
                      jax.nn.one_hot(sli_c, wpd, dtype=jnp.int32), 0),
            axis=0)
        wstart = jnp.cumsum(wcount) - wcount
        posw = lax.iota(jnp.int32, n2) - wstart[sli_c]
        out = jnp.zeros((wpd, n2, width), jnp.uint8) \
            .at[sli, posw].set(sr, mode="drop")             # wpd = dropped
        return out, wcount, hist_sb

    # check_vma=False: shard_map has no varying-axes rule for pallas_call
    # (the bucket_partition kernel); every output is explicitly sharded
    # over ``axis`` anyway, so the tracking buys nothing here.
    fn = shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                   out_specs=(P(axis), P(axis), P(axis)), check_vma=False)
    return fn(data, n_valids)


# ---------------------------------------------------------------------------
# Distributed sort (TeraSort) — sample, bucketize, all_to_all, local sort
# ---------------------------------------------------------------------------

def distributed_sort(keys: jax.Array, mesh: Mesh, axis: str = "data",
                     oversample: int = 4):
    """Sort uint32 keys sharded over ``axis``.

    Returns (sorted_padded, valid): per-shard ascending keys padded with
    SENTINEL; ``valid`` counts real keys per shard. Global order =
    concatenation of shards in axis order (asserted in tests).
    """
    n = mesh.shape[axis]

    def body(local):
        local = local.reshape(-1)
        m = local.shape[0]
        cap = 2 * m  # bucket capacity (skew headroom)

        # --- stage 1 (sample UDF): boundary estimation ---------------------
        samp_n = min(n * oversample, m)
        stride = max(m // samp_n, 1)
        samples = jnp.sort(local)[::stride][:samp_n]
        all_samples = lax.all_gather(samples, axis, tiled=True)
        ssorted = jnp.sort(all_samples)
        step = ssorted.shape[0] // n
        bounds = ssorted[step::step][: n - 1]  # [n-1]

        # --- shuffle: bucketize + fixed-capacity all_to_all -----------------
        bucket = jnp.searchsorted(bounds, local, side="right")  # [m]
        order = jnp.argsort(bucket)
        sk = local[order]
        sb = bucket[order]
        # position within bucket via cumulative count
        onehot = jax.nn.one_hot(sb, n, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1)
        pos = jnp.take_along_axis(pos, sb[:, None], axis=1)[:, 0]
        send = jnp.full((n, cap), SENTINEL, jnp.uint32)
        ok = pos < cap
        send = send.at[jnp.where(ok, sb, 0), jnp.where(ok, pos, 0)].set(
            jnp.where(ok, sk, SENTINEL), mode="drop")
        recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=True)  # [n, cap] from each peer

        # --- stage 2 (sort UDF): local sort of owned bucket ------------------
        flat = recv.reshape(-1)
        out = jnp.sort(flat)
        valid = jnp.sum((flat != SENTINEL).astype(jnp.int32))
        return out, valid[None]

    fn = shard_map(body, mesh=mesh, in_specs=P(axis),
                   out_specs=(P(axis), P(axis)))
    return fn(keys)


def barrier_sort(keys: jax.Array, mesh: Mesh, axis: str = "data"):
    """Hadoop-style comparison point: gather everything to every node, sort,
    keep your slice — the no-locality, all-data-moves baseline."""
    n = mesh.shape[axis]

    def body(local):
        local = local.reshape(-1)
        allk = lax.all_gather(local, axis, tiled=True)
        ssorted = jnp.sort(allk)
        m = ssorted.shape[0] // n
        idx = lax.axis_index(axis)
        return lax.dynamic_slice_in_dim(ssorted, idx * m, m)

    fn = shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    return fn(keys)
