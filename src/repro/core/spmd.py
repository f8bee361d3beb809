"""Sphere-on-SPMD: the paper's stage/shuffle model on the TPU mesh.

A Sphere stage is an embarrassingly-parallel UDF over the chunks resident on
each node; on the device mesh that is exactly a ``shard_map`` body over the
``data`` axis. The Sphere shuffle is ``lax.all_to_all``. The training step
is a two-stage Sphere job (fwd/bwd UDF -> gradient shuffle -> optimizer
UDF); this module exposes the generic combinators plus the distributed sort
(TeraSort, Table 3) built from them.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.records import _quarter_rows

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


def data_mesh(n: int | None = None) -> Mesh:
    """The engine's mesh: one ``data`` axis over the first ``n`` devices
    (all of them when ``n`` is None), one Sphere node per chip."""
    devs = jax.devices()
    n = len(devs) if n is None else n
    if not 1 <= n <= len(devs):
        raise ValueError(f"a {n}-device data mesh needs {n} devices; "
                         f"JAX sees {len(devs)}")
    return Mesh(np.array(devs[:n]), ("data",))


# the least exchange capacity, in rows: small rounds share one program
_CAP_FLOOR = 128


def _bucket_dests(n_buckets: int, n_workers: int, d: int) -> np.ndarray:
    """Destination device of each bucket: bucket ``b`` belongs to worker
    ``b % n_workers``, resident on device ``worker // (n_workers // d)``."""
    return (np.arange(n_buckets) % n_workers) // (n_workers // d)


def mesh_round_capacity(n_valid, rows: int, *, n_buckets: int,
                        n_workers: int, d: int):
    """``(cap, wcap)`` for :func:`fused_scatter_round`, from the host's
    per-slot valid counts of a ``[S, rows, width]`` round.  ``cap`` (rows
    per destination section) is 1.25 times the fullest device's rows
    times the busiest destination's share of the buckets; ``wcap`` (rows
    per worker partition) 1.25 times the busiest worker's share of every
    row.  Both are rounded up on the quarter-octave ladder, so record
    counts share programs, and capped at the whole block: a device's
    ``m = S / d * rows`` rows and the round's ``d * m``."""
    nv = np.asarray(n_valid, np.int64)
    m = nv.size // d * rows
    dev_share = np.bincount(_bucket_dests(n_buckets, n_workers, d),
                            minlength=d).max() / n_buckets
    w_share = np.bincount(np.arange(n_buckets) % n_workers,
                          minlength=n_workers).max() / n_buckets
    cap = _quarter_rows(math.ceil(1.25 * nv.reshape(d, -1).sum(1).max()
                                  * dev_share), _CAP_FLOOR)
    wcap = _quarter_rows(math.ceil(1.25 * nv.sum() * w_share), _CAP_FLOOR)
    return min(cap, m), min(wcap, d * m)


def mesh_round_regrow(hist_sb, rows: int, cap: int, wcap: int, *,
                      n_workers: int, d: int):
    """The ``(cap, wcap)`` a round that overflowed is redone at: double
    each, or what the round's synced per-slot histogram says it needs if
    that is more, capped at the whole block, where nothing can
    overflow.  The histogram counts every record before the exchange,
    so one redo always fits."""
    hist = np.asarray(hist_sb, np.int64)
    s, nb = hist.shape
    m = s // d * rows
    to_dev = _bucket_dests(nb, n_workers, d)[:, None] == np.arange(d)
    to_worker = np.arange(nb)[:, None] % n_workers == np.arange(n_workers)
    sections = hist.reshape(d, s // d, nb).sum(1) @ to_dev  # [src, dst]
    workers = hist.sum(0) @ to_worker
    cap = min(m, max(2 * cap, _quarter_rows(int(sections.max()),
                                            _CAP_FLOOR)))
    wcap = min(d * m, max(2 * wcap, _quarter_rows(int(workers.max()),
                                                  _CAP_FLOOR)))
    return cap, wcap


def fused_scatter_round(data: jax.Array, n_valids: jax.Array, bounds,
                        *, key_spec, n_buckets: int, n_workers: int,
                        mesh: Mesh, axis: str = "data",
                        cap: int | None = None, wcap: int | None = None,
                        interpret: bool | None = None):
    """The engine's fused shuffle round lowered through ``shard_map``:
    per-shard key extraction + ``bucket_partition`` kernel, the exchange
    as ``lax.all_to_all``, and on-device regrouping onto destination
    workers — the multi-device twin of the host-driven
    ``scatter_round_dispatch`` harvest, sharing its record ordering
    contract exactly.  One program, compiled as ``jit_mesh_round``.

    ``data`` is uint8 [S, rows, width] — the engine's stacked round,
    slots ordered worker-major and sharded contiguously over ``axis``
    (S must divide by the mesh size D) — and ``n_valids`` its int32 [S]
    valid-count vector.  ``n_workers`` must divide by D; worker ``w``
    is resident on device ``w // (n_workers // D)`` and owns buckets
    ``{b : b % n_workers == w}``.

    ``cap`` is the rows a device sends to each destination device (the
    send and receive buffers are ``[D, cap, width]`` a device) and
    ``wcap`` the rows of each worker's partition; they default to the
    whole block (``m = S / D * rows`` and ``D * m``), which nothing can
    overflow.  :func:`mesh_round_capacity` sizes them to the round.

    Returns ``(parts, counts, hist_sb, overflow)``:

    * ``parts`` uint8 [n_workers, wcap, width] (sharded over ``axis``) —
      worker ``w``'s regrouped partition in slot ``w``: its buckets in
      ascending order, records within a bucket in (slot-major, then
      input) order; tails are junk.
    * ``counts`` int32 [n_workers] — valid prefixes of ``parts``.
    * ``hist_sb`` int32 [S, n_buckets] — the per-slot histogram, the
      metadata the executor syncs for movement accounting.
    * ``overflow`` int32 [D] — 1 on a device that had more rows for a
      section than ``cap`` or for a worker than ``wcap``: records were
      left out, and the round must be redone larger
      (:func:`mesh_round_regrow`).
    """
    D = mesh.shape[axis]
    if n_workers % D or data.shape[0] % D:
        raise ValueError(f"fused_scatter_round needs S ({data.shape[0]}) "
                         f"and n_workers ({n_workers}) divisible by the "
                         f"mesh size ({D})")
    m = data.shape[0] // D * data.shape[1]
    return mesh_round(data, n_valids, bounds, key_spec=key_spec,
                      n_buckets=n_buckets, n_workers=n_workers, mesh=mesh,
                      axis=axis, cap=m if cap is None else cap,
                      wcap=D * m if wcap is None else wcap,
                      interpret=interpret)


@partial(jax.jit, static_argnames=("key_spec", "n_buckets", "n_workers",
                                   "mesh", "axis", "cap", "wcap",
                                   "interpret"))
def mesh_round(data, n_valids, bounds, *, key_spec, n_buckets: int,
               n_workers: int, mesh: Mesh, axis: str, cap: int, wcap: int,
               interpret):
    """:func:`fused_scatter_round`'s program.  Rows move by gathers
    only: each send section and each worker partition is a window of
    one stable argsort, taken at its static capacity."""
    from repro.core.shuffle import _extract_keys, _kernel_partition

    D = mesh.shape[axis]
    wpd = n_workers // D
    rows, width = data.shape[1], data.shape[2]
    to_dev = jnp.asarray(_bucket_dests(n_buckets, n_workers, D)[None, :]
                         == np.arange(D)[:, None], jnp.int32)  # [D, nb]

    def body(local, nv, bnds):
        s_l = local.shape[0]
        m = s_l * rows
        flat = local.reshape(m, width)
        ids, _ = _kernel_partition(_extract_keys(flat, key_spec), bnds,
                                   n_buckets, interpret=interpret)
        pos = lax.iota(jnp.int32, m)
        valid = (pos % rows) < nv[pos // rows]
        bid = jnp.where(valid, ids, n_buckets)      # n_buckets: no record
        hist_sb = jnp.sum(bid.reshape(s_l, rows, 1) == jnp.arange(n_buckets),
                          axis=1, dtype=jnp.int32)
        # --- sender: rows sorted by (dest device, bucket), stable;
        # section e of the send buffer is that order's rows
        # [start_e, start_e + cap)
        dest = jnp.where(valid, (ids % n_workers) // wpd, D)
        order = jnp.argsort(dest * (n_buckets + 1) + bid, stable=True)
        sec = jnp.sum(to_dev * jnp.sum(hist_sb, axis=0), axis=1)
        start = jnp.cumsum(sec) - sec
        j = lax.iota(jnp.int32, cap)
        src = order[jnp.minimum(start[:, None] + j, m - 1)]   # [D, cap]
        send = flat[src]
        meta = jnp.where(j < jnp.minimum(sec, cap)[:, None], bid[src], -1)
        recv = lax.all_to_all(send, axis, 0, 0, tiled=True)
        rmeta = lax.all_to_all(meta, axis, 0, 0, tiled=True)
        # --- receiver: one stable sort by (local worker, bucket) lands
        # every incoming row in its worker's bucket-ordered partition;
        # source sections arrive device-major, so ties keep slot-major
        # input order — the host harvest's ordering contract
        n2 = D * cap
        rb = rmeta.reshape(n2)
        lw = jnp.where(rb >= 0, rb % n_workers - lax.axis_index(axis) * wpd,
                       wpd)                         # wpd: no record
        rorder = jnp.argsort(lw * (n_buckets + 1)
                             + jnp.where(rb >= 0, rb, n_buckets),
                             stable=True)
        wcount = jnp.sum(lw[:, None] == jnp.arange(wpd), axis=0,
                         dtype=jnp.int32)
        wstart = jnp.cumsum(wcount) - wcount
        k = lax.iota(jnp.int32, wcap)
        out = recv.reshape(n2, width)[
            rorder[jnp.minimum(wstart[:, None] + k, n2 - 1)]]
        over = (jnp.max(sec) > cap) | (jnp.max(wcount) > wcap)
        return (out, jnp.minimum(wcount, wcap), hist_sb,
                over.astype(jnp.int32)[None])

    # check_vma=False: shard_map has no varying-axes rule for pallas_call
    # (the bucket_partition kernel); every output is explicitly sharded
    # over ``axis`` anyway, so the tracking buys nothing here.
    fn = shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis), P()),
                   out_specs=(P(axis),) * 4, check_vma=False)
    return fn(data, n_valids, bounds)


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
