"""The main path's kernels compiled for a described (not attached) TPU v5e.

Interpret mode (every other kernel test) cannot see what the Mosaic
compiler refuses: tiling-illegal block shapes, unsupported reshapes and
scans, VMEM overflow.  These tests lower and compile each kernel of the
TeraSort / k-means path at its real widths — 100-byte records, 3- and
4-word keys, 64 buckets, 2048-row blocks, 8-dim points — for a v5e, plus
the two callers that wrap them on the chip: the vmapped stacked round and
the ``shard_map`` + ``all_to_all`` mesh round on a 2x2 host.  Nothing
runs; a compile that passes is not a chip run.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and under several test workers
only the worker that runs this file does.
"""
import os
import sys
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.executor import _TracedUDF, _slab
from repro.core.kmeans import make_kmeans_stages
from repro.core.shuffle import _scatter_stacked
from repro.core.spmd import (fused_scatter_round, mesh_round,
                             mesh_round_capacity)
from repro.kernels.bucket_partition.kernel import (bucket_dest_call,
                                                   bucket_partition_call,
                                                   bucket_scatter_call)
from repro.kernels.bucket_partition.ops import ACCEL_BLOCK_N
from repro.kernels.kmeans_assign.kernel import kmeans_assign_call

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # the checkout root, for ``bench``

ROWS = 1 << 16                  # rows per compiled batch (scale, not width)
RECORD = 100                    # TeraSort record bytes
N_OUT = 64                      # buckets


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-device compile lands in the persistent cache but
        # cannot be read back without a chip: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sorted_bounds(n: int, k: int) -> np.ndarray:
    b = np.random.default_rng(0).integers(0, 2**32, (n, k), dtype=np.uint32)
    return b[np.lexsort(b.T[::-1])]


# 100 rows: a batch under one 128-lane tile, a single-block kernel
@pytest.mark.parametrize("rows", [100, ROWS])
@pytest.mark.parametrize("k", [3, 4])
@pytest.mark.parametrize("kernel", ["dest", "scatter", "partition"])
def test_bucket_kernel_compiles(one_chip, kernel, k, rows):
    keys = one_chip((rows, k), jnp.uint32)
    bounds = one_chip((N_OUT - 1, k), jnp.uint32)
    if kernel == "partition":
        _compile(partial(bucket_partition_call, n_buckets=N_OUT,
                         block_n=ACCEL_BLOCK_N), keys, bounds)
    elif kernel == "dest":
        _compile(partial(bucket_dest_call, n_out=N_OUT,
                         block_n=ACCEL_BLOCK_N),
                 keys, bounds, one_chip((), jnp.int32))
    else:
        _compile(partial(bucket_scatter_call, n_out=N_OUT,
                         block_n=ACCEL_BLOCK_N),
                 one_chip((rows, RECORD), jnp.uint8), keys, bounds,
                 one_chip((), jnp.int32))


def test_kmeans_assign_compiles(one_chip):
    _compile(partial(kmeans_assign_call, block_n=1024),
             one_chip((ROWS, 8), jnp.float32), one_chip((10, 8), jnp.float32))


def test_output_slab_compiles(one_chip):
    """The copy-out's pack at TeraSort's sort-stage output, six slots of
    1835008 rows: a uint32 [358400, 128] slab, no intermediate padded to
    128 lanes (a bitcast through a minor axis of 4 asks 23 GB), and a
    program of a few MB (its code stays in HBM; the gathers of jnp's
    step indexing made it 56 MB)."""
    compiled = jax.jit(_slab).lower(
        one_chip((6, 1835008, RECORD), jnp.uint8),
        one_chip((), jnp.int32)).compile()
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((358400, 128), jnp.uint32)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes * 2
    assert mem.generated_code_size_in_bytes < 4 << 20


def test_stacked_round_compiles(one_chip):
    """The compiled-backend fused round: key extraction + bucket_scatter
    vmapped over the slot axis, at the TeraSort key spec."""
    _compile(partial(_scatter_stacked, n_buckets=6,
                     key_spec=("range", 10, 3, None), block_n=None,
                     interpret=False),
             one_chip((4, ROWS // 4, RECORD), jnp.uint8),
             one_chip((5, 3), jnp.uint32), one_chip((4,), jnp.int32))


def test_mesh_round_compiles(topo):
    """The mesh round on the 2x2 host: per-device bucket_partition kernel
    and the all_to_all exchange, sharded over a 4-device data axis."""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    fn = partial(fused_scatter_round, bounds=_sorted_bounds(7, 3),
                 key_spec=("range", 10, 3, None), n_buckets=8, n_workers=8,
                 mesh=mesh, interpret=False)
    compiled = _compile(
        fn, jax.ShapeDtypeStruct((8, ROWS // 8, RECORD), jnp.uint8,
                                 sharding=sharded),
        jax.ShapeDtypeStruct((8,), jnp.int32, sharding=sharded))
    assert "all-to-all" in compiled.as_text()


def test_mesh_round_fits_a_chip_at_the_mesh_cells_share(topo):
    """The share-sized mesh round at ``terasort-gray-mesh4``'s shape: 60
    stage-0 slots of 786432 rows (15 a chip, 64 MiB chunks of 671088
    records), 8 workers and 8 buckets on the 2x2 host, the exchange
    sized by ``mesh_round_capacity``.  What a chip holds for it fits the
    v5e's 16 GB (sized from the whole block, its send, receive and
    regroup buffers alone came to about 19 GB); its module is
    ``jit_mesh_round`` with the collective's op in it, as the device
    trace readers of the cell name them."""
    import re
    from bench.registry import load_module
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    slots, rows = 60, 786432
    cap, wcap = mesh_round_capacity(np.full(slots, 671088), rows,
                                    n_buckets=8, n_workers=8, d=4)
    compiled = mesh_round.lower(
        jax.ShapeDtypeStruct((slots, rows, RECORD), jnp.uint8,
                             sharding=sharded),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=sharded),
        jax.ShapeDtypeStruct((7, 3), jnp.uint32,
                             sharding=NamedSharding(mesh, P())),
        key_spec=("range", 10, 3, None), n_buckets=8, n_workers=8,
        mesh=mesh, axis="data", cap=cap, wcap=wcap,
        interpret=False).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
             - mem.alias_size_in_bytes)
    assert total < 16e9, total
    module, ops_text = _module_and_ops(compiled)
    assert module == "jit_mesh_round"
    assert re.match(load_module("metrics", "mesh_round_roofline").MODULE,
                    f"{module}/x")
    rx = re.compile(load_module("metrics", "collective_s").OP)
    assert len([op for op in ops_text if rx.search(op)]) == 2


def _module_and_ops(compiled):
    """The compiled module's name and its instructions' HLO text, as the
    device trace names them (``%op = ...``)."""
    lines = compiled.as_text().splitlines()
    module = lines[0].split()[1].rstrip(",")
    ops = [ln.strip().removeprefix("ROOT ") for ln in lines]
    return module, [op for op in ops if op.startswith("%")]


def _kernel_regex(metric):
    from bench.registry import load_module
    return load_module("metrics", metric).KERNEL


def test_assign_stage_module_and_kernel_name(one_chip, monkeypatch):
    """The k-means assign stage compiles to a module named after the
    stage, and its Pallas call still matches the roofline reader."""
    import re
    from repro.kernels.kmeans_assign import ops
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    assign = make_kmeans_stages(20, 10, "array")[0]
    traced = _TracedUDF(assign.name, assign.masked_udf, masked=True)
    compiled = traced._jit.lower(one_chip((ROWS, 80), jnp.uint8),
                                 one_chip((), jnp.int32),
                                 one_chip((10, 20), jnp.float32)).compile()
    module, ops_text = _module_and_ops(compiled)
    assert module == "jit_stage_assign_masked"
    rx = re.compile(_kernel_regex("kmeans_assign_roofline"))
    assert [op for op in ops_text if rx.search(op)]


def test_stacked_round_kernel_matches_the_roofline_reader(one_chip):
    import re
    compiled = _compile(partial(_scatter_stacked, n_buckets=6,
                                key_spec=("range", 10, 3, None),
                                block_n=None, interpret=False),
                        one_chip((2, 4096, RECORD), jnp.uint8),
                        one_chip((5, 3), jnp.uint32),
                        one_chip((2,), jnp.int32))
    _, ops_text = _module_and_ops(compiled)
    rx = re.compile(_kernel_regex("bucket_scatter_roofline"))
    assert [op for op in ops_text if rx.search(op)]


@pytest.mark.parametrize("stage", ["partition", "sort"])
def test_terasort_stage_modules_carry_the_stage_name(one_chip, stage):
    """Both TeraSort stages run through the stacked-pieces entry point;
    their modules differ by the stage's name, which the device trace
    keeps (``sort_device_s`` reads ``jit_stage_sort_*``)."""
    from repro.core.shuffle import terasort_stages
    st = {s.name: s for s in terasort_stages([], "array", 1)}[stage]
    traced = _TracedUDF(st.name, st.batch_udf, pad_value=st.pad_value)
    piece = one_chip((256, RECORD), jnp.uint8)
    compiled = traced._jit_stack_pieces.lower(
        (piece,), one_chip((1,), jnp.int32), target=256).compile()
    module, _ = _module_and_ops(compiled)
    assert module == f"jit_stage_{stage}_pieces"
