"""TeraSort on a 4-device ``data`` mesh through ``SphereEngine.run``,
against the plain reference, each case in a subprocess with 4 forced
host devices (the main pytest process keeps exactly 1 — see conftest).

Every chunk is put on its slot's device, the shuffle is the
``shard_map`` + ``all_to_all`` round sized to each device's share, the
sort stage runs per device, and sharded slots leave as slabs."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a GraySort job on the Open Cloud Testbed's 4 racks, 2 chunk servers a
# rack, 8 range buckets from every key (even buckets: the estimated
# exchange capacity fits); ``skew`` then puts every key below the first
# boundary, so one worker receives every record
_PREAMBLE = """
import collections, math, tempfile
import numpy as np, jax
from repro.core import SphereEngine, SphereJob, Tracer
from repro.core import executor as ex
from repro.core.shuffle import sample_boundaries, terasort_stages
from repro.core.spmd import data_mesh
from repro.sector import ChunkServer, SectorClient, SectorMaster
from repro.sector.topology import OPEN_CLOUD_TESTBED as OCT
from bench.gen.gensort import generate
from bench.references.stable_key_sort import reference

def terasort(n, *, skew=False, chunk_records=300):
    rec = generate({"records": n, "record_bytes": 100, "key_bytes": 10}, 11)
    bounds = sample_boundaries([r.tobytes() for r in rec], 8)
    if skew:
        rec[:, 0] = 0
    master = SectorMaster(topology=OCT, chunk_size=100 * chunk_records)
    for i in range(8):
        master.register(ChunkServer(f"s{i}", OCT.sites[i // 2],
                                    tempfile.mkdtemp()))
    master.acl.add_member("u")
    master.acl.grant_write("u")
    client = SectorClient(master, "u", "starlight")
    client.upload("f", rec.tobytes(), replication=3)
    tracer = Tracer()
    eng = SphereEngine(master, client, tracer=tracer, mesh=data_mesh(4))
    job = SphereJob("ts", "f", terasort_stages(bounds, "array", 8),
                    record_size=100, backend="array")
    outs, rep = eng.run(job)
    got = np.frombuffer(b"".join(outs), np.uint8).reshape(-1, 100)
    return got, reference(rec), rep, tracer.snapshot()

def spans(snap, name):
    return [s.attrs or {} for s in snap if s.name == name]
"""


def run_py(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    out = subprocess.run(
        [sys.executable, "-c", _PREAMBLE + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=240, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_mesh_terasort_equals_the_stable_key_sort():
    out = run_py("""
        got, want, rep, snap = terasort(4000)
        assert np.array_equal(got, want), 'output differs from the reference'
        rounds = spans(snap, 'shuffle-round')
        assert [r['path'] for r in rounds] == ['mesh'], rounds
        assert not rounds[0]['regrow'] and rounds[0]['capacity'] > 0
        assert rep.mesh_rounds == rep.shuffle_rounds == rep.host_syncs == 1
        assert rep.exchange_regrows == 0
        assert rep.udf_traces == {'partition': 1, 'sort': 1}
        print('OK')
    """)
    assert "OK" in out


def test_skewed_keys_regrow_the_exchange_and_drop_nothing():
    out = run_py("""
        got, want, rep, snap = terasort(4000, skew=True)
        assert np.array_equal(got, want), 'output differs from the reference'
        rounds = spans(snap, 'shuffle-round')
        assert [(r['path'], r['regrow']) for r in rounds] == [('mesh', True)]
        assert rep.exchange_regrows >= 1
        # one sync for the round and one for each redo, nothing more
        assert rep.host_syncs == rep.shuffle_rounds + rep.exchange_regrows
        # the one worker that holds bucket 0 got every record
        sizes = [a['bytes'] for a in spans(snap, 'd2h')]
        assert sizes == [len(want) * 100], sizes
        print('OK')
    """)
    assert "OK" in out


def test_stage0_puts_each_chunk_on_its_slots_device():
    """Each chunk is decoded straight onto the device ``_slot_devices``
    names, no device takes more than ceil(S / D) of them, and each
    device's shard of the stage-0 stack holds exactly the chunks put
    there."""
    out = run_py("""
        puts, stacks = [], []
        decode, shard_stack = (ex.ArrayExecutor._decode_chunk,
                               ex.ArrayExecutor._shard_stack)
        def spy_decode(self, job, blob, track, device=None):
            batch = decode(self, job, blob, track, device)
            assert device is not None
            assert batch.data.devices() == {device}
            puts.append(device)
            return batch
        def spy_stack(self, pieces, target, width, rep):
            out = shard_stack(self, pieces, target, width, rep)
            stacks.append((out, [next(iter(p.devices())) for p in pieces]))
            return out
        ex.ArrayExecutor._decode_chunk = spy_decode
        ex.ArrayExecutor._shard_stack = spy_stack
        got, want, rep, snap = terasort(4000)
        assert np.array_equal(got, want)
        S = len(puts)
        per_dev = collections.Counter(d.id for d in puts)
        assert sorted(per_dev) == [0, 1, 2, 3], per_dev
        assert max(per_dev.values()) <= math.ceil(S / 4), per_dev
        assert sorted(a['device'] for a in spans(snap, 'h2d-put')) == \\
            sorted(d.id for d in puts)
        stack, piece_devs = stacks[0]      # stage 0; the sort stage
        assert stack.shape[0] % 4 == 0     # reuses the round's stack
        rows = stack.shape[0] // 4
        for k, shard in enumerate(stack.addressable_shards):
            dev = next(iter(shard.data.devices()))
            assert shard.data.shape[0] == rows
            mine = piece_devs[shard.index[0].start:shard.index[0].stop]
            assert all(d == dev for d in mine), (k, mine, dev)
            assert len(mine) == per_dev[dev.id]
        print('OK')
    """)
    assert "OK" in out


def test_sharded_slots_leave_as_slabs():
    """The slots of a stack sharded over the mesh each cross as a slab
    of the one-device shard that holds them, byte for byte the rows.
    Two slots a device fit the copy-out's window, so every copy is
    issued before the first ``tobytes`` ends, and no device ever holds
    more than two issued slabs."""
    out = run_py("""
        import weakref
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.records import StackedBatch
        live, most = [], collections.Counter()
        def counted(data, idx):
            arr = slab(data, idx)
            dev = next(iter(arr.devices())).id
            live.append((dev, weakref.ref(arr)))
            most[dev] = max(most[dev], sum(
                d == dev and r() is not None for d, r in live))
            return arr
        slab, ex._slab = ex._slab, counted
        mesh = data_mesh(4)
        rows = 16384                       # 1.6 MB a slot: over SLAB_MIN_BYTES
        host = np.random.default_rng(3).integers(
            0, 256, (8, rows, 100), dtype=np.uint8)
        n_valid = np.array([rows, 9000, 1, 0, 16000, 5, rows, 77])
        data = jax.device_put(host, NamedSharding(mesh, P('data')))
        stacked = StackedBatch(data, n_valid)
        workers = [f's{i}' for i in range(8)]
        tracer = Tracer()
        plane = ex.ArrayExecutor(None, workers, mesh=mesh, tracer=tracer)
        parts = {w: ex._SlotRef(stacked, i) if n_valid[i] else None
                 for i, w in enumerate(workers)}
        outs = plane.outputs(parts)
        want = [host[i, :n].tobytes() for i, n in enumerate(n_valid) if n]
        assert outs == want
        snap = tracer.snapshot()
        d2h = spans(snap, 'd2h')
        assert [a['layout'] for a in d2h] == ['slab'] * 7, d2h
        assert [a['ahead'] for a in d2h] == [False] + [True] * 6, d2h
        assert spans(snap, 'materialise') == [{'ahead': 6}]
        issue = [s for s in snap if s.name == 'd2h-issue']
        first = min(s.t1 for s in snap if s.name == 'd2h-tobytes')
        assert len(issue) == 7 and all(s.t0 < first for s in issue)
        assert [s.attrs['device'] for s in issue] == [0, 0, 1, 2, 2, 3, 3]
        assert dict(most) == {0: 2, 1: 1, 2: 2, 3: 2}, most
        assert all(r() is None for _, r in live)
        print('OK')
    """)
    assert "OK" in out
