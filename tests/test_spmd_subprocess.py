"""Multi-device behaviours, each in a subprocess with forced host devices.

(The main pytest process must keep exactly 1 device — see conftest.)
"""
import os
import subprocess
import sys
import textwrap


ROOT = os.path.join(os.path.dirname(__file__), "..")

# preamble available to every subprocess snippet
_PREAMBLE = """
import jax
from jax import shard_map
from repro.launch.mesh import make_auto_mesh as mk_mesh
"""


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", _PREAMBLE + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_sort_correct():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.spmd import distributed_sort
        from repro.core.spmd import data_mesh
        mesh = data_mesh()
        keys = jax.random.randint(jax.random.PRNGKey(0), (1<<13,), 0, 1<<30,
                                  dtype=jnp.uint32)
        outp, valid = distributed_sort(keys, mesh)
        per = np.asarray(outp).reshape(8, -1)
        got = np.concatenate([p[p != 0xFFFFFFFF] for p in per])
        ref = np.sort(np.asarray(keys))
        assert np.array_equal(got, ref), 'sort mismatch'
        print('OK')
    """)
    assert "OK" in out


def test_fused_scatter_round_multidevice_matches_host():
    """The engine's fused shuffle round through shard_map + all_to_all on
    an 8-device mesh: regrouped partitions, counts and per-slot
    histograms must match a per-record host reference exactly — the
    ordering contract (bucket-ascending within a worker, slot-major then
    input order within a bucket) survives the real exchange."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.records import RecordBatch, StackedBatch
        from repro.core.shuffle import hash_partitioner
        from repro.core.spmd import fused_scatter_round
        from repro.core.spmd import data_mesh
        mesh = data_mesh()                      # 8 devices on axis 'data'
        rec, n, W, S = 12, 11, 16, 24           # S slots, W workers, n buckets
        rng = np.random.default_rng(0)
        loads = rng.integers(0, 30, size=S)
        slots = [[rng.integers(0, 256, rec, dtype=np.uint8).tobytes()
                  for _ in range(k)] for k in loads]
        batches = [RecordBatch.from_records(s) if s
                   else RecordBatch.empty(rec) for s in slots]
        stacked = StackedBatch.pack(batches, pad_block=8)
        part = hash_partitioner(key_bytes=8)
        key_spec, bounds = part.scatter_spec(RecordBatch.empty(rec), n)
        parts, counts, hist, over = fused_scatter_round(
            stacked.data, jnp.asarray(stacked.n_valid, jnp.int32), bounds,
            key_spec=key_spec, n_buckets=n, n_workers=W, mesh=mesh)
        # host reference: bucket append order = slot-major, input order
        buckets = [[] for _ in range(n)]
        for s in slots:
            for r in s:
                buckets[part(r, n)].append(r)
        want = [b'' for _ in range(W)]
        wc = [0] * W
        for b in range(n):
            want[b % W] += b''.join(buckets[b])
            wc[b % W] += len(buckets[b])
        assert not np.asarray(over).any()
        counts = np.asarray(counts)
        assert counts.tolist() == wc, (counts.tolist(), wc)
        got = np.asarray(parts)
        for w in range(W):
            assert got[w, :wc[w]].tobytes() == want[w], f'worker {w}'
        hist = np.asarray(hist)
        for s in range(S):
            ref = [part(r, n) for r in slots[s]]
            assert hist[s].tolist() == [ref.count(b) for b in range(n)]
        print('OK')
    """)
    assert "OK" in out


def test_podwise_mode_matches_pjit():
    """Manual-pod train step == plain pjit step (no compression)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS
        from repro.models import model
        from repro.parallel.sharding import ParallelConfig
        from repro.train import optim
        from repro.train.step import make_train_step
        mesh = mk_mesh((2, 2, 2), ('pod', 'data', 'model'))
        cfg = ARCHS['qwen2.5-3b'].reduced().replace(
            param_dtype='float32', compute_dtype='float32')
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        ocfg = optim.AdamWConfig(lr=1e-2)
        opt = optim.init_state(params, ocfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                  cfg.vocab_size)
        batch = {'inputs': toks, 'labels': toks}
        lr = optim.warmup_cosine(1e-2, 2, 10)
        outs = {}
        for mode in ('pjit', 'podwise'):
            pcfg = ParallelConfig(mesh=mesh, multi_pod=True, mode=mode,
                                  remat='none')
            step = make_train_step(cfg, pcfg, ocfg, lr)
            with jax.set_mesh(mesh):
                p2, o2, m = jax.jit(step)(params, opt, batch)
            outs[mode] = (jax.device_get(p2), float(m['loss']))
        a, b = outs['pjit'], outs['podwise']
        assert abs(a[1] - b[1]) < 1e-5, (a[1], b[1])
        for x, y in zip(jax.tree.leaves(a[0]), jax.tree.leaves(b[0])):
            np.testing.assert_allclose(np.asarray(x, np.float32),
                                       np.asarray(y, np.float32),
                                       rtol=2e-4, atol=2e-5)
        print('OK')
    """)
    assert "OK" in out


def test_compressed_cross_pod_close_to_exact():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel import collectives
        from jax.sharding import PartitionSpec as P
        mesh = mk_mesh((4,), ('pod',))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 256))
        ef = jnp.zeros((4, 256))
        def body(gl, efl):
            out, ef2 = collectives.cross_pod_mean(
                {'w': gl[0]}, compress='int8_ef', ef_state={'w': efl[0]})
            return out['w'][None], ef2['w'][None]
        fn = shard_map(body, mesh=mesh, in_specs=(P('pod'), P('pod')),
                       out_specs=(P('pod'), P('pod')))
        red, ef2 = fn(g, ef)
        exact = jnp.mean(g, axis=0)
        err = float(jnp.abs(red[0] - exact).max())
        amax = float(jnp.abs(g).max())
        assert err < amax / 64, (err, amax)   # int8 quantisation band
        # error feedback carries the residual
        assert float(jnp.abs(ef2).max()) > 0
        print('OK')
    """)
    assert "OK" in out


def test_sharded_train_step_matches_single_device():
    """Same seed/batch: 4-device FSDP/TP step == 1-device step."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS
        from repro.models import model
        from repro.parallel.sharding import ParallelConfig
        from repro.train import optim
        from repro.train.step import make_train_step
        cfg = ARCHS['qwen2.5-3b'].reduced().replace(
            param_dtype='float32', compute_dtype='float32')
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        ocfg = optim.AdamWConfig(lr=1e-2)
        opt = optim.init_state(params, ocfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                  cfg.vocab_size)
        batch = {'inputs': toks, 'labels': toks}
        lr = optim.warmup_cosine(1e-2, 2, 10)
        import numpy as _np
        n = jax.device_count()
        if n == 1:
            mesh = mk_mesh((1, 1), ('data', 'model'))
        else:
            mesh = mk_mesh((2, 2), ('data', 'model'))
        pcfg = ParallelConfig(mesh=mesh, remat='none')
        step = make_train_step(cfg, pcfg, ocfg, lr)
        with jax.set_mesh(mesh):
            p2, o2, m = jax.jit(step)(params, opt, batch)
        print('LOSS', float(m['loss']))
    """
    out1 = run_py(code, devices=1)
    out4 = run_py(code, devices=4)
    l1 = float(out1.split("LOSS")[1])
    l4 = float(out4.split("LOSS")[1])
    assert abs(l1 - l4) < 1e-4, (l1, l4)
