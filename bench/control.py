"""Controls for the correctness check: runs that must come out not correct.

Usage, from the root of a checkout on a TPU host::

    python3 bench/control.py --workload <name> --seeds 11,12,13 [--jobs 2]

For each seed it sets the cell up as a benchmark run does, runs
``--jobs`` jobs of the program and reads the cell's checks (the sound
reading), then reads the same checks for the control:

* TeraSort — the program's own shorter-key path (``key_bytes=4``): range
  boundaries and the sort use the first 4 key bytes only, which breaks
  the configuration's guarantee of a stable sort by the 10-byte key.
* k-means — the plain reference put in the program's place, with every
  matrix product at ``Precision.HIGH`` (bf16x3), the precision just below
  the program's float32 at ``HIGHEST``.

One JSON line per seed; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402
from bench.registry import load_module  # noqa: E402

SHORT_KEY = 4          # bytes the TeraSort control sorts by


def high_dot(a, b):
    """``a @ b`` at ``Precision.HIGH``: bf16x3 on a TPU."""
    import jax
    import jax.numpy as jnp

    return jnp.dot(a, b, precision=jax.lax.Precision.HIGH,
                   preferred_element_type=jnp.float32)


def lloyd_control(points, init, iters: int, dot=high_dot):
    """The Lloyd reference's update rule with every matrix product done
    by ``dot``: the k-means control."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def step(x, c):
        d2 = (jnp.sum(x * x, 1)[:, None] - 2.0 * dot(x, c.T)
              + jnp.sum(c * c, 1)[None, :])
        onehot = jax.nn.one_hot(jnp.argmin(d2, 1), c.shape[0],
                                dtype=jnp.float32)
        sums, counts = dot(onehot.T, x), onehot.sum(0)
        return jnp.where(counts[:, None] > 0,
                         sums / jnp.maximum(counts, 1.0)[:, None], c)

    x = jnp.asarray(points, jnp.float32)
    c = jnp.asarray(init, jnp.float32)
    for _ in range(iters):
        c = step(x, c)
    return np.asarray(c)


def _outs(state, job, n):
    return [job.run_one(state) for _ in range(n)]


def read_seed(spec, seed: int, jobs: int) -> dict:
    """Sound and control readings of one seed's checks."""
    from repro.core import Tracer

    job = load_module("jobs", spec.traffic["job"])
    kind = spec.traffic["job"]
    out = {"seed": seed}
    with tempfile.TemporaryDirectory(prefix="sphere-control-") as store:
        tracer = Tracer()
        state = job.set_up(spec.config, spec.traffic, seed, store, tracer)
        try:
            t = time.perf_counter()
            outs = _outs(state, job, jobs)
            out["program_s"] = time.perf_counter() - t
        finally:
            job.close(state)
        checks, _ = job.check(state, [o.result for o in outs], outs,
                              tracer.snapshot())
        out["sound"] = {k: v for k, (v, _lim) in checks.items()}
        out["limits"] = {k: lim for k, (_v, lim) in checks.items()}
        t = time.perf_counter()
        if kind == "terasort":
            ctl_tracer = Tracer()
            ctl = job.set_up(spec.config, spec.traffic, seed,
                             os.path.join(store, "control"), ctl_tracer,
                             key_bytes=SHORT_KEY)
            outs = _outs(ctl, job, 1)
            checks, _ = job.check(ctl, [outs[0].result], outs,
                                  ctl_tracer.snapshot())
            out["control"] = {k: v for k, (v, _lim) in checks.items()}
        elif kind == "kmeans":
            got = lloyd_control(state.points, state.init,
                                spec.config["max_iteration"])
            checks, _ = job.check(state, [got], [], [])
            out["control"] = {k: v for k, (v, _lim) in checks.items()}
        else:
            raise ValueError(f"no control for job {kind!r}")
        out["control_s"] = time.perf_counter() - t
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--jobs", type=int, default=2)
    args = ap.parse_args(argv)
    spec = run.resolve(args.workload)
    try:
        run.tpu_devices(spec.workload["chips"])
    except run.NoChip as err:
        print(f"bench/control.py: {err}", file=sys.stderr)
        return 2
    run.configure_jax()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_seed(spec, seed, args.jobs)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
