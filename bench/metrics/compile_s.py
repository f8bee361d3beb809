"""JAX's compile path inside the window, seconds per job: the union of
the program's ``jit-trace``, ``jit-lower``, ``jit-compile`` and
``jit-cache-load`` spans (a trace holds the traces of the functions it
calls, so the spans nest).  0 where nothing traced; nothing where the
program records no compile spans.  Each span's ``fun_name`` names
what compiled."""
from bench.trace_reduce import union


def _names():
    from repro.core import trace

    return getattr(trace, "COMPILE_SPANS", None)


def read(ctx):
    names = _names()
    if names is None:
        return None
    spans = union([(s.t0, s.t1) for s in ctx.spans if s.name in names])
    return sum(b - a for a, b in spans) / ctx.n_jobs

