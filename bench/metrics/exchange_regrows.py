"""Mesh rounds redone at a larger exchange capacity, per job
(``SphereReport.exchange_regrows``); nothing where the program's report
has no such counter."""


def read(ctx):
    counts = [getattr(o.report, "exchange_regrows", None) for o in ctx.outs]
    if not counts or None in counts:
        return None
    return sum(counts) / ctx.n_jobs
