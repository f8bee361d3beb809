"""Compiled device dispatches per job (``SphereReport.device_dispatches``)."""


def read(ctx):
    return sum(o.report.device_dispatches for o in ctx.outs) / ctx.n_jobs
