"""Seconds per job: the window's wall time, less the harness's own
output checks between jobs, over the jobs it completed."""


def read(ctx):
    return (ctx.window_s - ctx.check_s) / ctx.n_jobs
