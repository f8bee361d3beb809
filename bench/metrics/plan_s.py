"""Planner, seconds per job: the ``planner:plan-stage`` spans."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "planner:plan-stage"]
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_jobs if spans else None
