"""Sector chunk fetch and decode, seconds per job: the ``fetch-chunk``
spans of both the main and the prefetch track."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "fetch-chunk"]
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_jobs if spans else None
