"""Waiting on Sector, seconds per job: the ``prefetch-wait`` spans, in
which the data plane stands waiting for the prefetch thread's next
chunk (``fetch_s`` is the fetch work, which overlaps device time)."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "prefetch-wait"]
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_jobs if spans else None
