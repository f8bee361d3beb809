"""Shuffle rounds, seconds per job: the ``shuffle-round`` spans, which
end in the round's one host sync and so hold the device's work up to
the histogram."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "shuffle-round"]
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_jobs if spans else None
