"""Output copy-out, seconds per job: the ``d2h`` spans, one per output
partition, each the copy of a finished partition to the host (the wait
for the device work behind it is the ``output-wait`` span before)."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "d2h"]
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_jobs if spans else None
