"""95th percentile of every iteration's wall time in the window (jobs
that report iterations)."""
import numpy as np


def read(ctx):
    its = [t for o in ctx.outs for t in (o.iter_seconds or ())]
    return float(np.percentile(its, 95)) if its else None
