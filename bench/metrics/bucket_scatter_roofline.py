"""The ``bucket_scatter`` kernel's share of its roofline, in percent:
the least bytes its passes must move (``kernel_cost``) at the chip's
HBM bandwidth, over the kernel's summed device time in the trace.
Memory bound: the kernel compares keys and counts, with no FLOPs to
speak of.  One pass per job over every record."""
from bench.kernel_cost import bucket_scatter_bytes
from bench.trace_reduce import roofline_share

# the Pallas call's op in the trace, named after its jitted wrapper
KERNEL = r'^%\S*bucket_scatter\S* = .*custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.kernel_seconds(KERNEL)
    if seconds is None:
        return None
    cfg = ctx.config
    nbytes = ctx.n_jobs * bucket_scatter_bytes(cfg["records"],
                                               cfg["key_bytes"])
    return roofline_share(nbytes / ctx.peaks["hbm_bytes_per_s"], seconds)
