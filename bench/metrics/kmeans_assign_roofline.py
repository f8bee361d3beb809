"""The ``kmeans_assign`` kernel's share of its roofline, in percent: the
larger of its least bytes at the chip's HBM bandwidth and its FLOPs at
the bf16 peak (``kernel_cost``; at 20 dimensions and 10 centroids the
bytes bound it), over the kernel's summed device time in the trace.
One pass over every point per iteration."""
from bench.kernel_cost import kmeans_assign_cost, least_seconds
from bench.trace_reduce import roofline_share

# the Pallas call's op in the trace, named after its jitted wrapper
KERNEL = r'^%\S*kmeans_assign\S* = .*custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.kernel_seconds(KERNEL)
    if seconds is None:
        return None
    cfg = ctx.config
    iters = sum(len(o.iter_seconds or ()) for o in ctx.outs)
    flops, nbytes = kmeans_assign_cost(cfg["samples"], cfg["dimensions"],
                                       cfg["k"])
    least, _bound = least_seconds(iters * flops, iters * nbytes, ctx.peaks)
    return roofline_share(least, seconds)
