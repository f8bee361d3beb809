"""The mesh round's share of its roofline on a chip, in percent: the
least time the round needs at the chip's HBM bandwidth
(``hbm_bytes_per_s`` of ``bench/peaks.json``) over the chip's device
time in the round's module ``jit_mesh_round``, both summed over the
chips and the window's jobs.

The exchange also crosses the chips' interconnect, whose bound is
tighter than HBM's and is not in ``peaks.json``: leaving it out makes
the least time shorter, so the share reads low, never high.  A round
redone at a larger capacity adds its time and no bytes."""
from bench.trace_reduce import roofline_share

MODULE = r"^jit_mesh_round/"


def mesh_round_bytes(records: int, record_bytes: int) -> int:
    """Least HBM bytes of one round, summed over the chips: every record
    is read once from its chip's input, written and read once in a send
    buffer, and written once on the chip that receives it."""
    return 4 * records * record_bytes


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.kernel_seconds(MODULE)
    if seconds is None:
        return None
    cfg = ctx.config
    nbytes = ctx.n_jobs * mesh_round_bytes(cfg["records"],
                                           cfg["record_bytes"])
    return roofline_share(nbytes / ctx.peaks["hbm_bytes_per_s"], seconds)
