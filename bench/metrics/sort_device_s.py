"""The sort stage's device time, seconds per job: every op of the
programs compiled for the stage named ``sort``, whose modules carry the
stage's name (``jit_stage_sort_pieces``, ``jit_stage_sort_stacked``)."""

MODULE = r"^jit_stage_sort_\w+/"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.kernel_seconds(MODULE)
    return None if seconds is None else seconds / ctx.n_jobs
