"""The mesh exchange's device time, seconds per job: the ``all-to-all``
ops of the trace, summed over the chips and divided by the chips that
ran anything in the window, over the window's jobs.  Every chip of the
mesh runs each exchange, so that is the time one chip spends in it."""

# the collective's own op, by the opcode in its HLO text (a synchronous
# ``all-to-all``, or the start and done halves of an asynchronous one)
OP = r" all-to-all(-start|-done)?\("


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.kernel_seconds(OP)
    if seconds is None:
        return None
    return seconds / ctx.trace.devices / ctx.n_jobs
