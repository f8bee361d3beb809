"""Set-up time: from process start to the first timed job (data made
from the seed, Sector upload, programs loaded or compiled, one warm-up
job)."""


def read(ctx):
    return ctx.setup_s
