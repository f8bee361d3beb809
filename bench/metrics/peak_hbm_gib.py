"""Peak device memory in use over the run, on the fullest chip, in GiB
(``memory_stats()["peak_bytes_in_use"]`` after the window)."""


def read(ctx):
    return None if ctx.peak_bytes is None else ctx.peak_bytes / 2**30
