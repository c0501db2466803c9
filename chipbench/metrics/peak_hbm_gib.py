"""``memory_stats()["peak_bytes_in_use"]`` after the window, on the fullest
chip, in GiB."""


def read(ctx):
    return ctx.peak_bytes / 2**30 if ctx.peak_bytes else None
