"""Share of the traced window in which no operation ran on the device:
1 − (union of device-op intervals ÷ window), mean over chips, in %."""


def read(ctx):
    t = ctx.trace
    return None if t.window_s <= 0 else 100.0 * (1.0 - t.busy_s / t.window_s)
