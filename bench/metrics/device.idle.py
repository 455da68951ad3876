"""device.idle: share of the traced window in which no operation ran on
the device (1 - union of the op intervals / window), averaged over the
chips the cell uses."""


def read(ctx):
    if ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
