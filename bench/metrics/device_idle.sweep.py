"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window."""


def read(ctx):
    return 100 * (1 - ctx.trace["busy_s"] / ctx.trace["window_s"])
