"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / (traced window), averaged over
the cell's chips."""


def read(ctx):
    if ctx.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
