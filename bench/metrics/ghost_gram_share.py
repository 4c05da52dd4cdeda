"""Share of the traced steps' device busy time spent in the Gram
ghost-norm kernel (Pallas name `ghost_norm`): the summed device time of
its launches over the union of the device's op intervals, averaged over
the cell's chips.  How much of the step the paper's mechanism is.  None
when no `ghost_norm` ran."""

KERNEL = "ghost_norm"


def read(ctx):
    seconds, launches = ctx.kernel_seconds(KERNEL)
    if launches == 0 or ctx.busy_s <= 0:
        return None
    return 100.0 * seconds / ctx.busy_s
