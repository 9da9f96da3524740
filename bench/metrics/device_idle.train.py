"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over
the chips the cell uses."""


def read(ctx):
    if not ctx.trace.busy_ns:
        return None
    return 100.0 * ctx.trace.idle_share()
