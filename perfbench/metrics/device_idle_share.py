"""Share of the traced window in which no operation ran on the device:
1 − (union of the device's operation intervals) / window, averaged over
the cell's chips."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window is None:
        return None
    return 100.0 * ctx.trace.idle_share()
