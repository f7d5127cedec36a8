"""Device: the share of the traced stretch of the window in which no
operation ran on the chip, 1 - busy / window, in percent (the union of
the device's op intervals, ``tracing.summarize``)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
