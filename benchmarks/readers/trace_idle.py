"""The device's idle share of the traced span, in percent: 1 minus the union
of the operation intervals over first start to last end, averaged over the
chips."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.window_s:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
