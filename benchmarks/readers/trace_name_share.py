"""The share of the device's busy time spent in the operations whose name
matches ``pattern``, in percent."""

from trace_reduce import matching_seconds


def read(ctx, pattern: str):
    if ctx.trace is None or not ctx.trace.busy_s:
        return None
    matched = ctx.trace.per_device(lambda ev: matching_seconds(ev, pattern))
    return 100.0 * matched / ctx.trace.busy_s if matched else None
