"""A statistic of the durations of the spans of one name, times ``scale``."""

from stats import stat


def read(ctx, span: str, stat_name: str = "p50", scale: float = 1.0):
    got = stat([s.end - s.start for s in ctx.spans if s.name == span],
               stat_name)
    return None if got is None else got * scale
