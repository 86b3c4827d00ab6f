"""A statistic of the start-to-start intervals of consecutive spans of one
name, times ``scale``. Where ``group_attr`` is given, spans are consecutive
within one value of that attribute (one decode group's own cadence).
Intervals longer than ``max_s`` are left out: the engine was idle between
them, not turning."""

from stats import stat


def read(ctx, span: str, stat_name: str = "p50", scale: float = 1.0,
         group_attr: str = "", max_s: float = float("inf")):
    last, gaps = {}, []
    for s in sorted((s for s in ctx.spans if s.name == span),
                    key=lambda s: s.start):
        key = s.attrs.get(group_attr) if group_attr else None
        if key in last and s.start - last[key] <= max_s:
            gaps.append(s.start - last[key])
        last[key] = s.start
    got = stat(gaps, stat_name)
    return None if got is None else got * scale
