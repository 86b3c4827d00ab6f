"""A statistic of a span's self time, times ``scale``: the duration of each
span named ``span`` less what its children cover (``Span.parent``, the
program's own record of which span caused which). With ``less``, only the
children of those names are taken out: ``worker_step`` less ``loss_sync`` is
what a step costs the host beside its wait for the device. None where the
program records no such span."""

from stats import stat
from trace_reduce import union_seconds


def read(ctx, span: str, less=(), stat_name: str = "p50",
         scale: float = 1.0):
    children = {}
    for s in ctx.spans:
        parent = getattr(s, "parent", None)
        if parent is not None and (not less or s.name in less):
            children.setdefault(id(parent), []).append(
                (s.name, s.start, s.end - s.start))
    got = stat([s.end - s.start - union_seconds(children.get(id(s), ()))
                for s in ctx.spans if s.name == span], stat_name)
    return None if got is None else got * scale
