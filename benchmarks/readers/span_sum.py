"""The sum of the durations, or with ``count`` the number, of the spans whose
name is one of ``spans``, and with ``parent`` only those whose parent span
(``Span.parent``, the span open on the thread that made it) has that name;
times ``scale``. None where the program records no such span: a program
without the spans leaves the metric out of the line."""


def read(ctx, spans, parent=None, count: bool = False, scale: float = 1.0):
    got = [s for s in ctx.spans if s.name in spans and (
        parent is None or getattr(getattr(s, "parent", None), "name", None)
        == parent)]
    if not got:
        return None
    return (len(got) if count else sum(s.end - s.start for s in got)) * scale
