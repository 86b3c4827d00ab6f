"""The share of the device's idle seconds in the traced span that fall
inside a program span on the profiler's clock, in percent. The program's
``Tracer`` mirrors its context-managed spans into the trace as host events of
the same name; where spans nest or overlap the innermost wins
(``trace_scopes.attribute``). Prints, on a line of its own, the idle seconds
by span and the ``top`` longest gaps, each with the span that holds most of
it (``none``: the host was in no span). None where the trace holds no
program span: the program mirrors none."""

import json

import trace_scopes


def read(ctx, top: int = 10):
    trace = trace_scopes.current()
    if trace is None:
        return None
    host = trace.host_spans(s.name for s in ctx.spans)
    if not host:
        return None
    by_span, longest = {}, []
    for ops in trace.devices.values():
        took, labelled = trace_scopes.attribute(trace_scopes.gaps(ops), host)
        for name, ps in took.items():
            by_span[name] = by_span.get(name, 0) + ps
        longest += labelled
    idle = sum(by_span.values())
    if not idle:
        return None
    print("idle seconds by host span: " + json.dumps(
        {name: ps * trace_scopes.PS for name, ps
         in sorted(by_span.items(), key=lambda kv: -kv[1])}), flush=True)
    print("longest idle gaps (seconds), with the host span that holds most "
          "of each: " + json.dumps(
              [[ps * trace_scopes.PS, name] for ps, name
               in sorted(longest, reverse=True)[:top]]), flush=True)
    return 100.0 * (idle - by_span.get("none", 0)) / idle
