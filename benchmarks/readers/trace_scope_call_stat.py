"""A statistic of the device seconds of one call under a scope the program
named (a kernel's ``name``, a ``jax.named_scope``), times ``scale``. A call
is a run of consecutive device events under the scope
(``trace_scopes.scope_calls``). None where the trace has no such event."""

import trace_scopes
from stats import stat


def read(ctx, scope: str, stat_name: str = "p50", scale: float = 1.0):
    trace = trace_scopes.current()
    if trace is None:
        return None
    got = stat([took * trace_scopes.PS for ops in trace.devices.values()
                for took in trace_scopes.scope_calls(ops, scope)], stat_name)
    return None if got is None else got * scale
