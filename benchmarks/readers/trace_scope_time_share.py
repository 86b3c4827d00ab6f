"""The share of the device's busy time spent in operations the program named
under any of ``scopes`` (a ``jax.named_scope`` anywhere in the operation's
op_name, or a kernel's own name), self time, in percent: forward,
recomputation and backward together. None where no operation is under
them."""

import trace_scopes


def read(ctx, scopes):
    trace = trace_scopes.current()
    if trace is None:
        return None
    mine = busy = 0
    for ops in trace.devices.values():
        by = trace_scopes.time_by(
            ops, lambda op: any(trace_scopes.under(op, s) for s in scopes))
        mine += by.get(True, 0)
        busy += sum(by.values())
    return 100.0 * mine / busy if mine else None
