"""The share of the device's busy time spent in the operations of one phase
of the train step (``trace_scopes.phase_of`` of each operation's op_name:
``forward``, ``recompute``, ``backward``, ``optimizer``), self time, in
percent. None where no operation carries the phase: the program names none."""

import trace_scopes


def read(ctx, phase: str):
    trace = trace_scopes.current()
    if trace is None:
        return None
    mine = busy = 0
    for ops in trace.devices.values():
        by_phase = trace_scopes.time_by(
            ops, lambda op: trace_scopes.phase_of(op.op_name))
        mine += by_phase.get(phase, 0)
        busy += sum(by_phase.values())
    return 100.0 * mine / busy if mine else None
