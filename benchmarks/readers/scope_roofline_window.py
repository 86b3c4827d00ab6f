"""A scope's share of its roofline in training, in percent, for the
sliding-window family: reader ``scope_roofline`` with the floor functions of
``costs_window`` (that reader imports ``costs_hybrid`` by name). The least
seconds the chip needs for one step's work of the kind (``cost``: model work
from the configuration's shapes and the step's tokens, recomputation not
counted, the larger of the compute and the memory bound) over the device
seconds a step spends under the ``scopes`` the program puts around whatever
implements it (recomputation included in the seconds). ``counted`` names
arguments of ``cost`` that the runner counted in this run (``{argument: key
of the runner's values}``). None where the trace has no operation under the
scopes (a program that does not name them: the parent of the PR that added
them), or the configuration has no window."""

import costs
import costs_window
import trace_scopes


def read(ctx, scopes, cost: str, counted=None,
         rate_key: str = "steady_tokens_per_s", step_key: str = "step_s_p50"):
    trace = trace_scopes.current()
    rate, step_s = ctx.values.get(rate_key), ctx.values.get(step_key)
    seq_len = ctx.values.get("seq_len")
    if trace is None or None in (rate, step_s, seq_len) \
            or "window" not in ctx.model_kwargs:
        return None
    took = steps = 0
    for plane, ops in trace.devices.items():
        took += trace_scopes.time_by(ops, lambda op: any(
            trace_scopes.under(op, s) for s in scopes)).get(True, 0)
        steps += max(trace_scopes.steps_with(
            ops, trace.modules.get(plane, ()), s) for s in scopes)
    if not took or not steps:
        return None
    seen = {arg: ctx.values[key] for arg, key in (counted or {}).items()
            if key in ctx.values}
    floor = getattr(costs_window, cost)(
        rate * step_s / ctx.chips, costs.peaks(ctx.device["kind"]),
        **ctx.model_kwargs, seq_len=int(seq_len), **seen)
    return 100.0 * floor / (took * trace_scopes.PS / steps)
