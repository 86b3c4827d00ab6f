"""A scope's share of its roofline in training, in percent: the least
seconds the chip needs for one step's work of the kind (the function
``cost`` of ``costs_hybrid``: model work from the configuration's shapes and
the step's tokens, recomputation not counted, the larger of the compute and
the memory bound) over the device seconds a step spends under the ``scopes``
the program puts around whatever implements it (recomputation included), as
``attn_roofline`` does for the dense block's attention. More than one scope
where part of the work carries another name: XLA names the kernels it makes of
a `ragged_dot` itself (``ragged-dot-none.N``) and drops the program's scopes
from their op_name, so the instruction's name is the route to them.
``counted`` names arguments of ``cost`` that the runner counted in this run
(``{argument: key of the runner's values}``: work that follows the data, as
the held experts' rows do); one the runner did not give is left to the
function's own reckoning. None where the trace has no operation under the
scopes."""

import costs
import costs_hybrid
import trace_scopes


def read(ctx, scopes, cost: str, counted=None,
         rate_key: str = "steady_tokens_per_s", step_key: str = "step_s_p50"):
    trace = trace_scopes.current()
    rate, step_s = ctx.values.get(rate_key), ctx.values.get(step_key)
    seq_len = ctx.values.get("seq_len")
    if trace is None or None in (rate, step_s, seq_len):
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
    floor = getattr(costs_hybrid, cost)(
        rate * step_s / ctx.chips, costs.peaks(ctx.device["kind"]),
        **ctx.model_kwargs, seq_len=int(seq_len), **seen)
    return 100.0 * floor / (took * trace_scopes.PS / steps)
