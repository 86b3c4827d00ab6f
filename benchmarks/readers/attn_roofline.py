"""Attention's share of its roofline in training, in percent: the least
seconds the chip needs for one step's attention work (``costs_attn``: model
work from the configuration's shapes and the step's tokens, recomputation
not counted, the larger of the compute and the memory bound) over the device
seconds a step spends under the ``scope`` the program puts around whatever
implements attention (recomputation included). The work never comes from
what the trace says a kernel did, so the share reads the same work under any
implementation. None where the trace has no operation under the scope."""

import costs
import costs_attn
import trace_scopes


def read(ctx, scope: str = "attn_core",
         rate_key: str = "steady_tokens_per_s", step_key: str = "step_s_p50"):
    trace = trace_scopes.current()
    rate, step_s = ctx.values.get(rate_key), ctx.values.get(step_key)
    if trace is None or rate is None or step_s is None:
        return None
    took = steps = 0
    for plane, ops in trace.devices.items():
        took += trace_scopes.time_by(
            ops, lambda op: trace_scopes.under(op, scope)).get(True, 0)
        steps += trace_scopes.steps_with(
            ops, trace.modules.get(plane, ()), scope)
    if not took or not steps:
        return None
    # a step's tokens are spread over the chips; each traced step of each
    # chip is one sample of the seconds its share of them took
    floor = costs_attn.floor_seconds(
        rate * step_s / ctx.chips, costs.peaks(ctx.device["kind"]),
        **ctx.model_kwargs)
    return 100.0 * floor / (took * trace_scopes.PS / steps)
