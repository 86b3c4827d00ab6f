"""Model FLOP/s utilisation, in percent: the runner's tokens per second times
the benchmark's own FLOPs of one trained token (forward and backward, causal
attention halved, recompute not counted) over chips times the published peak
of this kind of device."""

import costs


def read(ctx, rate_key: str):
    rate = ctx.values.get(rate_key)
    if rate is None:
        return None
    peak = costs.peaks(ctx.device["kind"])["flops_per_s"]
    flops = costs.train_flops_per_token(**ctx.model_kwargs)
    return 100.0 * rate * flops / (ctx.chips * peak)
