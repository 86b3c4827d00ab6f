"""Model FLOP/s utilisation of a sliding-window cell, in percent: as reader
``mfu``, on ``costs_window.train_flops_per_token`` (a window layer's
attention core over the pairs inside the window and no other, a global
layer's over the causal half; routed experts at ``top_k x held / published``
of a token through three matrices; recompute not counted). The sequence
length is the traffic's, which the runner reports. None where the runner
gave no rate or the configuration has no window."""

import costs
import costs_window


def read(ctx, rate_key: str = "steady_tokens_per_s"):
    rate, seq_len = ctx.values.get(rate_key), ctx.values.get("seq_len")
    if rate is None or seq_len is None or "window" not in ctx.model_kwargs:
        return None
    peak = costs.peaks(ctx.device["kind"])["flops_per_s"]
    flops = costs_window.train_flops_per_token(
        **ctx.model_kwargs, seq_len=int(seq_len))
    return 100.0 * rate * flops / (ctx.chips * peak)
