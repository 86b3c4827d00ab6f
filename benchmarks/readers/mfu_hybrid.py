"""Model FLOP/s utilisation of a hybrid-family cell, in percent: as reader
``mfu``, on ``costs_hybrid.train_flops_per_token`` (per layer kind of the
pattern; products under a causal mask halved; routed experts at ``top_k x
held / published`` of a token; recompute not counted). The sequence length
is the traffic's, which the runner reports."""

import costs
import costs_hybrid


def read(ctx, rate_key: str = "steady_tokens_per_s"):
    rate, seq_len = ctx.values.get(rate_key), ctx.values.get("seq_len")
    if rate is None or seq_len is None:
        return None
    peak = costs.peaks(ctx.device["kind"])["flops_per_s"]
    flops = costs_hybrid.train_flops_per_token(
        **ctx.model_kwargs, seq_len=int(seq_len))
    return 100.0 * rate * flops / (ctx.chips * peak)
