"""Model FLOP/s utilisation of a sparse-attention cell, in percent: as reader
``mfu``, on ``costs_sparse.train_flops_per_token`` (the attention core over
the keys a query selects and no other; the indexer forward alone, its scores
over the causal half; routed experts at ``top_k x held / published`` of a
token through three matrices; recompute not counted). The sequence length is
the traffic's, which the runner reports."""

import costs
import costs_sparse


def read(ctx, rate_key: str = "steady_tokens_per_s"):
    rate, seq_len = ctx.values.get(rate_key), ctx.values.get("seq_len")
    if rate is None or seq_len is None:
        return None
    peak = costs.peaks(ctx.device["kind"])["flops_per_s"]
    flops = costs_sparse.train_flops_per_token(
        **ctx.model_kwargs, seq_len=int(seq_len))
    return 100.0 * rate * flops / (ctx.chips * peak)
