"""Attention's model work in training, from shapes: the operations and the
least bytes of the causal self-attention core (scores, softmax, values; not
the projections), whatever implements it. Counted as
``costs.train_flops_per_token`` counts attention, so that a roofline share
built on it and ``mfu_pct.train`` count the same work: rematerialised work is
not counted.

Check (PERF.md, section 5): one forward call over 32 x 16 batch-heads of
(1024, 64), 32,768 tokens at d_model 1024, is 68.7 GFLOP and 268 MB.
"""

from __future__ import annotations


def forward_flops_per_token_layer(d_model: int, seq_len: int) -> float:
    """QK^T and PV: 4 S D a token, halved by the causal mask."""
    return 0.5 * 4 * seq_len * d_model


def forward_bytes_per_token_layer(d_model: int, itemsize: int = 2) -> float:
    """q, k and v read and o written once, each d_model wide, bf16."""
    return 4 * d_model * itemsize


def train_flops_per_token(d_model: int, n_layers: int, seq_len: int,
                          **_) -> float:
    """Forward, and backward at twice the forward."""
    return 3.0 * n_layers * forward_flops_per_token_layer(d_model, seq_len)


def train_bytes_per_token(d_model: int, n_layers: int, **_) -> float:
    """Forward: q, k, v, o. Backward: those again and dO read, dQ, dK and dV
    written: eight arrays, twice the forward's four."""
    return 3.0 * n_layers * forward_bytes_per_token_layer(d_model)


def floor_seconds(tokens: float, peaks: dict, **model_kwargs) -> float:
    """The least seconds a device with these ``peaks`` needs for attention's
    work on ``tokens`` trained tokens: the larger of operations over peak
    FLOP/s and bytes over peak bytes/s."""
    return max(
        tokens * train_flops_per_token(**model_kwargs) / peaks["flops_per_s"],
        tokens * train_bytes_per_token(**model_kwargs)
        / peaks["hbm_bytes_per_s"])
