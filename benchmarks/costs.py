"""Operations and bytes computed from shapes, and the table of peaks.

Kept with the benchmark so that no later PR can change the yardstick. The
closed form is a copy of ``edl_tpu.models.transformer._flops_per_step`` for
the dense block (PERF.md, Open questions, lists the original for deletion).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in benchmarks/peaks.json")
    return table[device_kind]


def train_flops_per_token(d_model: int, n_layers: int, d_ff: int,
                          vocab_size: int, seq_len: int, **_) -> float:
    """Model FLOPs of one trained token: forward (qkv 6D^2, output
    projection 2D^2, feed-forward 4DF, causal attention 4SD halved by the
    mask, per layer; the head 2DV) and backward at twice the forward.
    Rematerialised work is not counted."""
    D, L, F, V, S = d_model, n_layers, d_ff, vocab_size, seq_len
    forward = L * (8 * D * D + 4 * D * F + 0.5 * 4 * S * D) + 2 * D * V
    return 3.0 * forward
