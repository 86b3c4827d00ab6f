"""Operations and bytes of the hybrid decoder's sliding-window family
(`edl_tpu/models/hybrid.py`, a pattern of ``*`` layers (global grouped-query
attention without positions), ``W`` layers (the same under a sliding window,
with rotary positions) and ``E`` layers of gated experts with no shared
one), from shapes only, whatever implements them. Kept with the benchmark so
that no later PR can change the yardstick. The sizes are the configuration's
under the program's names (``run.model_kwargs``) plus ``seq_len``, which the
traffic gives.

Conventions, those of ``costs.py``, ``costs_hybrid.py`` and ``costs_sparse.py``
(whose ``_floor``, ``held_assignments_per_token``, attention bytes and gated
experts' floor are used here): matmuls only, 2
a multiply-add; an attention core counts the (query, key) pairs a query SEES
and no other: ``t + 1`` keys in a global layer (half of S on average),
``min(t + 1, window)`` in a window layer (a core that computes every causal
tile under a mask is credited with the window's pairs alone); routed experts
count ``top_k x held / published`` of a token through three matrices;
backward twice the forward; recomputation not counted. Bytes are the least a
call must move: each operand read and each result written once, bf16.
"""

from __future__ import annotations

from costs_hybrid import _floor, held_assignments_per_token  # noqa: F401
# the same work whatever gates the experts and whatever masks the core: q and
# o at the query heads' width, k and v once at the K/V heads'; 6 D F an
# assignment through gate, up and down
from costs_sparse import (attn_forward_bytes_per_token_layer,  # noqa: F401
                          experts_floor_seconds,
                          experts_forward_flops_per_token_layer)


def seen_keys_mean(seq_len, window=None, **_) -> float:
    """Keys a query sees, averaged over the positions of a sequence: ``t +
    1`` up to ``window``, that many after; without a window ``(S + 1) / 2``."""
    w = seq_len if window is None else min(window, seq_len)
    return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len


def attn_forward_flops_per_token_layer(n_heads, head_dim, seq_len,
                                       window=None, **_) -> float:
    """QK^T and PV over the query heads' width for the keys seen: 4 (heads x
    head_dim) a pair."""
    return 4 * seen_keys_mean(seq_len, window) * n_heads * head_dim


def forward_flops_per_token(pattern, d_model, vocab_size, n_heads, n_kv_heads,
                            head_dim, n_experts, seq_len, window,
                            **kw) -> dict:
    """One token's forward pass by layer kind (one layer of the kind) and
    the head."""
    D = d_model
    q, kv = n_heads * head_dim, n_kv_heads * head_dim
    proj = 2 * D * (q + 2 * kv) + 2 * q * D
    sizes = dict(kw, d_model=D, n_heads=n_heads, head_dim=head_dim,
                 n_experts=n_experts, seq_len=seq_len)
    return {
        "*": proj + attn_forward_flops_per_token_layer(**sizes),
        "W": proj + attn_forward_flops_per_token_layer(window=window,
                                                       **sizes),
        "E": 2 * D * n_experts + experts_forward_flops_per_token_layer(**sizes),
        "head": 2 * D * vocab_size,
    }


def train_flops_per_token(pattern, **kw) -> float:
    """Model FLOPs of one trained token: the pattern's layers and the head
    forward, and backward at twice that. (The routers are frozen in the
    benchmark's configuration; their backward is 0.08% of this and is
    counted as any matmul's.)"""
    per = forward_flops_per_token(pattern, **kw)
    return 3.0 * (sum(per[kind] for kind in pattern) + per["head"])


def _attn_floor(kind, windowed, tokens, peaks, pattern, window=None, **kw):
    n = pattern.count(kind)
    return _floor(
        3.0 * n * tokens * attn_forward_flops_per_token_layer(
            window=window if windowed else None, **kw),
        3.0 * n * tokens * attn_forward_bytes_per_token_layer(**kw), peaks)


def window_attn_floor_seconds(tokens: float, peaks: dict, pattern,
                              **kw) -> float:
    """The least seconds the attention cores of a step's ``W`` layers need
    for the pairs INSIDE the window: forward, and backward at twice the
    forward in operations and bytes."""
    return _attn_floor("W", True, tokens, peaks, pattern, **kw)


def full_attn_floor_seconds(tokens: float, peaks: dict, pattern,
                            **kw) -> float:
    """The same for a step's ``*`` layers, every causal pair."""
    return _attn_floor("*", False, tokens, peaks, pattern, **kw)
