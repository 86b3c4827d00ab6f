"""Operations and bytes of the hybrid decoder's sparse-attention family
(`edl_tpu/models/hybrid.py`, pattern of ``S`` and ``E`` layers: rotary
grouped-query attention under a learned indexer that keeps ``indexer_topk``
keys a query, a softmax-routed layer of gated experts with no shared one),
from shapes only, whatever implements them. Kept with the benchmark so that
no later PR can change the yardstick. The sizes are the configuration's under
the program's names (``run.model_kwargs``) plus ``seq_len``, which the
traffic gives.

Conventions, those of ``costs.py`` and ``costs_hybrid.py`` (whose ``ITEM``,
``_floor`` and ``held_assignments_per_token`` are used here): matmuls only, 2 a
multiply-add; the attention core counts the keys a query SELECTS and no
other (``mean over t of min(t + 1, indexer_topk)``: a masked dense core
computes four times that at the cell and is credited with none of it); the
indexer's scores count the causal half; routed experts count ``top_k x held
/ published`` of a token through three matrices; backward twice the forward,
except the indexer, which takes no gradient and runs once a step, forward;
recomputation not counted. Bytes are the least a call must move: each
operand read and each result written once, bf16.
"""

from __future__ import annotations

from costs_hybrid import ITEM, _floor, held_assignments_per_token


def kept_keys_mean(seq_len, indexer_topk, **_) -> float:
    """Keys a query attends to, averaged over the positions of a sequence:
    ``t + 1`` up to ``indexer_topk``, that many after."""
    k = min(indexer_topk, seq_len)
    return (k * (k + 1) / 2 + (seq_len - k) * k) / seq_len


def attn_forward_flops_per_token_layer(n_heads, head_dim, **kw) -> float:
    """QK^T and PV over the query heads' width for the selected keys: 4
    (heads x head_dim) a selected key."""
    return 4 * kept_keys_mean(**kw) * n_heads * head_dim


def attn_forward_bytes_per_token_layer(n_heads, n_kv_heads, head_dim,
                                       **_) -> float:
    """q read and o written at the query heads' width, k and v read once at
    the K/V heads' (a core that gathers a query's keys moves far more: that
    is its cost, not the work's)."""
    return (2 * n_heads + 2 * n_kv_heads) * head_dim * ITEM


def indexer_forward_flops_per_token_layer(indexer_heads, indexer_head_dim,
                                          seq_len, **_) -> float:
    """The indexer's scores: ``qI . kI`` over the heads for every causal
    pair, 2 (heads x head_dim) a pair, half of S a query."""
    return 0.5 * 2 * seq_len * indexer_heads * indexer_head_dim


def indexer_forward_bytes_per_token_layer(indexer_heads, indexer_head_dim,
                                          **_) -> float:
    """qI and kI read in bf16, the head weights in float32; the scores need
    never leave the chip's fast memory, a threshold a query does."""
    return (indexer_heads + 1) * indexer_head_dim * ITEM \
        + indexer_heads * 4 + 4


def experts_forward_flops_per_token_layer(d_model, expert_width,
                                          **kw) -> float:
    """The grouped product over the gated experts held: gate, up and down,
    6 D F an assignment."""
    return held_assignments_per_token(**kw) * 6 * d_model * expert_width


def forward_flops_per_token(pattern, d_model, vocab_size, n_heads, n_kv_heads,
                            head_dim, n_experts, indexer_heads,
                            indexer_head_dim, **kw) -> dict:
    """One token's forward pass by layer kind (one layer of the kind), the
    indexer of an S layer apart, and the head."""
    D = d_model
    q, kv = n_heads * head_dim, n_kv_heads * head_dim
    sizes = dict(kw, d_model=D, n_heads=n_heads, head_dim=head_dim,
                 n_experts=n_experts, indexer_heads=indexer_heads,
                 indexer_head_dim=indexer_head_dim)
    return {
        "S": 2 * D * (q + 2 * kv) + 2 * q * D
        + attn_forward_flops_per_token_layer(**sizes),
        "indexer": 2 * D * (indexer_heads * indexer_head_dim
                            + indexer_head_dim + indexer_heads)
        + indexer_forward_flops_per_token_layer(**sizes),
        "E": 2 * D * n_experts + experts_forward_flops_per_token_layer(**sizes),
        "head": 2 * D * vocab_size,
    }


def train_flops_per_token(pattern, **kw) -> float:
    """Model FLOPs of one trained token: the pattern's layers and the head
    forward and backward at twice that, the indexers forward alone."""
    per = forward_flops_per_token(pattern, **kw)
    return 3.0 * (sum(per[kind] for kind in pattern) + per["head"]) \
        + pattern.count("S") * per["indexer"]


def sparse_attn_floor_seconds(tokens: float, peaks: dict, pattern,
                              **kw) -> float:
    """The least seconds the attention cores of a step's ``S`` layers need
    for the SELECTED keys' work: forward, and backward at twice the forward
    in operations and bytes."""
    n = pattern.count("S")
    return _floor(
        3.0 * n * tokens * attn_forward_flops_per_token_layer(**kw),
        3.0 * n * tokens * attn_forward_bytes_per_token_layer(**kw), peaks)


def indexer_floor_seconds(tokens: float, peaks: dict, pattern, **kw) -> float:
    """The indexer's scores over a step's ``S`` layers: once, forward."""
    n = pattern.count("S")
    return _floor(n * tokens * indexer_forward_flops_per_token_layer(**kw),
                  n * tokens * indexer_forward_bytes_per_token_layer(**kw),
                  peaks)


def experts_floor_seconds(tokens: float, peaks: dict, pattern, d_model,
                          expert_width, experts_count, held_per_token=None,
                          **kw) -> float:
    """The held experts' grouped product over a step's ``E`` layers, for
    ``held_per_token`` assignments a token a layer where the run counted
    them, else what uniform routing sends (`held_assignments_per_token`).
    Bytes: the held experts' three matrices read once forward and twice
    backward (whatever the tokens), their gradients written once; an
    assignment's row read and written at D and at F forward, twice that
    backward."""
    n = pattern.count("E")
    if held_per_token is None:
        held_per_token = held_assignments_per_token(
            experts_count=experts_count, **kw)
    weights = experts_count * 3 * d_model * expert_width * ITEM
    rows = held_per_token * tokens * 2 * (d_model + expert_width) * ITEM
    return _floor(
        3.0 * n * tokens * held_per_token * 6 * d_model * expert_width,
        n * (4.0 * weights + 3.0 * rows), peaks)
