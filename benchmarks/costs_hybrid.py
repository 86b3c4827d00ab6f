"""Operations and bytes of the hybrid decoder family (`edl_tpu/models/
hybrid.py`: Mamba-2 mixers, grouped-query attention, sparse experts with a
shared one, dense relu^2 MLPs in a pattern), from shapes only, whatever
implements them. Kept with the benchmark so that no later PR can change the
yardstick. The sizes are the configuration's under the program's names
(``run.model_kwargs``) plus ``seq_len``, which the traffic gives.

Conventions, those of ``costs.py`` and ``costs_attn.py``: matmuls only, 2 a
multiply-add; a product under a causal mask counts half (attention's scores
and values, and the SSD's two products within a chunk); routed experts count
``top_k x held / published`` of a token, what uniform routing sends to the
experts held; backward twice the forward; recomputation not counted. Bytes
are the least a call must move: each operand read and each result written
once, bf16.
"""

from __future__ import annotations

ITEM = 2  # bytes of a bf16 element


def ssd_forward_flops_per_token_layer(mamba_heads, mamba_head_dim,
                                      mamba_groups, state_size, chunk_size,
                                      **_) -> float:
    """The chunked scan of one Mamba-2 layer: within a chunk of Q the masked
    ``C B^T`` (2 Q N a group) and its product with x (2 Q P a head), halved
    by the mask; the state a chunk leaves and what the carried state gives
    its positions (2 P N a head each)."""
    H, P, G, N, Q = (mamba_heads, mamba_head_dim, mamba_groups, state_size,
                     chunk_size)
    return 0.5 * (2 * Q * N * G + 2 * Q * P * H) + 2 * (2 * P * N * H)


def ssd_forward_bytes_per_token_layer(mamba_heads, mamba_head_dim,
                                      mamba_groups, state_size, **_) -> float:
    """x read and y written (H x P each), B and C read (G x N each), dt
    read (H)."""
    H, P, G, N = mamba_heads, mamba_head_dim, mamba_groups, state_size
    return (2 * H * P + 2 * G * N + H) * ITEM


def attn_forward_flops_per_token_layer(n_heads, head_dim, seq_len,
                                       **_) -> float:
    """QK^T and PV over the query heads' width: 4 S (heads x head_dim) a
    token, halved by the causal mask."""
    return 0.5 * 4 * seq_len * n_heads * head_dim


def attn_forward_bytes_per_token_layer(n_heads, n_kv_heads, head_dim,
                                       **_) -> float:
    """q read and o written at the query heads' width, k and v read at the
    K/V heads' (grouped-query attention reads each K/V head once)."""
    return (2 * n_heads + 2 * n_kv_heads) * head_dim * ITEM


def held_assignments_per_token(top_k, experts_count, n_experts, **_) -> float:
    return top_k * experts_count / n_experts


def experts_forward_flops_per_token_layer(d_model, expert_width,
                                          **kw) -> float:
    """The grouped product over the experts held: up and down, 4 D F an
    assignment."""
    return held_assignments_per_token(**kw) * 4 * d_model * expert_width


def forward_flops_per_token(pattern, d_model, vocab_size, mamba_heads,
                            mamba_head_dim, mamba_groups, state_size,
                            conv_kernel, n_heads, n_kv_heads, head_dim,
                            n_experts, shared_width, mlp_width, **kw) -> dict:
    """One token's forward pass by layer kind (one layer of the kind) and
    for the head."""
    D = d_model
    inner = mamba_heads * mamba_head_dim
    conv_dim = inner + 2 * mamba_groups * state_size
    q, kv = n_heads * head_dim, n_kv_heads * head_dim
    sizes = dict(kw, d_model=D, mamba_heads=mamba_heads,
                 mamba_head_dim=mamba_head_dim, mamba_groups=mamba_groups,
                 state_size=state_size, n_heads=n_heads, head_dim=head_dim,
                 n_experts=n_experts)
    return {
        "M": 2 * D * (inner + conv_dim + mamba_heads)
        + 2 * conv_kernel * conv_dim
        + ssd_forward_flops_per_token_layer(**sizes) + 2 * inner * D,
        "*": 2 * D * (q + 2 * kv) + 2 * q * D
        + attn_forward_flops_per_token_layer(**sizes),
        "E": 2 * D * n_experts + 4 * D * shared_width
        + experts_forward_flops_per_token_layer(**sizes),
        "-": 4 * D * mlp_width,
        "head": 2 * D * vocab_size,
    }


def train_flops_per_token(pattern, **kw) -> float:
    """Model FLOPs of one trained token: the pattern's layers and the head
    forward, and backward at twice that."""
    per = forward_flops_per_token(pattern, **kw)
    return 3.0 * (sum(per[kind] for kind in pattern) + per["head"])


def _floor(flops: float, nbytes: float, peaks: dict) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s."""
    return max(flops / peaks["flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def ssd_floor_seconds(tokens: float, peaks: dict, pattern, **kw) -> float:
    """The least seconds the SSD cores of a step's ``M`` layers need:
    forward, and backward at twice the forward in operations and bytes."""
    n = pattern.count("M")
    return _floor(3.0 * n * tokens * ssd_forward_flops_per_token_layer(**kw),
                  3.0 * n * tokens * ssd_forward_bytes_per_token_layer(**kw),
                  peaks)


def attn_floor_seconds(tokens: float, peaks: dict, pattern, **kw) -> float:
    """Grouped-query attention's core (scores, softmax, values) over a
    step's ``*`` layers."""
    n = pattern.count("*")
    return _floor(
        3.0 * n * tokens * attn_forward_flops_per_token_layer(**kw),
        3.0 * n * tokens * attn_forward_bytes_per_token_layer(**kw), peaks)


def experts_floor_seconds(tokens: float, peaks: dict, pattern, d_model,
                          expert_width, experts_count, held_per_token=None,
                          **kw) -> float:
    """The held experts' grouped product over a step's ``E`` layers, for
    ``held_per_token`` assignments a token a layer where the run counted
    them, else what uniform routing sends (`held_assignments_per_token`).
    Bytes: the held experts' two matrices read once forward and twice
    backward (whatever the tokens), their gradients written once; an
    assignment's row read and written at D and at F forward, twice that
    backward."""
    n = pattern.count("E")
    if held_per_token is None:
        held_per_token = held_assignments_per_token(
            experts_count=experts_count, **kw)
    weights = experts_count * 2 * d_model * expert_width * ITEM
    rows = held_per_token * tokens * 2 * (d_model + expert_width) * ITEM
    return _floor(
        3.0 * n * tokens * held_per_token * 4 * d_model * expert_width,
        n * (4.0 * weights + 3.0 * rows), peaks)
