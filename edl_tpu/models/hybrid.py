"""Hybrid decoder LM: a stack that is a PATTERN of layer kinds.

Where `models/transformer.py` scans identical attention+FFN blocks, this
decoder's stack is a string, one character a layer, and every layer is one
mixer OR one feed-forward part alone under a pre-norm and a residual:
``x = x + mixer(rmsnorm(x; w, eps))``. After the last layer a final RMS norm
and an untied head; the loss is the mean next-token cross-entropy over the
vocabulary held. The kinds (the family of NVIDIA's Nemotron-H / Nemotron 3
hybrids; equations in each function's docstring):

- ``M``  a Mamba-2 mixer: causal depthwise conv, the SSD chunked scan and
  its backward (the Pallas kernels of `ops.ssd`), a grouped gated RMS norm;
- ``*``  grouped-query causal attention without positional encoding, through
  `ops.flash_attention` with K and V repeated to the query heads outside it;
- ``E``  an expert layer: sigmoid router with a selection bias over ALL the
  published experts, top-k, renormalised and scaled; non-gated relu^2
  experts; one shared expert. The layer is told which experts it holds
  (``experts_held = (first, count)``), routes over all of them and computes
  its own experts' part of the result for the tokens routed to them, by a
  grouped matrix product over assignments sorted by expert. No token is ever
  dropped: there is no capacity and no ``(T, E, C)`` one-hot. What the
  absent experts would add is left out (the chip's share of an
  expert-parallel deployment; the exchange is a later PR);
- ``-``  a dense relu^2 MLP (the family's other sizes use it).

Training only: a Mamba layer carries recurrent state beside K/V, which the
serving engine's cache manager does not know, so `serving/lm.py` and
`runtime/export.py` refuse this module (`NOT_SERVABLE`).

The parameter tree is heterogeneous, one dict a layer under
``params["layers"]["00M"]`` ..., no stacked ``blocks`` leaf; `Trainer`,
`ElasticWorker` and the checkpoint path take it as any other pytree. Axes:
``data`` only. Matmuls in bf16 with float32 accumulation, float32 masters;
norms, softmax statistics, the router, ``dt``, decay sums and the scan's
state in float32. The residual stream is bf16; with ``remat`` each layer is a
`jax.checkpoint` whose weight casts live inside it.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from edl_tpu.models.base import Model
from edl_tpu.obs.metrics import get_registry
from edl_tpu.parallel.sharding import present_axes

KINDS = "ME*-"

#: why the serving tier and the export path refuse this module
NOT_SERVABLE = (
    "the hybrid model's Mamba-2 layers carry recurrent state (a conv window "
    "and an SSM state a layer) beside attention's K/V, and the serving "
    "engine's cache manager, prefill and decode programs know K/V only: "
    "serving it needs state beside K/V (ROADMAP R6); it trains only")

_REG = get_registry()
_M_ASSIGNED = _REG.counter(
    "edl_moe_assignments_total",
    "Token-to-expert assignments the router made (tokens x top_k), by layer",
    labelnames=("layer",))
_M_HELD = _REG.counter(
    "edl_moe_assignments_held_total",
    "Assignments to the experts this rank holds, by layer", labelnames=("layer",))
_M_DROPPED = _REG.counter(
    "edl_moe_assignments_dropped_total",
    "Held assignments the grouped product did not compute (always 0: there "
    "is no capacity)", labelnames=("layer",))
_M_EXPERT_TOKENS = _REG.counter(
    "edl_moe_expert_tokens_total",
    "Tokens routed to each held expert, by layer and published expert index",
    labelnames=("layer", "expert"))


@dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 512
    d_model: int = 64
    #: one character a layer: M, E, *, -
    pattern: str = "ME*E-M"
    seq_len: int = 64
    norm_eps: float = 1e-5
    # -- M: Mamba-2 mixer
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    mamba_groups: int = 2
    state_size: int = 16
    conv_kernel: int = 4
    chunk_size: int = 8
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # -- *: grouped-query attention
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    # -- E: experts. `n_experts` is the router's published width; the layer
    # holds `experts_count` of them from `experts_first` on
    n_experts: int = 8
    experts_first: int = 0
    experts_count: int = 8
    top_k: int = 2
    expert_width: int = 32
    shared_width: int = 64
    routed_scale: float = 2.5
    # -- -: dense MLP
    mlp_width: int = 32
    batch_axis: Union[str, Tuple[str, ...]] = "data"
    #: each layer under `jax.checkpoint`: its input is kept, the rest
    #: recomputed in the backward pass
    remat: bool = False
    #: the Pallas flash kernels for `*` layers; False takes the dense path
    flash: bool = True
    #: tokens a chunk of the head and loss (logits never exist whole)
    loss_chunk: int = 2048

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.mamba_groups * self.state_size

    @property
    def experts_held(self) -> Tuple[int, int]:
        return (self.experts_first, self.experts_count)

    @property
    def layer_names(self) -> Tuple[str, ...]:
        """``00M``, ``01E``, ...: position and kind; dict keys sort in order."""
        return tuple(f"{i:02d}{kind}" for i, kind in enumerate(self.pattern))


def _check(cfg: HybridConfig) -> None:
    bad = set(cfg.pattern) - set(KINDS)
    if bad or not cfg.pattern:
        raise ValueError(f"pattern {cfg.pattern!r}: layer kinds are {KINDS!r}")
    if len(cfg.pattern) > 99:
        raise ValueError("more than 99 layers")
    if cfg.mamba_heads % cfg.mamba_groups or cfg.mamba_inner % cfg.mamba_groups:
        raise ValueError("mamba_heads must divide by mamba_groups")
    if cfg.n_heads % cfg.n_kv_heads:
        raise ValueError("n_heads must divide by n_kv_heads")
    first, count = cfg.experts_held
    if not (0 <= first and count >= 1 and first + count <= cfg.n_experts):
        raise ValueError(f"experts_held {cfg.experts_held} outside the "
                         f"router's {cfg.n_experts} experts")
    if not 1 <= cfg.top_k <= cfg.n_experts:
        raise ValueError(f"top_k {cfg.top_k} of {cfg.n_experts} experts")


# -- parameters ---------------------------------------------------------------------


def _layer_shapes(cfg: HybridConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    D = cfg.d_model
    if kind == "M":
        H, inner = cfg.mamba_heads, cfg.mamba_inner
        return {"norm": (D,), "in_proj": (D, inner + cfg.conv_dim + H),
                "conv_w": (cfg.conv_dim, cfg.conv_kernel),
                "conv_b": (cfg.conv_dim,), "dt_bias": (H,), "A_log": (H,),
                "D": (H,), "gate_norm": (inner,), "out_proj": (inner, D)}
    if kind == "*":
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        return {"norm": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
                "wo": (q, D)}
    if kind == "E":
        F, Fs, n = cfg.expert_width, cfg.shared_width, cfg.experts_count
        return {"norm": (D,), "router": (cfg.n_experts, D),
                "router_bias": (cfg.n_experts,),
                "w_up": (n, D, F), "w_down": (n, F, D),
                "shared_up": (D, Fs), "shared_down": (Fs, D)}
    return {"norm": (D,), "w_up": (D, cfg.mlp_width),
            "w_down": (cfg.mlp_width, D)}


#: every leaf whose product adds to the residual stream
_OUT_PROJECTIONS = ("out_proj", "wo", "w_down", "shared_down")


def _init_layer(cfg: HybridConfig, kind: str, key: jax.Array) -> dict:
    """Normal(0, 0.02) matrices, the out-projections divided by the square
    root of the depth (`rescale_prenorm_residual`); norms 1, biases 0; the
    Mamba-2 family's published initialiser for ``dt_bias`` (inverse softplus
    of a log-uniform step in [min, max], floored), ``A_log = log U[1, 16]``
    and ``D = 1``."""
    out = {}
    shapes = _layer_shapes(cfg, kind)
    for name, k in zip(sorted(shapes), jax.random.split(key, len(shapes))):
        shape = shapes[name]
        if name in ("norm", "gate_norm", "D"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name in ("conv_b", "router_bias"):
            out[name] = jnp.zeros(shape, jnp.float32)
        elif name == "dt_bias":
            lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
            dt = jnp.maximum(dt, cfg.time_step_floor)
            out[name] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
        elif name == "A_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                   1.0, 16.0))
        elif name == "conv_w":  # the conv's fan-in is its kernel
            bound = 1.0 / math.sqrt(cfg.conv_kernel)
            out[name] = jax.random.uniform(k, shape, jnp.float32,
                                           -bound, bound)
        else:
            scale = 0.02 / (math.sqrt(len(cfg.pattern))
                            if name in _OUT_PROJECTIONS else 1.0)
            out[name] = jax.random.normal(k, shape, jnp.float32) * scale
    return out


def _param_spec(cfg: HybridConfig, mesh: Mesh) -> dict:
    """Everything replicated: the only axis is the batch's."""
    rep = lambda shapes: {n: P(*([None] * len(s))) for n, s in shapes.items()}
    return {
        "embed": P(None, None),
        "layers": {name: rep(_layer_shapes(cfg, name[-1]))
                   for name in cfg.layer_names},
        "norm_f": P(None),
        "head": P(None, None),
    }


def _init(cfg: HybridConfig, key: jax.Array, mesh: Mesh) -> dict:
    D, V = cfg.d_model, cfg.vocab_size
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    host = {
        "embed": jax.random.normal(k_embed, (V, D), jnp.float32) * 0.02,
        "layers": {
            name: _init_layer(cfg, name[-1], k) for name, k in zip(
                cfg.layer_names,
                jax.random.split(k_layers, len(cfg.pattern)))},
        "norm_f": jnp.ones((D,), jnp.float32),
        "head": jax.random.normal(k_head, (D, V), jnp.float32) * 0.02,
    }
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        host, _param_spec(cfg, mesh), is_leaf=lambda x: isinstance(x, P))


# -- the layers -----------------------------------------------------------------------

bf16 = jnp.bfloat16


def _mm(spec: str, a: jax.Array, b: jax.Array, out=jnp.float32) -> jax.Array:
    """A matmul on the MXU: bf16 operands, float32 accumulation."""
    return jnp.einsum(spec, a.astype(bf16), b.astype(bf16),
                      preferred_element_type=out)


def _rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * w


def _relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def _ssd(cfg: HybridConfig, x, dt, A, Bm, Cm, D):
    """The Mamba-2 recurrence by the SSD chunked scan. Per head (group
    ``g = head // (H/G)``): ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = S_t C_t + D x_t`` (the skip is folded in). x (B, S, H, P), dt
    (B, S, H) > 0, A (H,) < 0, Bm and Cm (B, S, G, N), D (H,); returns y
    (B, S, H, P) float32.

    The Pallas kernels of `ops/ssd.py` (`ssd_fwd`, `ssd_bwd`): within a chunk
    of Q positions the masked product ``C B^T`` weighted by the decay between
    the two positions; between chunks the state each chunk leaves, carried in
    VMEM; a chunk's output adds what the carried state gives its positions.
    Decay sums, ``exp`` and the state are float32; the products feed the MXU
    bf16 with float32 accumulation. Lowered for a TPU, Q, N and the group's
    R x P must be multiples of 128; for the CPU the same bodies run in the
    interpreter at any shape."""
    from edl_tpu.ops.ssd import ssd_scan

    return ssd_scan(x, dt, A, Bm, Cm, D, chunk=cfg.chunk_size)


def _mamba(cfg: HybridConfig, h: jax.Array, p: dict) -> jax.Array:
    """``[z | xBC | dt] = h W_in``; ``xBC = silu(conv1d_causal_depthwise(xBC)
    + b)`` split into x (H x P), B and C (G x N each); ``dt = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)``; the recurrence of `_ssd` with the skip
    ``D x``; ``y = group_rmsnorm(y * silu(z); w)`` over groups of inner/G
    (the gate before the norm); ``out = y W_out``. h (B, S, D) bf16."""
    Bz, S, _ = h.shape
    H, Pd, G, N = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups,
                   cfg.state_size)
    inner, conv_dim, K = cfg.mamba_inner, cfg.conv_dim, cfg.conv_kernel
    with jax.named_scope("mamba_proj"):
        w_in = p["in_proj"]
        zx = _mm("bsd,de->bse", h, w_in[:, :inner + conv_dim], out=bf16)
        z, xBC = zx[..., :inner], zx[..., inner:]
        dt = _mm("bsd,dh->bsh", h, w_in[:, inner + conv_dim:])
    with jax.named_scope("mamba_conv"):
        xf = jnp.pad(xBC.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(xf[:, j:j + S] * p["conv_w"][:, j] for j in range(K))
        # computed in float32, kept in bf16: x, B and C feed the MXU
        xBC = jax.nn.silu(conv + p["conv_b"]).astype(bf16)
        x = xBC[..., :inner].reshape(Bz, S, H, Pd)
        Bm = xBC[..., inner:inner + G * N].reshape(Bz, S, G, N)
        Cm = xBC[..., inner + G * N:].reshape(Bz, S, G, N)
    with jax.named_scope("ssd_core"):
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y = _ssd(cfg, x, dt, -jnp.exp(p["A_log"]), Bm, Cm, p["D"])
    with jax.named_scope("mamba_norm"):
        y = y.reshape(Bz, S, G, inner // G) \
            * jax.nn.silu(z.astype(jnp.float32)).reshape(Bz, S, G, inner // G)
        y = _rmsnorm(y, p["gate_norm"].reshape(G, inner // G), cfg.norm_eps)
    with jax.named_scope("mamba_proj"):
        return _mm("bse,ed->bsd", y.reshape(Bz, S, inner), p["out_proj"])


def _attention(cfg: HybridConfig, h: jax.Array, p: dict) -> jax.Array:
    """``q = h W_q`` (n_heads x head_dim), ``k, v = h W_k, h W_v`` (n_kv_heads
    x head_dim), no bias, no positional encoding; causal softmax of ``q k^T /
    sqrt(head_dim)``, query head j on K/V head ``j // (n_heads/n_kv_heads)``;
    ``out = a W_o``. K and V are repeated to the query heads outside the
    kernel, which is exact."""
    Bz, S, _ = h.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn_proj"):
        q = _mm("bsd,de->bse", h, p["wq"], out=bf16).reshape(Bz, S, Hq, Dh)
        k = _mm("bsd,de->bse", h, p["wk"], out=bf16).reshape(Bz, S, Hkv, Dh)
        v = _mm("bsd,de->bse", h, p["wv"], out=bf16).reshape(Bz, S, Hkv, Dh)
    with jax.named_scope("attn_core"):
        k, v = (jnp.repeat(a, Hq // Hkv, axis=2) for a in (k, v))
        scale = 1.0 / math.sqrt(Dh)
        if cfg.flash:
            from edl_tpu.ops import flash_attention

            a = flash_attention(q, k, v, causal=True, scale=scale)
        else:
            from edl_tpu.parallel.ring_attention import dense_attention

            a = dense_attention(q, k, v, causal=True, scale=scale)
    with jax.named_scope("attn_proj"):
        return _mm("bse,ed->bsd", a.reshape(Bz, S, Hq * Dh), p["wo"])


def _route(cfg: HybridConfig, tok: jax.Array, p: dict):
    """The router, in float32, over all the published experts: ``s =
    sigmoid(tok W_r^T)``; the top k of ``s + b`` (the selection bias chooses
    and takes no gradient); weights ``s[chosen] / (sum + 1e-20) x scale``.
    Returns chosen experts (T, k) int32 and their weights (T, k)."""
    logits = jnp.einsum("td,ed->te", tok.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(p["router_bias"]),
                              cfg.top_k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * cfg.routed_scale
    return chosen.astype(jnp.int32), weights


def _dispatch_plan(chosen: jax.Array, experts_held: Tuple[int, int]):
    """Sort the (T x k) assignments by expert held; those to experts held
    elsewhere go last, in a group of their own that nothing computes.
    Returns ``order`` (the sorted position's assignment; assignment a is
    token ``a // k``) and ``group_sizes`` (count,): every held assignment is
    in its expert's group, the groups fill the first ``group_sizes.sum()``
    sorted positions, and nothing here has a capacity."""
    first, count = experts_held
    local = chosen.reshape(-1) - first
    group = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
    return order, sizes


def _grouped(rows, w, sizes, held):
    """Row i times the matrix of its group: the grouped matrix product over
    the experts held (bf16 operands and result, float32 accumulation). On
    the TPU `ragged_dot` writes the rows of its groups and leaves the rest of
    its result as it finds it, in the product and in the gradient of its
    rows alike (my chip runs, PR 27: finite leftovers in a small call, NaN in
    the step), and its time follows the rows in its groups (3.4 us a held
    row over a step, by the seed's routing: PERF.md). So the rows past the
    groups' end are zeros and join the last group: every row is written and a
    call costs the same whatever it holds. Zeros give zeros, out and back;
    the mask on the result stays because the chip has not run without it."""
    rows = jnp.where(held[:, None], rows, 0)
    filled = sizes.at[-1].add(rows.shape[0] - sizes.sum())
    out = jax.lax.ragged_dot(rows.astype(bf16), w.astype(bf16), filled,
                             preferred_element_type=bf16)
    return jnp.where(held[:, None], out, 0)


def _experts_of(rows, w_up, w_down, sizes):
    """``relu(rows U_g)^2 V_g``, g a row's group; zeros past the groups."""
    held = jnp.arange(rows.shape[0]) < sizes.sum()
    up = _grouped(rows, w_up, sizes, held)
    act = _relu2(up.astype(jnp.float32)).astype(bf16)
    return _grouped(act, w_down, sizes, held)


#: sorted assignments that one pass of `_experts_held` computes: a third more
#: than uniform routing sends the experts held at the benchmark's sizes
#: (6,144 of 98,304 a layer)
_ROW_TILE = 8192


def _passes(sizes, tile: int):
    """Tiles of ``tile`` sorted assignments that hold a group's, and never
    none: with the whole tiles that `_grouped` computes, a step's time moves
    only when the held assignments pass a multiple of ``tile``, and not with
    a router that sends the experts held nothing."""
    return jnp.maximum(1, (sizes.sum() + tile - 1) // tile)


def _tile(tok, weights, order, sizes, i, tile: int):
    """Sorted assignments ``[i x tile, (i + 1) x tile)``: which they are,
    their tokens, those tokens' rows, their routing weights, and how many of
    them each group has."""
    with jax.named_scope("moe_dispatch"):
        start = i * tile
        which = jax.lax.dynamic_slice_in_dim(order, start, tile)
        token = which // weights.shape[1]
        ends = jnp.cumsum(sizes)
        mine = jnp.clip(jnp.minimum(ends, start + tile)
                        - jnp.maximum(ends - sizes, start), 0, None)
        return which, token, tok[token], weights.reshape(-1)[which], mine


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _experts_held(tok, weights, w_up, w_down, order, sizes, tile: int):
    """``sum over a token's held assignments of w_e f_e(tok)``, (T, D)
    float32, for tokens (T, D), routing weights (T, k) and the plan of
    `_dispatch_plan`. Of the T x k sorted assignments only the first
    ``sizes.sum()`` are to experts held here, ``experts_count / n_experts``
    of them at uniform routing, so one loop walks them a tile at a time for
    as many tiles as hold any (`_passes`): gather the tile's token rows, the
    grouped product up, relu^2, the grouped product down, weigh and add to
    the tokens. Work and memory follow the imbalance a tile at a time, and
    no assignment is left out whatever it is. The loop's length is known
    only on the device, so the backward pass is a second loop, written out:
    it makes a tile's product again. The experts' gradients add up in bf16,
    what one grouped product gives them: in float32 the step's temporaries
    did not fit the chip."""
    return _experts_held_fwd(tok, weights, w_up, w_down, order, sizes,
                             tile)[0]


def _experts_held_loop(tok, weights, w_up, w_down, order, sizes, tile):
    """`_experts_held`, and how many sorted assignments its product gave a
    value other than zero (`_routing_stats` counts the dropped by it)."""
    up, down = w_up.astype(bf16), w_down.astype(bf16)

    def one(i, carry):
        out, computed = carry
        _, token, rows, w, mine = _tile(tok, weights, order, sizes, i, tile)
        with jax.named_scope("moe_experts"):
            part = _experts_of(rows, up, down, mine)
        with jax.named_scope("moe_combine"):
            return (out.at[token].add(part.astype(jnp.float32) * w[:, None]),
                    computed + jnp.sum(jnp.any(part != 0, axis=1),
                                       dtype=jnp.int32))

    return jax.lax.fori_loop(
        0, _passes(sizes, tile), one,
        (jnp.zeros(tok.shape, jnp.float32), jnp.zeros((), jnp.int32)))


def _experts_held_fwd(tok, weights, w_up, w_down, order, sizes, tile):
    out, _ = _experts_held_loop(tok, weights, w_up, w_down, order, sizes,
                                tile)
    return out, (tok, weights, w_up, w_down, order, sizes)


def _experts_held_bwd(tile, res, g):
    tok, weights, w_up, w_down, order, sizes = res
    up, down = w_up.astype(bf16), w_down.astype(bf16)

    def one(i, grads):
        d_tok, d_weights, d_up, d_down = grads
        which, token, rows, w, mine = _tile(tok, weights, order, sizes, i,
                                            tile)
        with jax.named_scope("moe_combine"):
            g_rows = g[token]
        with jax.named_scope("moe_experts"):
            part, pull = jax.vjp(
                lambda r, u, d: _experts_of(r, u, d, mine), rows, up, down)
            d_rows, u, d = pull((g_rows * w[:, None]).astype(bf16))
        with jax.named_scope("moe_combine"):
            d_weights = d_weights.at[which].add(
                jnp.sum(g_rows * part.astype(jnp.float32), axis=-1))
        with jax.named_scope("moe_dispatch"):
            d_tok = d_tok.at[token].add(d_rows.astype(jnp.float32))
        return d_tok, d_weights, d_up + u, d_down + d

    d_tok, d_weights, d_up, d_down = jax.lax.fori_loop(
        0, _passes(sizes, tile), one,
        (jnp.zeros(tok.shape, jnp.float32),
         jnp.zeros((weights.size,), jnp.float32),
         jnp.zeros_like(up), jnp.zeros_like(down)))
    return (d_tok.astype(tok.dtype), d_weights.reshape(weights.shape),
            d_up.astype(w_up.dtype), d_down.astype(w_down.dtype), None, None)


_experts_held.defvjp(_experts_held_fwd, _experts_held_bwd)


def _routed(cfg: HybridConfig, tok, w_up, w_down, chosen, weights):
    """The held experts' part of the layer for tokens (T, D): the plan that
    sorts the assignments by expert, then `_experts_held`. No assignment to
    a held expert is ever left out."""
    with jax.named_scope("moe_dispatch"):
        order, sizes = _dispatch_plan(chosen, cfg.experts_held)
    return _experts_held(tok, weights, w_up, w_down, order, sizes,
                         math.gcd(chosen.size, _ROW_TILE))


def _moe(cfg: HybridConfig, h: jax.Array, p: dict) -> jax.Array:
    """``out = sum over e chosen and held of w_e f_e(h) + f_shared(h)``,
    ``f(h) = relu(h U)^2 V``. h (B, S, D) bf16."""
    Bz, S, D = h.shape
    tok = h.reshape(Bz * S, D)
    with jax.named_scope("moe_route"):
        chosen, weights = _route(cfg, tok, p)
    routed = _routed(cfg, tok, p["w_up"], p["w_down"], chosen, weights)
    with jax.named_scope("moe_shared"):
        shared = _mm("tf,fd->td",
                     _relu2(_mm("td,df->tf", tok, p["shared_up"])),
                     p["shared_down"])
    return (routed + shared).reshape(Bz, S, D)


def _mlp(cfg: HybridConfig, h: jax.Array, p: dict) -> jax.Array:
    with jax.named_scope("mlp"):
        return _mm("bsf,fd->bsd", _relu2(_mm("bsd,df->bsf", h, p["w_up"])),
                   p["w_down"])


_MIXERS = {"M": ("mamba_mixer", _mamba), "*": ("attn", _attention),
           "E": ("moe", _moe), "-": ("dense_mlp", _mlp)}


def _layer(cfg: HybridConfig, kind: str, x: jax.Array, p: dict) -> jax.Array:
    """``x + mixer(rmsnorm(x))``; x (B, S, D) bf16."""
    scope, mixer = _MIXERS[kind]
    with jax.named_scope(scope):
        h = _rmsnorm(x, p["norm"], cfg.norm_eps).astype(bf16)
        return x + mixer(cfg, h, p).astype(bf16)


def _stack(cfg: HybridConfig, params: dict, tokens: jax.Array, visit=None):
    """Embedding and the layers; ``visit(name, x)`` sees each layer's input."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(bf16)
    for name in cfg.layer_names:
        if visit is not None:
            visit(name, x)
        fn = partial(_layer, cfg, name[-1])
        if cfg.remat:
            fn = jax.checkpoint(fn)
        x = fn(x, params["layers"][name])
    return x


def _head_loss(cfg: HybridConfig, x, norm_f, head, targets) -> jax.Array:
    """Final norm, head and the sum of token cross-entropies, a chunk of
    the sequence at a time under `jax.checkpoint`: the logits of a chunk
    exist, those of the step never do."""
    Bz, S, D = x.shape
    C = math.gcd(S, cfg.loss_chunk)

    @jax.checkpoint
    def chunk(total, xs):
        xc, tc = xs  # (B, C, D), (B, C)
        hc = _rmsnorm(xc, norm_f, cfg.norm_eps)
        logits = _mm("bcd,dv->bcv", hc, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return total + jnp.sum(lse - gold), None

    with jax.named_scope("head_loss"):
        xs = (x.reshape(Bz, S // C, C, D).swapaxes(0, 1),
              targets.reshape(Bz, S // C, C).swapaxes(0, 1))
        total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), xs)
        return total / (Bz * S)


def _kernel(cfg: HybridConfig, mesh: Mesh, params, tokens, targets):
    x = _stack(cfg, params, tokens)
    loss = _head_loss(cfg, x, params["norm_f"], params["head"], targets)
    axes = present_axes(mesh, cfg.batch_axis)
    return jax.lax.pmean(loss, axes) if axes else loss


def _batch_specs(cfg: HybridConfig, mesh: Mesh) -> Dict[str, P]:
    dp = present_axes(mesh, cfg.batch_axis) or None
    return {"tokens": P(dp, None), "targets": P(dp, None)}


def _loss(cfg: HybridConfig, params: dict, batch: dict, mesh: Mesh):
    specs = _batch_specs(cfg, mesh)
    return jax.shard_map(
        partial(_kernel, cfg, mesh), mesh=mesh,
        in_specs=(_param_spec(cfg, mesh), specs["tokens"], specs["targets"]),
        out_specs=P(), check_vma=False,
    )(params, batch["tokens"], batch["targets"])


# -- routing statistics ---------------------------------------------------------------


def _routing_stats(cfg: HybridConfig, params: dict, tokens: jax.Array):
    """What every E layer does with one batch, through the layer's own
    routing, dispatch plan and grouped product: per layer the assignments
    made, those to held experts, each held expert's rows, and ``dropped``:
    the held assignments less the rows that the held experts' product gave a
    value other than zero (`_experts_held_loop`, what the step runs)."""
    stats = {}
    first, count = cfg.experts_held

    def visit(name, x):
        if name[-1] != "E":
            return
        p = params["layers"][name]
        h = _rmsnorm(x, p["norm"], cfg.norm_eps).astype(bf16)
        tok = h.reshape(-1, cfg.d_model)
        chosen, weights = _route(cfg, tok, p)
        order, sizes = _dispatch_plan(chosen, cfg.experts_held)
        _, computed = _experts_held_loop(
            tok, weights, p["w_up"], p["w_down"], order, sizes,
            math.gcd(chosen.size, _ROW_TILE))
        to_held = jnp.sum((chosen >= first) & (chosen < first + count),
                          dtype=jnp.int32)
        stats[name] = {"made": jnp.asarray(chosen.size, jnp.int32),
                       "held": to_held, "per_expert": sizes,
                       "dropped": to_held - computed}

    _stack(cfg, params, tokens, visit=visit)
    return stats


def make_routing_stats(cfg: HybridConfig):
    """``routing_stats(params, batch) -> {layer: {made, held, per_expert,
    dropped}}`` as host numbers, and the same into the metrics registry. One
    jitted forward pass; never part of the train step."""
    run = jax.jit(partial(_routing_stats, cfg))

    def routing_stats(params, batch) -> Dict[str, dict]:
        got = jax.device_get(run(params, batch["tokens"]))
        out = {}
        for layer, s in got.items():
            per = [int(n) for n in s["per_expert"]]
            out[layer] = {"made": int(s["made"]), "held": int(s["held"]),
                          "per_expert": per, "dropped": int(s["dropped"])}
            _M_ASSIGNED.inc(out[layer]["made"], layer=layer)
            _M_HELD.inc(out[layer]["held"], layer=layer)
            _M_DROPPED.inc(max(out[layer]["dropped"], 0), layer=layer)
            for i, n in enumerate(per):
                _M_EXPERT_TOKENS.inc(n, layer=layer,
                                     expert=str(cfg.experts_first + i))
        return out

    return routing_stats


# -- the Model ------------------------------------------------------------------------


def synthetic_batch(cfg: HybridConfig, rng: np.random.Generator,
                    batch_size: int):
    """Uniform ids over the vocabulary held; next-token targets."""
    ids = rng.integers(0, cfg.vocab_size, (batch_size, cfg.seq_len + 1),
                       dtype=np.int64).astype(np.int32)
    return {"tokens": ids[:, :-1], "targets": ids[:, 1:]}


def forward_flops_per_token(cfg: HybridConfig) -> Dict[str, float]:
    """Model FLOPs of one token's forward pass by layer kind (one layer of
    it) and for the head: matmuls only, products under a causal mask halved
    (attention's, and the SSD's within a chunk), routed experts at ``top_k
    x held / published`` of a token."""
    D, S = cfg.d_model, cfg.seq_len
    H, Pd, G, N, Q = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups,
                      cfg.state_size, cfg.chunk_size)
    inner = cfg.mamba_inner
    q = cfg.n_heads * cfg.head_dim
    kv = cfg.n_kv_heads * cfg.head_dim
    ssd = 0.5 * (2 * Q * N * G + 2 * Q * Pd * H) + 2 * (2 * Pd * N * H)
    return {
        "M": 2 * D * (inner + cfg.conv_dim + H) + 2 * cfg.conv_kernel
        * cfg.conv_dim + ssd + 2 * inner * D,
        "*": 2 * D * (q + 2 * kv) + 2 * q * D + 0.5 * 4 * S * q,
        "E": 2 * D * cfg.n_experts + 4 * D * cfg.shared_width
        + cfg.top_k * cfg.experts_count / cfg.n_experts
        * 4 * D * cfg.expert_width,
        "-": 4 * D * cfg.mlp_width,
        "head": 2 * D * cfg.vocab_size,
    }


def _flops_per_step(cfg: HybridConfig, batch_size: int) -> float:
    """Train-step model FLOPs (`models.base` convention: backward twice the
    forward, recompute not counted)."""
    per = forward_flops_per_token(cfg)
    forward = sum(per[kind] for kind in cfg.pattern) + per["head"]
    return 3.0 * forward * cfg.seq_len * batch_size


def make_model(cfg: Optional[HybridConfig] = None, **overrides) -> Model:
    cfg = cfg or HybridConfig(**overrides)
    _check(cfg)
    return Model(
        name="hybrid",
        init=lambda key, mesh: _init(cfg, key, mesh),
        loss_fn=lambda params, batch, mesh: _loss(cfg, params, batch, mesh),
        param_spec=lambda mesh: _param_spec(cfg, mesh),
        synthetic_batch=lambda rng, bs: synthetic_batch(cfg, rng, bs),
        batch_spec=lambda mesh: _batch_specs(cfg, mesh),
        label_keys=("targets",),
        config=cfg,
        flops_per_step=lambda bs: _flops_per_step(cfg, bs),
        routing_stats=make_routing_stats(cfg),
    )


#: default zoo instance: the tiny preset of the CPU tests
MODEL = make_model()
