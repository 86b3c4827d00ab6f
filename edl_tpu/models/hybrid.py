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
  `ops.flash_attention`, which takes K and V as projected (a K/V head's
  group of query heads to a grid step; nothing repeats them);
- ``E``  an expert layer: sigmoid router with a selection bias over ALL the
  published experts, top-k, renormalised and scaled; non-gated relu^2
  experts; one shared expert. The layer is told which experts it holds
  (``experts_held = (first, count)``), routes over all of them and computes
  its own experts' part of the result for the tokens routed to them, by a
  grouped matrix product over assignments sorted by expert. No token is ever
  dropped: there is no capacity and no ``(T, E, C)`` one-hot. What the
  absent experts would add is left out (the chip's share of an
  expert-parallel deployment; the exchange is a later PR);
- ``-``  a dense relu^2 MLP (the family's other sizes use it);
- ``W``  ``*`` under a sliding window, with rotary positions: q and k turned
  by rotate-half over the whole head (``rope_theta``), and a query sees its
  latest ``window`` keys, itself included. The flash kernels walk only the
  tiles that hold such a pair (`ops.flash_attention`'s ``window``), so the
  layer's arithmetic follows the window and not the sequence. A model of
  ``*`` and ``W`` layers is the mix of global layers without positions and
  local rotary ones that several released decoders have;
- ``S``  grouped-query attention under a learned sparse-attention indexer
  (DeepSeek-V3.2's lightning indexer): a per-head RMS norm on q and k, then
  rotary positions over the whole head; an indexer of its own projections
  scores every causal (query, key) pair from the layer's input with the
  gradient stopped, and a query attends to the ``indexer_topk`` keys of
  largest score (all of them where it sees no more). The selection is made
  once a layer a step, OUTSIDE the layer's `jax.checkpoint`, and handed to
  `ops.flash_attention` as its ``selection`` operand; it carries no
  gradient, so under the LM loss the indexer's leaves stay where they are.

The expert layer's router scores by ``router_score`` (``sigmoid`` with a
selection bias and a scale, or a plain ``softmax`` renormalised over the
chosen), its experts are ``expert_act`` ``relu2`` (two matrices) or gated,
``silu`` or ``relu`` (``act(h G) * (h U)`` through ``D``, three matrices),
and ``shared_width`` 0 leaves the shared expert out. ``router_input`` says
what the router reads: the layer's ``own`` normed input, or that of the
``previous`` layer (an attention sublayer: the router of such a model
stands BEFORE attention and its experts after it); `_stack` then makes the
routing outside the layer's `jax.checkpoint` and hands it on, as it hands an
``S`` layer its selection. With ``router_frozen`` the routing weights carry
no gradient, to the router or through it: the router's leaves take a zero
gradient and stay (a fine-tune with the routers frozen).

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

KINDS = "ME*-SW"

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


_M_KEYS_SELECTED = _REG.counter(
    "edl_sparse_keys_selected_total",
    "Keys the sparse-attention selection kept, over the queries of the "
    "batches asked about, by layer", labelnames=("layer",))
_M_KEYS_VISIBLE = _REG.counter(
    "edl_sparse_keys_visible_total",
    "Keys those queries could see under the causal mask alone, by layer",
    labelnames=("layer",))
_M_KEYS_FUTURE = _REG.counter(
    "edl_sparse_keys_future_total",
    "Selected keys that lie after their query (always 0)",
    labelnames=("layer",))
_M_ROWS_MISCOUNTED = _REG.counter(
    "edl_sparse_rows_miscounted_total",
    "Queries whose selection does not hold min(t + 1, topk) keys (always 0)",
    labelnames=("layer",))


_M_PAIRS_VISIBLE = _REG.counter(
    "edl_window_pairs_visible_total",
    "(query, key) pairs an attention layer's core saw, over the queries of "
    "the batches asked about: under its window where it has one, by layer",
    labelnames=("layer",))
_M_PAIRS_CAUSAL = _REG.counter(
    "edl_window_pairs_causal_total",
    "Pairs those queries see under causality alone, by layer",
    labelnames=("layer",))


@dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 512
    d_model: int = 64
    #: one character a layer: M, E, *, -, S, W
    pattern: str = "ME*E-M"
    seq_len: int = 64
    norm_eps: float = 1e-5
    #: standard deviation of the embedding's initial rows (every other
    #: matrix starts at 0.02)
    embed_std: float = 0.02
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
    # -- *, S and W: grouped-query attention
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    # -- S and W: rotary base
    rope_theta: float = 10000.0
    # -- W alone: keys a query sees, itself included
    window: int = 16
    # -- S alone: the indexer that selects a query's keys
    indexer_heads: int = 4
    indexer_head_dim: int = 16
    indexer_topk: int = 16
    # -- E: experts. `n_experts` is the router's published width; the layer
    # holds `experts_count` of them from `experts_first` on
    n_experts: int = 8
    experts_first: int = 0
    experts_count: int = 8
    top_k: int = 2
    expert_width: int = 32
    #: 0: no shared expert
    shared_width: int = 64
    routed_scale: float = 2.5
    #: ``sigmoid`` (selection bias, renormalised, scaled) or ``softmax``
    #: (over all published experts, renormalised over the chosen, scaled)
    router_score: str = "sigmoid"
    #: ``relu2`` (``relu(h U)^2 D``), or gated: ``silu`` or ``relu``
    #: (``(act(h G) * (h U)) D``)
    expert_act: str = "relu2"
    #: what the router reads: the layer's ``own`` normed input, or the
    #: ``previous`` layer's (an attention sublayer's)
    router_input: str = "own"
    #: the routing weights carry no gradient, to the router or through it
    router_frozen: bool = False
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
    if cfg.router_score not in ("sigmoid", "softmax"):
        raise ValueError(f"router_score {cfg.router_score!r}")
    if cfg.expert_act not in ("relu2", *_GATES):
        raise ValueError(f"expert_act {cfg.expert_act!r}")
    if cfg.router_input not in ("own", "previous"):
        raise ValueError(f"router_input {cfg.router_input!r}")
    if cfg.router_input == "previous" and any(
            kind == "E" and (i == 0 or cfg.pattern[i - 1] not in "*SW")
            for i, kind in enumerate(cfg.pattern)):
        raise ValueError(
            f"pattern {cfg.pattern!r}: with router_input 'previous' an E "
            "layer's router reads the normed input of the attention layer "
            "(*, S or W) before it, and an E layer here has none")
    if "S" in cfg.pattern and (cfg.head_dim % 2 or cfg.indexer_head_dim % 2
                               or cfg.indexer_topk < 1):
        raise ValueError("rotary positions pair the halves of an even head; "
                         "indexer_topk is at least 1")
    if "W" in cfg.pattern and (cfg.head_dim % 2 or cfg.window < 1):
        raise ValueError("rotary positions pair the halves of an even head; "
                         "a window is at least the query's own key")


# -- parameters ---------------------------------------------------------------------


def _layer_shapes(cfg: HybridConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    D = cfg.d_model
    if kind == "M":
        H, inner = cfg.mamba_heads, cfg.mamba_inner
        return {"norm": (D,), "in_proj": (D, inner + cfg.conv_dim + H),
                "conv_w": (cfg.conv_dim, cfg.conv_kernel),
                "conv_b": (cfg.conv_dim,), "dt_bias": (H,), "A_log": (H,),
                "D": (H,), "gate_norm": (inner,), "out_proj": (inner, D)}
    if kind in "*W":
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        return {"norm": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
                "wo": (q, D)}
    if kind == "S":
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        Hi, Di = cfg.indexer_heads, cfg.indexer_head_dim
        return {"norm": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
                "wo": (q, D), "q_norm": (cfg.head_dim,),
                "k_norm": (cfg.head_dim,), "ix_wq": (D, Hi * Di),
                "ix_wk": (D, Di), "ix_ww": (D, Hi), "ix_norm": (Di,),
                "ix_norm_b": (Di,)}
    if kind == "E":
        F, Fs, n = cfg.expert_width, cfg.shared_width, cfg.experts_count
        shapes = {"norm": (D,), "router": (cfg.n_experts, D),
                  "w_up": (n, D, F), "w_down": (n, F, D)}
        if cfg.router_score == "sigmoid":
            shapes["router_bias"] = (cfg.n_experts,)
        if cfg.expert_act in _GATES:  # gate and up side by side: [G | U]
            shapes["w_up"] = (n, D, 2 * F)
        if Fs:
            shapes.update(shared_up=(D, Fs), shared_down=(Fs, D))
        return shapes
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
        if name in ("norm", "gate_norm", "D", "q_norm", "k_norm", "ix_norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name in ("conv_b", "router_bias", "ix_norm_b"):
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
        "embed": jax.random.normal(k_embed, (V, D), jnp.float32)
        * cfg.embed_std,
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


#: the gates of a gated expert, by ``expert_act``
_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


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


def _attention(cfg: HybridConfig, h: jax.Array, p: dict,
               window: Optional[int] = None) -> jax.Array:
    """``q = h W_q`` (n_heads x head_dim), ``k, v = h W_k, h W_v`` (n_kv_heads
    x head_dim), no bias, no positional encoding; causal softmax of ``q k^T /
    sqrt(head_dim)``, query head j on K/V head ``j // (n_heads/n_kv_heads)``;
    ``out = a W_o``. The flash kernels take K and V as projected, a K/V
    head's group of query heads to a grid step; the plain path repeats them
    to the query heads, which is exact. With a ``window`` (a ``W`` layer)
    ``q, k = rope(q), rope(k)`` and a query sees its latest ``window`` keys
    alone; the core's scope then reads ``attn_window`` where a global
    layer's reads ``attn_full``, both inside ``attn_core``."""
    Bz, S, _ = h.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn_proj"):
        if window is None:
            q = _mm("bsd,de->bse", h, p["wq"], out=bf16).reshape(Bz, S, Hq, Dh)
            k = _mm("bsd,de->bse", h, p["wk"], out=bf16).reshape(Bz, S, Hkv, Dh)
        else:  # the rotation in float32, the MXU's operands in bf16
            q = _rope(_mm("bsd,de->bse", h, p["wq"]).reshape(Bz, S, Hq, Dh),
                      cfg.rope_theta).astype(bf16)
            k = _rope(_mm("bsd,de->bse", h, p["wk"]).reshape(Bz, S, Hkv, Dh),
                      cfg.rope_theta).astype(bf16)
        v = _mm("bsd,de->bse", h, p["wv"], out=bf16).reshape(Bz, S, Hkv, Dh)
    with jax.named_scope("attn_core"), jax.named_scope(
            "attn_full" if window is None else "attn_window"):
        a = _attend(cfg, q, k, v, window)
    with jax.named_scope("attn_proj"):
        return _mm("bse,ed->bsd", a.reshape(Bz, S, Hq * Dh), p["wo"])


def _attend(cfg: HybridConfig, q, k, v, window=None):
    """Causal softmax attention of q (B, S, Hq, Dh) over k, v (B, S, Hkv,
    Dh), a query on its latest ``window`` keys where one is given: the flash
    kernels, or the plain path on K and V repeated to the query heads."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if cfg.flash:
        from edl_tpu.ops import flash_attention

        return flash_attention(q, k, v, causal=True, scale=scale,
                               window=window)
    from edl_tpu.parallel.ring_attention import dense_attention

    k, v = (jnp.repeat(a, q.shape[2] // k.shape[2], axis=2) for a in (k, v))
    return dense_attention(q, k, v, causal=True, scale=scale, window=window)


def _window_attention(cfg: HybridConfig, h: jax.Array, p: dict) -> jax.Array:
    """A ``W`` layer: `_attention` with rotary positions on q and k and the
    configuration's window."""
    return _attention(cfg, h, p, cfg.window)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions 0..S-1 over the whole last axis of x (B, S, ..., d),
    rotate-half pairing: element i < d/2 pairs with i + d/2, both turned by
    the angle ``t theta^(-2i/d)``. float32 in, float32 out."""
    S, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freq  # (S, d/2)
    angle = angle.reshape((1, S) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _indexer_proj(cfg: HybridConfig, h: jax.Array, p: dict):
    """The indexer's three projections of h (B, S, D) bf16: ``qI = rope(h
    W_qI)`` (B, S, Hi, Di) and ``kI = rope(LayerNorm(h W_kI))`` (B, S, Di),
    both bf16 for the MXU, and the head weights ``w = h W_w`` (B, S, Hi)
    float32 with the score's two scales, ``Di^-0.5 Hi^-0.5``, folded in."""
    Bz, S, _ = h.shape
    Hi, Di = cfg.indexer_heads, cfg.indexer_head_dim
    qI = _mm("bsd,de->bse", h, p["ix_wq"]).reshape(Bz, S, Hi, Di)
    kI = _layernorm(_mm("bsd,de->bse", h, p["ix_wk"]), p["ix_norm"],
                    p["ix_norm_b"], cfg.norm_eps)
    w = _mm("bsd,dh->bsh", h, p["ix_ww"]) * (Di ** -0.5 * Hi ** -0.5)
    return (_rope(qI, cfg.rope_theta).astype(bf16),
            _rope(kI, cfg.rope_theta).astype(bf16), w)


def _scores(qI, kI, w):
    """``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``, TRANSPOSED: (B, S
    keys, S queries) float32, by the Pallas kernel `ops.sparse_select.
    indexer_scores` (bf16 products accumulated in float32, relu and the
    head-weighted sum in float32 on the VPU). Pairs in a query's future hold
    anything."""
    from edl_tpu.ops.sparse_select import indexer_scores

    return indexer_scores(qI, kI, w)


def _select(scores_t: jax.Array, topk: int) -> jax.Array:
    """Of every query's causal scores (column t of ``scores_t`` (B, S keys, S
    queries)) the ``min(t + 1, topk)`` largest, ties to the earlier key
    (what a stable sort or `jax.lax.top_k` keeps): int8 of the same shape,
    by the Pallas kernel `ops.sparse_select.top_k_select`: the k-th largest
    of a query by bisection on the float's bits, 32 counting passes over a
    block that stays in VMEM. A sort of every row, and the same passes in
    plain XLA, cost this chip more (PERF.md, Findings, PR 32)."""
    from edl_tpu.ops.sparse_select import top_k_select

    return top_k_select(scores_t, topk)


def _selection(cfg: HybridConfig, x: jax.Array, p: dict) -> jax.Array:
    """Which keys each query of an ``S`` layer attends to, int8 (B, S, S),
    from the layer's input x (B, S, D) with the gradient stopped: the
    layer's own pre-norm, the indexer's projections, `_scores`, `_select`.
    The kernels take whole 128-lane rows of queries: a shorter sequence is
    padded with positions after every real one, which no real query can
    select."""
    Bz, S, _ = x.shape
    with jax.named_scope("indexer"):
        h = _rmsnorm(jax.lax.stop_gradient(x), p["norm"],
                     cfg.norm_eps).astype(bf16)
        with jax.named_scope("indexer_proj"):
            # no gradient reaches the indexer's leaves: the selection is a
            # comparison, and the kernels below have no derivative
            qI, kI, w = (jax.lax.stop_gradient(jnp.pad(
                a, ((0, 0), (0, -S % 128)) + ((0, 0),) * (a.ndim - 2)))
                for a in _indexer_proj(cfg, h, p))
        with jax.named_scope("indexer_scores"):
            scores_t = _scores(qI, kI, w)
    with jax.named_scope("attn_select"):
        return _select(scores_t, cfg.indexer_topk)[:, :S, :S].swapaxes(1, 2)


def _sparse_attention(cfg: HybridConfig, h: jax.Array, p: dict,
                      selection: jax.Array) -> jax.Array:
    """``q = rope(rmsnorm_head(h W_q))``, ``k = rope(rmsnorm_head(h W_k))``
    (a learned scale over each head's ``head_dim``), ``v = h W_v``, no bias;
    softmax of ``q k^T / sqrt(head_dim)`` over the keys ``selection`` (B, S,
    S) keeps for the query (all of them causal), query head j on K/V head
    ``j // (n_heads/n_kv_heads)``; ``out = a W_o``."""
    Bz, S, _ = h.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn_proj"):
        q = _mm("bsd,de->bse", h, p["wq"]).reshape(Bz, S, Hq, Dh)
        k = _mm("bsd,de->bse", h, p["wk"]).reshape(Bz, S, Hkv, Dh)
        v = _mm("bsd,de->bse", h, p["wv"], out=bf16).reshape(Bz, S, Hkv, Dh)
        q = _rope(_rmsnorm(q, p["q_norm"], cfg.norm_eps),
                  cfg.rope_theta).astype(bf16)
        k = _rope(_rmsnorm(k, p["k_norm"], cfg.norm_eps),
                  cfg.rope_theta).astype(bf16)
    with jax.named_scope("attn_core"):
        scale = 1.0 / math.sqrt(Dh)
        if cfg.flash:  # K and V as projected: a group a grid step
            from edl_tpu.ops import flash_attention

            a = flash_attention(q, k, v, causal=True, scale=scale,
                                selection=selection)
        else:  # explicit scores under the selection's mask
            k, v = (jnp.repeat(a, Hq // Hkv, axis=2) for a in (k, v))
            s = _mm("bqhd,bkhd->bhqk", q, k) * scale
            s = jnp.where(selection[:, None] != 0, s, -jnp.inf)
            a = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, out=bf16)
    with jax.named_scope("attn_proj"):
        return _mm("bse,ed->bsd", a.reshape(Bz, S, Hq * Dh), p["wo"])


def _route(cfg: HybridConfig, tok: jax.Array, p: dict):
    """The router, in float32, over all the published experts: ``s =
    sigmoid(tok W_r^T)``; the top k of ``s + b`` (the selection bias chooses
    and takes no gradient); weights ``s[chosen] / (sum + 1e-20) x scale``.
    With ``router_score`` ``softmax``: ``s = softmax(tok W_r^T)``, the top k
    of ``s``, weights ``s[chosen] / sum x scale``.
    Returns chosen experts (T, k) int32 and their weights (T, k)."""
    logits = jnp.einsum("td,ed->te", tok.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "softmax":  # no bias: the probabilities choose
        s = jax.nn.softmax(logits, axis=-1)
        picked, chosen = jax.lax.top_k(s, cfg.top_k)
        return chosen.astype(jnp.int32), picked / picked.sum(
            -1, keepdims=True) * cfg.routed_scale
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


def _experts_of(rows, w_up, w_down, sizes, act: str = "relu2"):
    """``relu(rows U_g)^2 V_g``, g a row's group; zeros past the groups.
    With ``act`` ``silu`` or ``relu`` the experts are gated, ``w_up`` holds
    ``[G_g | U_g]`` side by side and one grouped product gives both halves:
    ``(act(rows G_g) * (rows U_g)) V_g``."""
    held = jnp.arange(rows.shape[0]) < sizes.sum()
    up = _grouped(rows, w_up, sizes, held)
    if act in _GATES:
        gate, up = jnp.split(up.astype(jnp.float32), 2, axis=-1)
        return _grouped((_GATES[act](gate) * up).astype(bf16), w_down, sizes,
                        held)
    act = _relu2(up.astype(jnp.float32)).astype(bf16)
    return _grouped(act, w_down, sizes, held)


#: sorted assignments that one pass of `_experts_held` computes: a third more
#: than uniform routing sends the experts held at the benchmark's sizes
#: (6,144 of 98,304 a layer)
_ROW_TILE = 8192


def _row_tile(cfg: "HybridConfig", assignments: int) -> int:
    """Sorted assignments one pass of `_experts_held` computes, of a layer's
    ``assignments`` (tokens x top_k): `_ROW_TILE`, or the power-of-two
    multiple of it that holds a third more than uniform routing sends the
    experts held (8,192 of 98,304 where 6,144 are held at uniform routing;
    32,768 of 131,072 where 16,384 are). While the held assignments stay
    under it a layer runs ONE pass whatever the router does, and the step's
    time does not follow the routing."""
    uniform = assignments * cfg.experts_count / cfg.n_experts
    tiles = max(1, math.ceil(uniform * 4 / 3 / _ROW_TILE))
    return math.gcd(assignments, _ROW_TILE * (1 << (tiles - 1).bit_length()))


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


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _experts_held(tok, weights, w_up, w_down, order, sizes, tile: int,
                  act: str = "relu2"):
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
                             tile, act)[0]


def _experts_held_loop(tok, weights, w_up, w_down, order, sizes, tile,
                       act="relu2"):
    """`_experts_held`, and how many sorted assignments its product gave a
    value other than zero (`_route_stats` counts the dropped by it)."""
    up, down = w_up.astype(bf16), w_down.astype(bf16)

    def one(i, carry):
        out, computed = carry
        _, token, rows, w, mine = _tile(tok, weights, order, sizes, i, tile)
        with jax.named_scope("moe_experts"):
            part = _experts_of(rows, up, down, mine, act)
        with jax.named_scope("moe_combine"):
            return (out.at[token].add(part.astype(jnp.float32) * w[:, None]),
                    computed + jnp.sum(jnp.any(part != 0, axis=1),
                                       dtype=jnp.int32))

    return jax.lax.fori_loop(
        0, _passes(sizes, tile), one,
        (jnp.zeros(tok.shape, jnp.float32), jnp.zeros((), jnp.int32)))


def _experts_held_fwd(tok, weights, w_up, w_down, order, sizes, tile,
                      act="relu2"):
    out, _ = _experts_held_loop(tok, weights, w_up, w_down, order, sizes,
                                tile, act)
    return out, (tok, weights, w_up, w_down, order, sizes)


def _experts_held_bwd(tile, act, res, g):
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
                lambda r, u, d: _experts_of(r, u, d, mine, act), rows, up,
                down)
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
                         _row_tile(cfg, chosen.size), cfg.expert_act)


def _routing(cfg: HybridConfig, tok: jax.Array, p: dict):
    """`_route` of the tokens (T, D) bf16, rows of the router's normed
    input, under the scope ``moe_route``; with ``router_frozen`` the weights
    carry no gradient, to the router or to the tokens."""
    with jax.named_scope("moe_route"):
        chosen, weights = _route(cfg, tok, p)
    if cfg.router_frozen:
        weights = jax.lax.stop_gradient(weights)
    return chosen, weights


def _moe(cfg: HybridConfig, h: jax.Array, p: dict, *routing) -> jax.Array:
    """``out = sum over e chosen and held of w_e f_e(h) + f_shared(h)``,
    ``f(h) = relu(h U)^2 V`` (or the gated ``(act(h G) * (h U)) V``; no
    shared term where ``shared_width`` is 0). h (B, S, D) bf16. ``routing``:
    the ``(chosen, weights)`` that `_stack` made from another layer's input
    (``router_input`` ``previous``); else the router reads h."""
    Bz, S, D = h.shape
    tok = h.reshape(Bz * S, D)
    chosen, weights = routing or _routing(cfg, tok, p)
    routed = _routed(cfg, tok, p["w_up"], p["w_down"], chosen, weights)
    if not cfg.shared_width:
        return routed.reshape(Bz, S, D)
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
           "E": ("moe", _moe), "-": ("dense_mlp", _mlp),
           "S": ("attn", _sparse_attention), "W": ("attn", _window_attention)}


def _layer(cfg: HybridConfig, kind: str, x: jax.Array, p: dict,
           *handed) -> jax.Array:
    """``x + mixer(rmsnorm(x))``; x (B, S, D) bf16. An ``S`` layer is
    ``handed`` its selection too, an ``E`` layer whose router reads another
    layer's input its routing."""
    scope, mixer = _MIXERS[kind]
    with jax.named_scope(scope):
        h = _rmsnorm(x, p["norm"], cfg.norm_eps).astype(bf16)
        return x + mixer(cfg, h, p, *handed).astype(bf16)


def _stack(cfg: HybridConfig, params: dict, tokens: jax.Array, visit=None):
    """Embedding and the layers; ``visit(name, x, *routing)`` sees each
    layer's input, and the routing of an ``E`` layer that was handed one."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(bf16)
    before = None  # the layer before: its input and its pre-norm's scale
    for name in cfg.layer_names:
        kind, p = name[-1], params["layers"][name]
        # What a layer is handed is an ARGUMENT of its checkpoint: made once
        # a step and kept for the backward pass, never made again. An E
        # layer whose router reads the layer before's normed input gets its
        # routing so, an S layer its selection.
        handed = ()
        if kind == "E" and cfg.router_input == "previous":
            with jax.named_scope("moe"):
                handed = _routing(cfg, _rmsnorm(*before, cfg.norm_eps).astype(
                    bf16).reshape(-1, cfg.d_model), p)
        if visit is not None:
            visit(name, x, *handed)
        fn = partial(_layer, cfg, kind)
        if cfg.remat:
            fn = jax.checkpoint(fn)
        if kind == "S":
            with jax.named_scope("attn"):
                handed = (_selection(cfg, x, p),)
        before = (x, p["norm"])
        x = fn(x, p, *handed)
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


# -- routing and selection statistics --------------------------------------------------


def _route_stats(cfg: HybridConfig, x: jax.Array, p: dict, *routing) -> dict:
    """What one E layer does with its input x, through the layer's own
    routing (or the ``routing`` `_stack` handed it), dispatch plan and
    grouped product: the assignments made, those to held experts, each held
    expert's rows, and ``dropped``: the held assignments less the rows that
    the held experts' product gave a value other than zero
    (`_experts_held_loop`, what the step runs)."""
    first, count = cfg.experts_held
    h = _rmsnorm(x, p["norm"], cfg.norm_eps).astype(bf16)
    tok = h.reshape(-1, cfg.d_model)
    chosen, weights = routing or _route(cfg, tok, p)
    order, sizes = _dispatch_plan(chosen, cfg.experts_held)
    _, computed = _experts_held_loop(
        tok, weights, p["w_up"], p["w_down"], order, sizes,
        _row_tile(cfg, chosen.size), cfg.expert_act)
    to_held = jnp.sum((chosen >= first) & (chosen < first + count),
                      dtype=jnp.int32)
    return {"made": jnp.asarray(chosen.size, jnp.int32), "held": to_held,
            "per_expert": sizes, "dropped": to_held - computed}


def sampled_rows(seq_len: int, topk: int, spread: int = 13) -> Tuple[int, ...]:
    """Query positions `selection_stats` reports in full: the first, three
    about ``topk`` (the last row that keeps every key, the first that drops
    one), ``spread`` evenly over the sequence, and the last."""
    rows = {0, seq_len - 1, topk // 2, topk - 1, topk, topk + 1}
    rows |= {round(i * (seq_len - 1) / (spread - 1)) for i in range(spread)}
    return tuple(sorted(r for r in rows if 0 <= r < seq_len))


def _select_stats(cfg: HybridConfig, rows, x: jax.Array, p: dict) -> dict:
    """What one S layer's selection does with its input x, through the
    layer's own `_selection`: counters over every query, for the query
    positions ``rows`` the keys picked, the layer's input, and the selection
    itself."""
    picked = _selection(cfg, x, p)  # (B, S, S)
    Bz, S, _ = picked.shape
    t = jnp.arange(S, dtype=jnp.int32)
    count = jnp.sum(picked, axis=-1, dtype=jnp.int32)  # (B, S)
    future = jnp.sum(jnp.where(t[None, :] > t[:, None], picked, 0),
                     dtype=jnp.int32)
    return {"selected": jnp.sum(count), "visible": Bz * jnp.sum(t + 1),
            "future": future,
            "miscounted": jnp.sum(
                count != jnp.minimum(t + 1, cfg.indexer_topk),
                dtype=jnp.int32),
            "input": x, "picked": picked[:, jnp.asarray(rows, jnp.int32)],
            "selection": picked}


def _layer_stats(cfg: HybridConfig, rows, params: dict, tokens: jax.Array):
    """``({E layer: _route_stats}, {S layer: _select_stats})`` of one batch
    from ONE forward pass: both hooks below run this one program, so a
    model with both kinds of layer compiles one forward pass for them and
    not two (20 s less a run of the sparse cell; my chip run, PR 32)."""
    routing, selection = {}, {}

    def visit(name, x, *handed):
        if name[-1] == "E":
            routing[name] = _route_stats(cfg, x, params["layers"][name],
                                         *handed)
        elif name[-1] == "S":
            selection[name] = _select_stats(cfg, rows, x,
                                            params["layers"][name])

    _stack(cfg, params, tokens, visit=visit)
    return routing, selection


#: keys a call of `_pairs_seen` counts over: ``exp(log n)`` through the
#: chip's float32 ``log`` and ``exp`` rounds to n with room to spare up to a
#: few thousand keys, and no longer at 16,384 (106 of 16,384 rows read one
#: key off there; my chip run, PR 35)
_PAIRS_CHUNK = 2048


def _pairs_seen(cfg: HybridConfig, batch: int, window=None) -> jax.Array:
    """How many keys the queries of ``batch`` sequences see in all, counted
    by the attention core's own mask and loops. The flash kernels: with q
    and k zeros every key a query sees scores 0, so the row's logsumexp is
    the log of their number (the sentinel where it sees none), `_PAIRS_
    CHUNK` keys a call at their global positions. The plain path: the mask
    `dense_attention` builds, summed."""
    S = cfg.seq_len
    if cfg.flash:
        from edl_tpu.ops import flash_attention

        C = math.gcd(S, _PAIRS_CHUNK)
        q, k = (jnp.zeros((batch, rows, 1, cfg.head_dim), bf16)
                for rows in (S, C))

        def chunk(first):
            _, lse = flash_attention(q, k, k, causal=True, return_lse=True,
                                     k_offset=first, window=window)
            return jnp.sum(jnp.round(jnp.exp(lse)).astype(jnp.int32))

        return jnp.sum(jax.lax.map(chunk, jnp.arange(0, S, C)))
    from edl_tpu.parallel.ring_attention import visible_pairs

    return batch * jnp.sum(visible_pairs(S, window), dtype=jnp.int32)


def make_layer_stats(cfg: HybridConfig):
    """The hooks of a model, none ever part of the train step: two over one
    jitted forward pass (`_layer_stats`), one over the attention core alone.

    ``routing_stats(params, batch) -> {layer: {made, held, per_expert,
    dropped}}`` as host numbers, and the same into the metrics registry.

    ``selection_stats(params, batch, whole=False) -> {layer: {...}}`` for a
    model with S layers, else None. Per layer, as host numbers: ``rows``
    (the query positions sampled: `sampled_rows`), ``picked`` (B, rows, S)
    int8 (the keys those queries attend to), ``input`` (B, S, D) (the
    layer's input, what the indexer saw), and the counters ``selected``,
    ``visible``, ``future`` and ``miscounted`` over every query of the
    batch, which also go into the metrics registry. With ``whole`` also
    ``selection``: the layer's whole selection (B, S, S) int8, left ON THE
    DEVICE (a byte a pair: 256 MiB a layer at 16k).

    ``window_stats(params, batch) -> {layer: {visible, causal}}`` for a
    model with W layers, else None: for every ``*`` and ``W`` layer the
    (query, key) pairs its core sees over the batch's queries, under its
    window where it has one, and under causality alone; exact counts, made
    by the core itself (`_pairs_seen`: the parameters and the tokens do not
    enter), as host numbers and into the metrics registry."""
    rows = sampled_rows(cfg.seq_len, cfg.indexer_topk) \
        if "S" in cfg.pattern else ()
    run = jax.jit(partial(_layer_stats, cfg, rows))

    def routing_stats(params, batch) -> Dict[str, dict]:
        got = jax.device_get(run(params, batch["tokens"])[0])
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

    def selection_stats(params, batch, whole: bool = False) -> Dict[str, dict]:
        out = {}
        for layer, st in run(params, batch["tokens"])[1].items():
            selection = st.pop("selection")
            st = jax.device_get(st)
            out[layer] = dict(
                {k: int(st[k]) for k in ("selected", "visible", "future",
                                         "miscounted")},
                rows=rows, picked=st["picked"], input=st["input"])
            if whole:
                out[layer]["selection"] = selection
            _M_KEYS_SELECTED.inc(out[layer]["selected"], layer=layer)
            _M_KEYS_VISIBLE.inc(out[layer]["visible"], layer=layer)
            _M_KEYS_FUTURE.inc(out[layer]["future"], layer=layer)
            _M_ROWS_MISCOUNTED.inc(out[layer]["miscounted"], layer=layer)
        return out

    pairs = jax.jit(lambda batch: {
        "W": _pairs_seen(cfg, batch, cfg.window),
        "*": _pairs_seen(cfg, batch)}, static_argnums=0)

    def window_stats(params, batch) -> Dict[str, dict]:
        got = jax.device_get(pairs(len(batch["tokens"])))
        out = {}
        for layer in cfg.layer_names:
            if layer[-1] in got:
                out[layer] = {"visible": int(got[layer[-1]]),
                              "causal": int(got["*"])}
                _M_PAIRS_VISIBLE.inc(out[layer]["visible"], layer=layer)
                _M_PAIRS_CAUSAL.inc(out[layer]["causal"], layer=layer)
        return out

    return (routing_stats, selection_stats if rows else None,
            window_stats if "W" in cfg.pattern else None)


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
    (attention's, the indexer's scores, and the SSD's within a chunk), an S
    layer's attention over the keys selected alone with its indexer apart
    (``indexer``: it runs forward only), a W layer's over the keys inside
    its window alone, routed experts at ``top_k x held /
    published`` of a token."""
    D, S = cfg.d_model, cfg.seq_len
    H, Pd, G, N, Q = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups,
                      cfg.state_size, cfg.chunk_size)
    inner = cfg.mamba_inner
    q = cfg.n_heads * cfg.head_dim
    kv = cfg.n_kv_heads * cfg.head_dim
    ssd = 0.5 * (2 * Q * N * G + 2 * Q * Pd * H) + 2 * (2 * Pd * N * H)
    Hi, Di, K = cfg.indexer_heads, cfg.indexer_head_dim, cfg.indexer_topk
    # keys a query attends to under the selection, averaged over positions
    kept = (min(K, S) * (min(K, S) + 1) / 2 + max(S - K, 0) * K) / S
    mats = 3 if cfg.expert_act in _GATES else 2
    w = min(cfg.window, S)  # keys a W layer's query sees, averaged likewise
    seen = (w * (w + 1) / 2 + (S - w) * w) / S
    return {
        "W": 2 * D * (q + 2 * kv) + 2 * q * D + 4 * seen * q,
        "S": 2 * D * (q + 2 * kv) + 2 * q * D + 4 * kept * q,
        "indexer": 2 * D * (Hi * Di + Di + Hi) + 0.5 * 2 * S * Hi * Di,
        "M": 2 * D * (inner + cfg.conv_dim + H) + 2 * cfg.conv_kernel
        * cfg.conv_dim + ssd + 2 * inner * D,
        "*": 2 * D * (q + 2 * kv) + 2 * q * D + 0.5 * 4 * S * q,
        "E": 2 * D * cfg.n_experts + 4 * D * cfg.shared_width
        + cfg.top_k * cfg.experts_count / cfg.n_experts
        * 2 * mats * D * cfg.expert_width,
        "-": 4 * D * cfg.mlp_width,
        "head": 2 * D * cfg.vocab_size,
    }


def _flops_per_step(cfg: HybridConfig, batch_size: int) -> float:
    """Train-step model FLOPs (`models.base` convention: backward twice the
    forward, recompute not counted; an S layer's indexer takes no gradient
    and counts its forward pass alone)."""
    per = forward_flops_per_token(cfg)
    forward = sum(per[kind] for kind in cfg.pattern) + per["head"]
    return (3.0 * forward + cfg.pattern.count("S") * per["indexer"]) \
        * cfg.seq_len * batch_size


def make_model(cfg: Optional[HybridConfig] = None, **overrides) -> Model:
    cfg = cfg or HybridConfig(**overrides)
    _check(cfg)
    routing_stats, selection_stats, window_stats = make_layer_stats(cfg)
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
        routing_stats=routing_stats,
        selection_stats=selection_stats,
        window_stats=window_stats,
    )


#: default zoo instance: the tiny preset of the CPU tests
MODEL = make_model()
