"""Decoder-only transformer LM — the long-context / multi-axis flagship.

The reference's model zoo tops out at a 5-gram embedding model
(`example/fit_a_line/train_ft.py:41-99`); a modern elastic-training framework
must schedule transformer jobs, so this model exists to exercise every mesh
axis the parallel layer supports, together and composably:

- ``data``  — batch sharding; gradient all-reduce inserted by the optimizer jit.
- ``seq``   — sequence/context parallelism: activations sharded on the
  sequence dimension, attention via `ring_attention` (K/V blocks rotating on
  ICI with blockwise online softmax).
- ``model`` — megatron-style tensor parallelism: QKV/up projections
  column-sharded, output/down projections row-sharded, one `psum` after each
  (two per block), heads split across the axis.
- ``pipe``  — pipeline parallelism: the block stack's leading layer dim is
  sharded over the axis and executed with one of three microbatch schedules
  (GPipe via `edl_tpu.parallel.pipeline._pipeline_local`; plain or
  interleaved 1F1B via `pipeline_train_1f1b` — with ``virtual_stages > 1``
  each rank holds v NONCONTIGUOUS chunks of blocks, packed chunk-major by
  `interleaved_layout` at init), composing with ring attention and the TP
  psums inside each stage. MoE's load-balance aux loss rides every
  schedule (per-stage accumulation, psum over the pipe axis).

The whole forward/loss is ONE `shard_map` kernel, manual over the mesh: every
matmul below is written against local shards, so the collectives are explicit
and auditable rather than left to the partitioner — this is the pattern the
scaling-book recipe recommends once sequence parallelism enters, because the
partitioner cannot infer a ring schedule. Matmuls run in bfloat16 (MXU), norms
and softmax/loss in float32.

Token/position embeddings and the LM head are replicated (vocab is small next
to the block stack); the big sharded-table machinery lives in
`edl_tpu.parallel.ShardedEmbedding` and the CTR/word2vec models.
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
from edl_tpu.parallel.pipeline import (
    _pipeline_local,
    interleaved_layout,
    pipeline_train_1f1b,
)
from edl_tpu.parallel.ring_attention import _ring_attention_local
from edl_tpu.parallel.sharding import present_axes


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 2048
    seq_len: int = 1024
    #: one mesh axis or a hierarchy (e.g. ("dcn", "data") for multi-slice
    #: data parallelism — gradient reductions then ride DCN, everything
    #: else stays on ICI; see parallel.mesh.build_hierarchical_mesh)
    batch_axis: Union[str, Tuple[str, ...]] = "data"
    seq_axis: str = "seq"
    tp_axis: str = "model"
    pp_axis: str = "pipe"
    #: microbatches for the pipeline schedule; None = stage count.
    microbatches: Optional[int] = None
    #: "gpipe" (default: autodiff through the forward schedule, O(M)
    #: activation stash), "1f1b" (combined fwd/bwd scan, O(pp) stash), or
    #: "1f1b-interleaved" (combined scan over ``virtual_stages`` chunks per
    #: rank — bubble shrinks ~v-fold at fixed microbatches; see the
    #: edl_tpu.parallel.pipeline docstring and the committed
    #: BENCH_PIPELINE.json sweep for the measured economics).
    pipeline_schedule: str = "gpipe"
    #: virtual stage chunks per pipe rank, >1 only with
    #: pipeline_schedule="1f1b-interleaved". Requires n_layers divisible by
    #: pp * virtual_stages and microbatches divisible by pp. Block storage
    #: is then packed chunk-major (interleaved_layout) at init.
    virtual_stages: int = 1
    #: per-block rematerialization (`jax.checkpoint` around each block under
    #: the scan): the backward pass recomputes block activations instead of
    #: storing them, cutting live activation memory from O(n_layers) to O(1)
    #: per stage — the standard HBM-for-FLOPs trade that makes long-context
    #: training fit (scaling-book recipe; the reference has no analog).
    remat: bool = False
    #: Pallas flash-attention kernel (`edl_tpu.ops.flash_attention`):
    #: blockwise online softmax in VMEM, no (S, S) score materialization.
    #: Serves BOTH attention paths — the unsharded-sequence case directly,
    #: and the seq-sharded ring as its per-hop block engine (hops merge
    #: associatively in (out, lse) form, gradients flow through the
    #: kernel's differentiable lse). Interpret mode on CPU. The kernel
    #: blocks over the batch dim, so changing the per-call batch (e.g.
    #: `grad_accum_microbatches` slicing) reassociates the softmax/grad
    #: accumulation order — bit-exact single-step-vs-accumulated
    #: comparisons need `flash=False` (see tests/test_collective.py).
    flash: bool = True
    #: mixture-of-experts FFN: >0 replaces every block's dense FFN with
    #: `moe_experts` switch-routed (top-1) experts whose weights shard over
    #: ``expert_axis`` — token dispatch is an `all_to_all` on ICI, the
    #: dense-model completion of the embedding layer's expert story
    #: (`parallel.embedding`). 0 = dense FFN. Experts do not split over the
    #: tp axis (attention still does); capacity-dropped tokens pass through
    #: on the residual; `moe_aux_weight` adds the load-balance term.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    #: experts per token: 1 = switch routing, 2 = GShard-style top-2 with
    #: renormalized gates (choices slot in priority order — every token's
    #: first choice outranks any second choice for capacity).
    moe_top_k: int = 1
    expert_axis: str = "expert"
    #: switch load-balance auxiliary loss weight (Shazeer/Fedus form:
    #: E * sum_e f_e * p_e per layer, f = routed-token fraction, p = mean
    #: router prob). 0 = off. Works on every mesh, pipelined or not: under
    #: a pipe axis each stage accumulates its layers' aux over its real
    #: (stage, microbatch) executions, the schedules psum it over the pipe
    #: axis and fold the microbatch-mean into the loss. Note the pipelined
    #: form averages PER-MICROBATCH aux (routing fractions computed over
    #: batch/microbatches tokens) — statistically the same balance pressure
    #: as the whole-batch form, not bit-identical.
    moe_aux_weight: float = 0.0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _rmsnorm(x: jax.Array, scale: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (norm * scale).astype(x.dtype)


def _maybe_psum(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    return jax.lax.psum(x, axis) if axis in mesh.axis_names else x


def _block_spec(cfg: TransformerConfig, mesh: Mesh) -> Dict[str, P]:
    """Specs for the stacked (leading dim = n_layers) block params. The
    leading layer dim shards over the pipe axis: each pipeline rank holds
    its contiguous chunk of blocks."""
    tp = cfg.tp_axis if cfg.tp_axis in mesh.axis_names else None
    pp = cfg.pp_axis if cfg.pp_axis in mesh.axis_names else None
    spec = {
        "ln1": P(pp, None),
        "wqkv": P(pp, None, None, tp, None),  # (L, D, 3, H, Dh) col-sharded
        "bqkv": P(pp, None, tp, None),
        "wo": P(pp, tp, None, None),  # (L, H, Dh, D) row-sharded -> psum
        "bo": P(pp, None),
        "ln2": P(pp, None),
    }
    if cfg.moe_experts > 0:
        ep = cfg.expert_axis if cfg.expert_axis in mesh.axis_names else None
        spec.update({
            "router": P(pp, None, None),      # (L, D, E) replicated
            "w_up": P(pp, ep, None, None),    # (L, E, D, F) expert-sharded
            "b_up": P(pp, ep, None),
            "w_down": P(pp, ep, None, None),  # (L, E, F, D)
            "b_down": P(pp, ep, None),
        })
    else:
        spec.update({
            "win": P(pp, None, tp),  # (L, D, F) col-sharded
            "bin": P(pp, tp),
            "wout": P(pp, tp, None),  # (L, F, D) row-sharded -> psum
            "bout": P(pp, None),
        })
    return spec


def _param_spec(cfg: TransformerConfig, mesh: Mesh) -> dict:
    return {
        "embed": P(None, None),
        "pos": P(None, None),
        "blocks": _block_spec(cfg, mesh),
        "lnf": P(None),
        "head": P(None, None),
    }


def _init(cfg: TransformerConfig, key: jax.Array, mesh: Mesh) -> dict:
    tp = _axis_size(mesh, cfg.tp_axis)
    if cfg.n_heads % tp or cfg.d_ff % tp:
        raise ValueError(
            f"n_heads={cfg.n_heads} and d_ff={cfg.d_ff} must be divisible by tp={tp}"
        )
    if cfg.seq_len % _axis_size(mesh, cfg.seq_axis):
        raise ValueError(
            f"seq_len={cfg.seq_len} must be divisible by "
            f"sp={_axis_size(mesh, cfg.seq_axis)}"
        )
    n_pp = _axis_size(mesh, cfg.pp_axis)
    if cfg.n_layers % n_pp:
        raise ValueError(
            f"n_layers={cfg.n_layers} must be divisible by pp={n_pp}"
        )
    if cfg.pipeline_schedule not in ("gpipe", "1f1b", "1f1b-interleaved"):
        raise ValueError(
            f"unknown pipeline_schedule {cfg.pipeline_schedule!r}; "
            "expected 'gpipe', '1f1b' or '1f1b-interleaved'"
        )
    v = cfg.virtual_stages
    if v < 1:
        raise ValueError(f"virtual_stages={v} must be >= 1")
    if v > 1 and cfg.pipeline_schedule != "1f1b-interleaved":
        raise ValueError(
            f"virtual_stages={v} requires pipeline_schedule="
            f"'1f1b-interleaved', got {cfg.pipeline_schedule!r}"
        )
    if cfg.pipeline_schedule == "1f1b-interleaved" and n_pp > 1:
        if cfg.n_layers % (n_pp * v):
            raise ValueError(
                f"n_layers={cfg.n_layers} must be divisible by "
                f"pp*virtual_stages={n_pp * v} for the interleaved schedule"
            )
        if v > 1 and (cfg.microbatches or n_pp) % n_pp:
            raise ValueError(
                f"microbatches={cfg.microbatches} must be divisible by "
                f"pp={n_pp} for the interleaved schedule (microbatches are "
                f"injected in groups of pp)"
            )
    E = cfg.moe_experts
    if E > 0 and E % _axis_size(mesh, cfg.expert_axis):
        raise ValueError(
            f"moe_experts={E} must be divisible by "
            f"ep={_axis_size(mesh, cfg.expert_axis)}"
        )
    if E > 0 and not 1 <= cfg.moe_top_k <= E:
        raise ValueError(
            f"moe_top_k={cfg.moe_top_k} must be in [1, moe_experts={E}]"
        )
    D, H, Dh, F, L, V = (
        cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers,
        cfg.vocab_size,
    )
    ks = jax.random.split(key, 8)
    blocks = {
        "ln1": jnp.ones((L, D), jnp.float32),
        "wqkv": jax.random.normal(ks[2], (L, D, 3, H, Dh), jnp.float32)
        * math.sqrt(1.0 / D),
        "bqkv": jnp.zeros((L, 3, H, Dh), jnp.float32),
        "wo": jax.random.normal(ks[3], (L, H, Dh, D), jnp.float32)
        * math.sqrt(1.0 / D),
        "bo": jnp.zeros((L, D), jnp.float32),
        "ln2": jnp.ones((L, D), jnp.float32),
    }
    if E > 0:
        blocks.update({
            "router": jax.random.normal(ks[7], (L, D, E), jnp.float32) * 0.02,
            "w_up": jax.random.normal(ks[4], (L, E, D, F), jnp.float32)
            * math.sqrt(2.0 / D),
            "b_up": jnp.zeros((L, E, F), jnp.float32),
            "w_down": jax.random.normal(ks[5], (L, E, F, D), jnp.float32)
            * math.sqrt(1.0 / F),
            "b_down": jnp.zeros((L, E, D), jnp.float32),
        })
    else:
        blocks.update({
            "win": jax.random.normal(ks[4], (L, D, F), jnp.float32)
            * math.sqrt(2.0 / D),
            "bin": jnp.zeros((L, F), jnp.float32),
            "wout": jax.random.normal(ks[5], (L, F, D), jnp.float32)
            * math.sqrt(1.0 / F),
            "bout": jnp.zeros((L, D), jnp.float32),
        })
    if cfg.pipeline_schedule == "1f1b-interleaved" and v > 1 and n_pp > 1:
        # Chunk-major storage for the interleaved schedule: the row held at
        # storage position p is logical layer perm[p], so rank r's P(pipe)
        # shard carries its v noncontiguous chunks back to back. The
        # permutation depends on this mesh's pp — checkpoints restored onto
        # a mesh with a different pp (or schedule) need re-permuting, the
        # same caveat contiguous stage sharding already has.
        perm = interleaved_layout(L, n_pp, v)
        blocks = jax.tree_util.tree_map(lambda a: a[perm], blocks)
    host = {
        "embed": jax.random.normal(ks[0], (V, D), jnp.float32) * 0.02,
        "pos": jax.random.normal(ks[1], (cfg.seq_len, D), jnp.float32) * 0.02,
        "blocks": blocks,
        "lnf": jnp.ones((D,), jnp.float32),
        "head": jax.random.normal(ks[6], (D, V), jnp.float32) * 0.02,
    }
    spec = _param_spec(cfg, mesh)
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        host,
        spec,
        is_leaf=lambda x: isinstance(x, P),
    )


def _moe_ffn(cfg: TransformerConfig, mesh: Mesh, h: jax.Array, bp: dict):
    """Switch (top-1) mixture-of-experts FFN on local shards.

    ``h``: (Bl, Sl, D) bf16 normed activations. Expert weights arrive
    expert-sharded: (E_local, D, F) where E_local = E/ep. The classic
    einsum-dispatch formulation (Mesh-TensorFlow / Switch):

      1. route: per-token top-k experts (k=1 switch: gate = raw router
         prob, its only gradient path; k>1 GShard: gates renormalized
         over the surviving choices, first choices outranking seconds
         for capacity);
      2. dispatch einsum packs each expert's first-C tokens into static
         (E, C, D) slots (capacity-dropped tokens contribute nothing and
         ride the residual unchanged);
      3. `all_to_all` over the expert axis turns expert-major slots into
         device-major: every device receives ITS experts' slots from all
         ep peers — the MoE shuffle, on ICI;
      4. batched expert FFN over the E_local dim;
      5. reverse `all_to_all`, combine einsum (dispatch x gate) unpacks
         slots back to token positions.

    Without an expert axis (ep=1) the two collectives vanish and the same
    math runs locally — layout changes, math doesn't (tested invariant).
    """
    B, S, D = h.shape
    E, F = cfg.moe_experts, cfg.d_ff
    ep = _axis_size(mesh, cfg.expert_axis)
    T = B * S
    cap = max(1, math.ceil(cfg.moe_top_k * T / E * cfg.moe_capacity_factor))
    tok = h.reshape(T, D)

    logits = jnp.einsum(
        "td,de->te", tok.astype(jnp.float32), bp["router"]
    )  # (T, E) f32 — routing decisions deserve full precision
    probs = jax.nn.softmax(logits, axis=-1)
    k = cfg.moe_top_k
    # k successive argmaxes (masking each choice out) instead of top_k:
    # the one-hots are needed anyway and the loop is tiny and static
    remaining = probs
    onehots, gates = [], []
    for _ in range(k):
        choice = remaining.argmax(axis=-1)  # (T,)
        oh = jax.nn.one_hot(choice, E, dtype=jnp.int32)
        onehots.append(oh)
        gates.append(jnp.sum(probs * oh, axis=-1))
        remaining = remaining * (1 - oh)
    # switch load-balance aux on FIRST choices (the standard form):
    # E * sum_e f_e p_e is minimized (=1) by uniform routing
    aux = E * jnp.sum(
        jnp.mean(onehots[0].astype(jnp.float32), axis=0)
        * jnp.mean(probs, axis=0)
    )
    # capacity slots assigned in priority order: the cumsum runs over all
    # first choices before any second choice, so an oversubscribed expert
    # sheds k>1 traffic first (GShard semantics)
    oh_all = jnp.concatenate(onehots, axis=0)  # (k*T, E)
    pos = jnp.cumsum(oh_all, axis=0) * oh_all - 1  # slot index or -1
    keep = (pos >= 0) & (pos < cap)
    dispatch_all = (
        jax.nn.one_hot(jnp.clip(pos, 0, cap - 1), cap, dtype=jnp.bfloat16)
        * keep[..., None].astype(jnp.bfloat16)
    )  # (k*T, E, C)
    alive = dispatch_all.sum(axis=(1, 2)).reshape(k, T)  # 1 if slotted
    gate_k = jnp.stack(gates) * alive.astype(jnp.float32)  # (k, T)
    if k > 1:
        # GShard: renormalize over the surviving choices. NOT at k=1 —
        # switch scales by the raw router prob (that product is the
        # router's only gradient path; argmax has none).
        gate_k = gate_k / jnp.maximum(gate_k.sum(axis=0, keepdims=True),
                                      1e-9)
    dispatch = dispatch_all.reshape(k, T, E, cap).sum(axis=0)  # (T, E, C)
    combine_k = (
        dispatch_all.reshape(k, T, E, cap)
        * gate_k[:, :, None, None].astype(jnp.bfloat16)
    )
    combine = combine_k.sum(axis=0)  # (T, E, C)

    slots = jnp.einsum("tec,td->ecd", dispatch, tok)  # (E, C, D)
    if ep > 1:
        # expert-major -> device-major: each device keeps rows for its own
        # E_local experts and receives the matching rows from every peer,
        # concatenated along the slot dim -> (E_local, ep*C, D).
        slots = jax.lax.all_to_all(
            slots, cfg.expert_axis, split_axis=0, concat_axis=1, tiled=True
        )
    up = jnp.einsum("ecd,edf->ecf", slots, bp["w_up"].astype(jnp.bfloat16))
    act = jax.nn.gelu(up + bp["b_up"][:, None, :].astype(jnp.bfloat16))
    down = jnp.einsum(
        "ecf,efd->ecd", act, bp["w_down"].astype(jnp.bfloat16)
    ) + bp["b_down"][:, None, :].astype(jnp.bfloat16)
    if ep > 1:
        down = jax.lax.all_to_all(
            down, cfg.expert_axis, split_axis=1, concat_axis=0, tiled=True
        )
    out = jnp.einsum("ecd,tec->td", down, combine)  # (T, D)
    return out.reshape(B, S, D).astype(jnp.float32), aux


def _block(cfg: TransformerConfig, mesh: Mesh, n_sp: int, x: jax.Array, bp: dict):
    """One decoder block on local shards. x: (Bl, Sl, D) bf16."""
    Dh = cfg.head_dim
    B, S, D = x.shape
    # The scopes name the block's parts in every operation's `op_name`, so
    # a device trace can tell attention from the matmuls around it.
    h = _rmsnorm(x, bp["ln1"])
    with jax.named_scope("attn_proj"):
        qkv = (
            jnp.einsum(
                "bsd,dthe->bsthe", h, bp["wqkv"].astype(jnp.bfloat16)
            )
            + bp["bqkv"].astype(jnp.bfloat16)
        )  # (Bl, Sl, 3, Hl, Dh)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    with jax.named_scope("attn_core"):
        attn = _ring_attention_local(
            q, k, v, seq_axis=cfg.seq_axis, n_shards=n_sp, causal=True,
            scale=1.0 / math.sqrt(Dh), flash=cfg.flash,
        )  # (Bl, Sl, Hl, Dh)
    with jax.named_scope("attn_proj"):
        out = jnp.einsum("bshe,hed->bsd", attn, bp["wo"].astype(jnp.bfloat16))
        out = _maybe_psum(out.astype(jnp.float32), mesh, cfg.tp_axis) + bp["bo"]
    x = x + out.astype(jnp.bfloat16)
    h = _rmsnorm(x, bp["ln2"])
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("mlp"):
        if cfg.moe_experts > 0:
            o, aux = _moe_ffn(cfg, mesh, h, bp)
        else:
            f = jnp.einsum("bsd,df->bsf", h, bp["win"].astype(jnp.bfloat16))
            f = jax.nn.gelu(f + bp["bin"].astype(jnp.bfloat16))
            o = jnp.einsum("bsf,fd->bsd", f, bp["wout"].astype(jnp.bfloat16))
            o = _maybe_psum(o.astype(jnp.float32), mesh, cfg.tp_axis) + bp["bout"]
    return x + o.astype(jnp.bfloat16), aux


def _kernel(cfg: TransformerConfig, mesh: Mesh, params: dict, tokens, targets):
    """Full forward + mean cross-entropy on local shards."""
    n_sp = _axis_size(mesh, cfg.seq_axis)
    Sl = tokens.shape[1]
    my_sp = (
        jax.lax.axis_index(cfg.seq_axis) if cfg.seq_axis in mesh.axis_names else 0
    )
    pos = my_sp * Sl + jnp.arange(Sl)  # global positions of local tokens
    x = params["embed"][tokens] + params["pos"][pos]
    x = x.astype(jnp.bfloat16)

    block_fn = partial(_block, cfg, mesh, n_sp)
    if cfg.remat:
        # Checkpoint at block granularity: under the scan this stores only
        # each block's INPUT carry and recomputes its internals in backward.
        # prevent_cse=False: scan already provides the staging that makes
        # checkpoint's CSE barriers necessary elsewhere; keeping them would
        # block XLA fusion inside the block body for nothing.
        block_fn = jax.checkpoint(block_fn, prevent_cse=False)

    def stage(blocks_local, h):
        """Apply this rank's chunk of blocks — activation-only form (the
        per-block aux scalar is dropped; the schedules use stage_with_aux
        when a nonzero moe_aux_weight needs it carried)."""
        h, _ = jax.lax.scan(
            lambda c, bp: (block_fn(c, bp)[0], None),
            h,
            blocks_local,
        )
        return h

    def stage_with_aux(blocks_local, h):
        """Aux-carrying form: accumulates the MoE load-balance aux through
        the scan carry alongside the activations. Doubles as the pipeline
        stage function under moe_aux_weight > 0 — the schedules accumulate
        the returned per-stage value across real (stage, microbatch)
        executions and psum it over the pipe axis. The accumulator is
        shape (1,), not scalar: jax 0.4's shard_map transpose assigns
        residuals a leading-dim sharding, which a rank-0 residual cannot
        carry (_SpecError) — any input-dependent scalar in a
        differentiated scan carry trips it."""

        def body(carry, bp):
            h, aux_acc = carry
            h, aux = block_fn(h, bp)
            return (h, aux_acc + aux), None

        (h, aux), _ = jax.lax.scan(
            body, (h, jnp.zeros((1,), jnp.float32)), blocks_local
        )
        return h, aux

    def tail_loss(lnf, head, y, tgt):
        """Final norm + LM head + mean token cross-entropy (f32)."""
        h = _rmsnorm(y, lnf).astype(jnp.float32)
        logits = jnp.einsum("bsd,dv->bsv", h, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - gold)

    n_pp = _axis_size(mesh, cfg.pp_axis)
    use_aux = cfg.moe_experts > 0 and cfg.moe_aux_weight > 0
    # per-LAYER weight: stages accumulate per-block aux sums, the no-pipe
    # path sums over the whole stack — dividing by n_layers makes the term
    # a per-layer mean under every composition.
    aux_w = cfg.moe_aux_weight / cfg.n_layers if use_aux else 0.0
    if n_pp > 1 and cfg.pipeline_schedule in ("1f1b", "1f1b-interleaved"):
        # Combined-schedule pipeline: per-microbatch tail loss inside the
        # scan (the seed cotangent must exist while later microbatches are
        # still in forward — that interleaving is what bounds the
        # activation stash at O(pp * virtual_stages); see parallel.pipeline).
        v_eff = (
            cfg.virtual_stages
            if cfg.pipeline_schedule == "1f1b-interleaved" else 1
        )
        loss = pipeline_train_1f1b(
            stage_with_aux if use_aux else stage,
            lambda tp, y, tgt: tail_loss(tp[0], tp[1], y, tgt),
            cfg.pp_axis,
            n_pp,
            cfg.microbatches or n_pp,
            v_eff,
            aux_w,
            params["blocks"],
            (params["lnf"], params["head"]),
            x,
            targets,
        )
    else:
        if n_pp > 1:
            out = _pipeline_local(
                stage_with_aux if use_aux else stage,
                params["blocks"],
                x,
                pipe_axis=cfg.pp_axis,
                n_stages=n_pp,
                microbatches=cfg.microbatches or n_pp,
                stage_aux=use_aux,
            )
            x, aux = out if use_aux else (out, jnp.zeros((1,), jnp.float32))
        else:
            x, aux = stage_with_aux(params["blocks"], x)
        loss = tail_loss(params["lnf"], params["head"], x, targets)
        if use_aux:
            loss = loss + aux_w * aux[0]
    reduce_axes = (*present_axes(mesh, cfg.batch_axis),
                   *present_axes(mesh, cfg.seq_axis))
    return jax.lax.pmean(loss, reduce_axes) if reduce_axes else loss


def _batch_specs(cfg: TransformerConfig, mesh: Mesh) -> Dict[str, P]:
    dp = present_axes(mesh, cfg.batch_axis) or None  # P takes the tuple
    sp = cfg.seq_axis if cfg.seq_axis in mesh.axis_names else None
    return {"tokens": P(dp, sp), "targets": P(dp, sp)}


def _loss(cfg: TransformerConfig, params: dict, batch: dict, mesh: Mesh):
    specs = _batch_specs(cfg, mesh)
    return jax.shard_map(
        partial(_kernel, cfg, mesh),
        mesh=mesh,
        in_specs=(_param_spec(cfg, mesh), specs["tokens"], specs["targets"]),
        out_specs=P(),
        check_vma=False,
    )(params, batch["tokens"], batch["targets"])


def synthetic_batch(cfg: TransformerConfig, rng: np.random.Generator, batch_size: int):
    """PTB-style id streams: next-token prediction over seq_len tokens."""
    ids = rng.integers(
        0, cfg.vocab_size, (batch_size, cfg.seq_len + 1), dtype=np.int64
    ).astype(np.int32)
    return {"tokens": ids[:, :-1], "targets": ids[:, 1:]}


def _flops_per_step(cfg: TransformerConfig, batch_size: int) -> float:
    """Train-step model FLOPs (MFU numerator; see models.base convention).

    Per token forward: qkv 6D^2 + out-proj 2D^2 + ffn 4DF per layer, plus
    causal attention (QK^T and PV are 2*S*D each, halved by the mask) and
    the LM head 2DV. Backward = 2x forward; remat recompute excluded.
    """
    D, F, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    # MoE: each token visits moe_top_k experts' 4DF FFNs plus the router
    # matmul (capacity-dropped tokens still count — MFU numerator
    # convention, like remat).
    if cfg.moe_experts:
        ffn = cfg.moe_top_k * 4 * D * F + 2 * D * cfg.moe_experts
    else:
        ffn = 4 * D * F
    per_token = (
        L * (6 * D * D + 2 * D * D + ffn + 0.5 * (4 * cfg.seq_len * D))
        + 2 * D * cfg.vocab_size
    )
    return 3.0 * per_token * cfg.seq_len * batch_size


# -- LM serving: prefill / single-token decode --------------------------------
#
# Training runs the whole forward as one shard_map kernel; serving an LM is
# a different shape of work. Autoregressive traffic splits into two phases
# with opposite hardware profiles (the prefill/decode separation every
# production LM server makes):
#
# - **prefill** — the prompt's full causal forward, compute-bound, shaped
#   (batch bucket, seq bucket). It returns the per-layer K/V it computed so
#   decode never re-touches prompt tokens, plus the prompt's next token.
# - **decode** — one token per step, memory-bound: each call reads the
#   whole K/V cache once and appends one position. Its K/V write-back is
#   returned to the caller (shaped (L, B, H, Dh)) instead of updating a
#   cache in place, so the serving engine owns cache layout — per-stream
#   host caches make per-token batch-membership changes free.
#
# Both are pure fixed-shape functions of (params, int32 arrays), AOT-
# compilable per (batch bucket, seq bucket) with jit(...).lower().compile()
# — the serve tier's empty-dispatch-cache contract extends to LM traffic.
# They run replicated (the serving mesh gives non-data axes size 1), so no
# collectives appear; matmuls in bf16, norms/softmax/logits in f32, same
# discipline as the training kernel. Dense FFN only: MoE decode needs the
# expert all_to_all plumbed through the cache path (not yet built).


def lm_cache_shape(cfg: TransformerConfig) -> Tuple[int, int, int]:
    """(n_layers, n_heads, head_dim) — the per-token K/V geometry the
    serving tier sizes its block pool from."""
    return (cfg.n_layers, cfg.n_heads, cfg.head_dim)


def lm_cache_bytes_per_token(cfg: TransformerConfig) -> int:
    """HBM bytes one token slot of K+V occupies (bf16 cache)."""
    L, H, Dh = lm_cache_shape(cfg)
    return 2 * L * H * Dh * 2  # K and V, 2 bytes each (bfloat16)


def _check_lm_servable(cfg: TransformerConfig) -> None:
    if cfg.moe_experts > 0:
        raise NotImplementedError(
            "LM serving path covers dense FFN configs only (MoE decode "
            "needs the expert all_to_all plumbed through the cache path)"
        )


def _decode_attention(q, k_cache, v_cache, k_new, v_new, lengths, scale):
    """One token's attention over its cache plus itself.

    q/k_new/v_new: (B, H, Dh) bf16; caches (B, C, H, Dh) bf16; lengths
    (B,) int32 = tokens already IN the cache (the new token's position).
    Cache positions >= length are dead slots (pad garbage or not yet
    written) and are masked out; the new token always attends to itself.
    """
    C = k_cache.shape[1]
    scores = jnp.einsum(
        "bhe,bche->bhc", q.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) * scale
    valid = jnp.arange(C)[None, :] < lengths[:, None]  # (B, C)
    scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
    self_score = jnp.sum(
        q.astype(jnp.float32) * k_new.astype(jnp.float32), axis=-1
    )[..., None] * scale  # (B, H, 1)
    w = jax.nn.softmax(jnp.concatenate([scores, self_score], axis=-1), axis=-1)
    out = jnp.einsum(
        "bhc,bche->bhe", w[..., :C], v_cache.astype(jnp.float32)
    ) + w[..., C:] * v_new.astype(jnp.float32)
    return out.astype(jnp.bfloat16)


def make_decode_step(cfg: TransformerConfig):
    """Single-token decode: (params, k_cache, v_cache, tokens, lengths) ->
    (next_tokens, k_new, v_new).

    Shapes: caches (L, B, C, H, Dh) bf16 — C is the stream's seq-bucket
    capacity; ``tokens`` (B,) the last emitted token ids; ``lengths`` (B,)
    the token count already cached (== the new token's position). Returns
    greedy-argmax next tokens (B,) int32 and the new position's per-layer
    K/V (L, B, H, Dh) for the caller to append at index ``lengths``.
    """
    _check_lm_servable(cfg)
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def step(params, k_cache, v_cache, tokens, lengths):
        x = (params["embed"][tokens] + params["pos"][lengths]).astype(
            jnp.bfloat16
        )  # (B, D)

        def body(x, layer):
            bp, k_c, v_c = layer
            h = _rmsnorm(x, bp["ln1"])
            qkv = (
                jnp.einsum("bd,dthe->bthe", h, bp["wqkv"].astype(jnp.bfloat16))
                + bp["bqkv"].astype(jnp.bfloat16)
            )  # (B, 3, H, Dh)
            q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]
            attn = _decode_attention(q, k_c, v_c, k_new, v_new, lengths, scale)
            out = jnp.einsum("bhe,hed->bd", attn, bp["wo"].astype(jnp.bfloat16))
            x = x + (out.astype(jnp.float32) + bp["bo"]).astype(jnp.bfloat16)
            h = _rmsnorm(x, bp["ln2"])
            f = jnp.einsum("bd,df->bf", h, bp["win"].astype(jnp.bfloat16))
            f = jax.nn.gelu(f + bp["bin"].astype(jnp.bfloat16))
            o = jnp.einsum("bf,fd->bd", f, bp["wout"].astype(jnp.bfloat16))
            x = x + (o.astype(jnp.float32) + bp["bout"]).astype(jnp.bfloat16)
            return x, (k_new.astype(jnp.bfloat16), v_new.astype(jnp.bfloat16))

        x, (k_appended, v_appended) = jax.lax.scan(
            body, x, (params["blocks"], k_cache, v_cache)
        )
        h = _rmsnorm(x, params["lnf"]).astype(jnp.float32)
        logits = jnp.einsum("bd,dv->bv", h, params["head"])
        return (
            jnp.argmax(logits, axis=-1).astype(jnp.int32),
            k_appended,
            v_appended,
        )

    return step


def make_prefill_step(cfg: TransformerConfig):
    """Prompt prefill: (params, tokens, lengths) ->
    (next_tokens, k_cache, v_cache).

    ``tokens`` (B, S) right-padded int32 prompts, ``lengths`` (B,) real
    token counts. Full causal attention over the padded bucket (pad
    positions compute dead K/V the decode mask never reads); returns the
    per-layer K/V for all S positions as (L, B, S, H, Dh) bf16 and the
    greedy next token read at position ``lengths - 1``.
    """
    _check_lm_servable(cfg)
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def step(params, tokens, lengths):
        B, S = tokens.shape
        pos = jnp.arange(S)
        x = (params["embed"][tokens] + params["pos"][pos]).astype(jnp.bfloat16)
        causal = pos[None, :] <= pos[:, None]  # (S, S) keys <= queries

        def body(x, bp):
            h = _rmsnorm(x, bp["ln1"])
            qkv = (
                jnp.einsum("bsd,dthe->bsthe",
                           h, bp["wqkv"].astype(jnp.bfloat16))
                + bp["bqkv"].astype(jnp.bfloat16)
            )
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            scores = jnp.einsum(
                "bshe,bthe->bhst", q.astype(jnp.float32),
                k.astype(jnp.float32)
            ) * scale
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            w = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum(
                "bhst,bthe->bshe", w, v.astype(jnp.float32)
            ).astype(jnp.bfloat16)
            out = jnp.einsum("bshe,hed->bsd",
                             attn, bp["wo"].astype(jnp.bfloat16))
            x = x + (out.astype(jnp.float32) + bp["bo"]).astype(jnp.bfloat16)
            h = _rmsnorm(x, bp["ln2"])
            f = jnp.einsum("bsd,df->bsf", h, bp["win"].astype(jnp.bfloat16))
            f = jax.nn.gelu(f + bp["bin"].astype(jnp.bfloat16))
            o = jnp.einsum("bsf,fd->bsd", f, bp["wout"].astype(jnp.bfloat16))
            x = x + (o.astype(jnp.float32) + bp["bout"]).astype(jnp.bfloat16)
            return x, (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))

        x, (k_cache, v_cache) = jax.lax.scan(body, x, params["blocks"])
        last = jnp.clip(lengths - 1, 0, S - 1)
        h_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        h_last = _rmsnorm(h_last, params["lnf"]).astype(jnp.float32)
        logits = jnp.einsum("bd,dv->bv", h_last, params["head"])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), k_cache, v_cache

    return step


def make_model(cfg: Optional[TransformerConfig] = None, **overrides) -> Model:
    cfg = cfg or TransformerConfig(**overrides)
    return Model(
        name="transformer",
        init=lambda key, mesh: _init(cfg, key, mesh),
        loss_fn=lambda params, batch, mesh: _loss(cfg, params, batch, mesh),
        param_spec=lambda mesh: _param_spec(cfg, mesh),
        synthetic_batch=lambda rng, bs: synthetic_batch(cfg, rng, bs),
        batch_spec=lambda mesh: _batch_specs(cfg, mesh),
        label_keys=("targets",),
        config=cfg,
        flops_per_step=lambda bs: _flops_per_step(cfg, bs),
    )


#: default zoo instance — a small LM whose shapes still tile the MXU (512/8
#: heads, 2048 ff) and divide cleanly over dp/sp/tp meshes up to 8x8x8.
MODEL = make_model()
