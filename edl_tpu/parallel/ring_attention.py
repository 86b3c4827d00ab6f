"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

The reference predates long context (its longest "sequence" is a 5-gram
window, `example/fit_a_line/train_ft.py:26`); this framework makes sequence
parallelism first-class. Q/K/V live sharded on the sequence dimension across
the ``seq`` axis; each device computes attention for its local query block
while K/V blocks rotate around the ring via `jax.lax.ppermute`, one hop per
step, overlapping the ICI transfer with the block matmuls. Softmax is the
blockwise online form (flash-attention accumulation): running max ``m``,
numerator ``num`` and denominator ``den`` are updated per visiting block, so
the full (S, S) score matrix never materializes and memory stays
O(S_local^2 / n_shards) per device.

Causality is enforced with *global* positions reconstructed from the ring
topology: the block arriving at step ``i`` originated on device
``(my_index - i) mod n``, so its key positions are known statically per step
and the mask costs one compare, no communication.

The public entrypoint wraps its own `shard_map`; `_ring_attention_local` is
the inside-a-shard_map form reused by models that are already manual over the
mesh (e.g. `edl_tpu.models.transformer`).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

#: scores below this are "masked"; finite so exp() is exactly 0 without nans.
_NEG_INF = -1e30


def visible_pairs(S: int, window: Optional[int] = None) -> jax.Array:
    """(S, S) bool, row t column s: causal query t sees key s; under a
    ``window`` only its latest ``window`` keys, itself included."""
    pos = jnp.arange(S)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[:, None] - pos[None, :] < window
    return seen


def dense_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    scale: Optional[float] = None, window: Optional[int] = None,
) -> jax.Array:
    """Reference O(S^2)-memory attention. q/k/v: (B, S, H, D).

    The correctness oracle for the ring kernel and the single-device
    fallback; f32 softmax regardless of input dtype. Under a ``window`` (a
    causal call's) a query sees its latest ``window`` keys, itself included.
    """
    B, S, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if window is not None and not causal:
        raise ValueError("dense_attention: a window bounds a CAUSAL query's "
                         "keys from below")
    if causal:
        s = jnp.where(visible_pairs(S, window), s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out.astype(q.dtype)


def _ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    seq_axis: str,
    n_shards: int,
    causal: bool = True,
    scale: Optional[float] = None,
    flash: bool = False,
) -> jax.Array:
    """Ring attention over local shards — call inside a shard_map whose manual
    axes include ``seq_axis``. q/k/v: (B, S_local, H_local, D).

    ``flash``: run every block's attention through the Pallas kernel
    (`edl_tpu.ops.flash_attention`) instead of the einsum engine — the
    unsharded case directly, the ring case via per-hop (out, lse) pairs
    merged associatively (gradients flow through the kernel's lse)."""
    B, S, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if n_shards == 1:
        if flash:
            from edl_tpu.ops import flash_attention

            return flash_attention(q, k, v, causal=causal, scale=scale)
        return dense_attention(q, k, v, causal=causal, scale=scale)
    if flash:
        return _ring_flash_local(
            q, k, v, seq_axis=seq_axis, n_shards=n_shards, causal=causal,
            scale=scale,
        )

    my = jax.lax.axis_index(seq_axis)
    q_pos = my * S + jnp.arange(S)  # global positions of local queries
    ring = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    qf = q.astype(jnp.float32)

    def accumulate(acc, k_blk, v_blk, src):
        """Fold one visiting K/V block into the online-softmax accumulator."""
        m, num, den = acc
        k_pos = src * S + jnp.arange(S)
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32)
        ) * scale
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]  # (S_q, S_k)
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))  # (B, H, S_q)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])  # (B, H, S_q, S_k)
        num = num * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )
        den = den * alpha + p.sum(axis=-1)
        return m_new, num, den

    def step(carry, i):
        k_blk, v_blk, acc = carry
        # Rotate first: the last step's output IS consumed, so exactly
        # n_shards-1 hops move each block all the way around the ring.
        k_blk = jax.lax.ppermute(k_blk, seq_axis, ring)
        v_blk = jax.lax.ppermute(v_blk, seq_axis, ring)
        acc = accumulate(acc, k_blk, v_blk, src=(my - i) % n_shards)
        return (k_blk, v_blk, acc), None

    m0 = jnp.full((B, H, S), _NEG_INF, jnp.float32)
    num0 = jnp.zeros((B, H, S, D), jnp.float32)
    den0 = jnp.zeros((B, H, S), jnp.float32)
    acc0 = accumulate((m0, num0, den0), k, v, src=my)  # local block, hop 0
    (_, _, (_, num, den)), _ = jax.lax.scan(
        step, (k, v, acc0), jnp.arange(1, n_shards)
    )
    out = num / den[..., None]  # (B, H, S_q, D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _ring_flash_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    seq_axis: str,
    n_shards: int,
    causal: bool,
    scale: float,
) -> jax.Array:
    """Ring attention with the Pallas kernel as the per-hop block engine.

    Each visiting K/V block runs through `flash_attention(return_lse=True)`
    with global offsets; hops merge associatively in (out, lse) form:
    ``lse' = logaddexp(lse_a, lse_b)``, ``out' = out_a e^{lse_a - lse'} +
    out_b e^{lse_b - lse'}``. Blocks with no visible keys report the finite
    masked sentinel, whose weight underflows to exactly 0 in the merge, so
    no special-casing. Gradients flow through the kernel's custom VJP for
    both outputs."""
    from edl_tpu.ops import flash_attention

    B, S, H, D = q.shape
    my = jax.lax.axis_index(seq_axis)
    ring = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def block(k_blk, v_blk, src):
        return flash_attention(
            q, k_blk, v_blk, causal=causal, scale=scale,
            q_offset=my * S, k_offset=src * S, return_lse=True,
        )

    def merge(acc, blk):
        (oa, la), (ob, lb) = acc, blk
        lse = jnp.logaddexp(la, lb)  # (B, H, S)
        wa = jnp.exp(la - lse)[..., None].transpose(0, 2, 1, 3)
        wb = jnp.exp(lb - lse)[..., None].transpose(0, 2, 1, 3)
        return (
            oa.astype(jnp.float32) * wa + ob.astype(jnp.float32) * wb,
            lse,
        )

    def step(carry, i):
        k_blk, v_blk, acc = carry
        k_blk = jax.lax.ppermute(k_blk, seq_axis, ring)
        v_blk = jax.lax.ppermute(v_blk, seq_axis, ring)
        acc = merge(acc, block(k_blk, v_blk, src=(my - i) % n_shards))
        return (k_blk, v_blk, acc), None

    out0, lse0 = block(k, v, src=my)  # local block, hop 0
    acc0 = (out0.astype(jnp.float32), lse0)
    (_, _, (out, _)), _ = jax.lax.scan(
        step, (k, v, acc0), jnp.arange(1, n_shards)
    )
    return out.astype(q.dtype)


def _qkv_spec(mesh: Mesh, batch_axis: str, seq_axis: str, head_axis: str) -> P:
    """(B, S, H, D) spec using only axes the mesh actually has."""
    have = mesh.axis_names
    return P(
        batch_axis if batch_axis in have else None,
        seq_axis if seq_axis in have else None,
        head_axis if head_axis in have else None,
        None,
    )


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    batch_axis: str = "data",
    head_axis: str = "model",
    causal: bool = True,
    scale: Optional[float] = None,
    flash: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Sequence-parallel attention on a mesh. q/k/v: (B, S, H, D) global.

    Sharding: batch over ``batch_axis``, sequence over ``seq_axis``, heads
    over ``head_axis`` (attention is embarrassingly parallel over batch and
    heads; only the sequence axis communicates). Axes absent from the mesh
    are simply unsharded. With no ``seq_axis`` in the mesh this degrades to
    dense attention under `jit` sharding propagation (``flash=False``) or
    to the Pallas kernel on each device's local batch/head block inside a
    communication-free shard_map (``flash=True``). A ``window`` is refused:
    see below.
    """
    if window is not None:
        raise NotImplementedError(
            f"ring_attention: window {window}: the ring passes every K/V "
            "shard by every query shard and its hops know causality alone. "
            "A windowed layer under sequence parallelism needs hops that "
            "stop once a shard lies wholly before the window (a halo of "
            "ceil(window / shard) neighbours, not the ring): call "
            "`ops.flash_attention(..., window=...)` or `dense_attention` "
            "on an unsharded sequence")
    n_sp = mesh.shape[seq_axis] if seq_axis in mesh.axis_names else 1
    if n_sp == 1:
        if not flash:
            return dense_attention(q, k, v, causal=causal, scale=scale)
        # flash prefers the shard_map below even with no sequence sharding
        # (pallas_call has no SPMD partitioning rule, so on global arrays
        # XLA would replicate batch/head-sharded inputs), but shard_map
        # demands divisibility — an indivisible batch/head (e.g. a single
        # eval sequence on a data mesh) takes the global call instead,
        # which is always correct, just potentially replicated.
        B, _, H, _ = q.shape
        n_b = mesh.shape.get(batch_axis, 1)
        n_h = mesh.shape.get(head_axis, 1)
        if B % n_b or H % n_h:
            from edl_tpu.ops import flash_attention

            return flash_attention(q, k, v, causal=causal, scale=scale)
    spec = _qkv_spec(mesh, batch_axis, seq_axis, head_axis)
    kernel = partial(
        _ring_attention_local,
        seq_axis=seq_axis,
        n_shards=n_sp,
        causal=causal,
        scale=scale,
        flash=flash,
    )
    return jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
