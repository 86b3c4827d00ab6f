"""Flash attention: a Pallas TPU kernel for blockwise-online attention.

The transformer's hot op. The plain path (`parallel.ring_attention.
dense_attention`) materializes the (S, S) score matrix per head — O(S^2)
HBM traffic and memory; this kernel streams K/V blocks through VMEM with
the online-softmax recurrence (running max / numerator / denominator), so
scores never leave on-chip memory and the sequence-length memory cost is
O(S) per head. The matmuls hit the MXU with f32 accumulation
(``preferred_element_type``); the elementwise recurrence rides the VPU.

Causality uses GLOBAL positions (``q_offset`` / ``k_offset``), so the ring
layer can hand the kernel any (query block, key block) pair with the same
masking semantics as `_ring_attention_local`'s compare — the kernel is the
within-block engine; `ppermute` stays the between-device engine.

Backward is the standard two-kernel flash recipe: forward also emits the
per-row logsumexp ``L = m + log(den)``; backward recomputes ``P = exp(S -
L)`` blockwise (never storing it) with ``delta = rowsum(dO * O)`` folded
in: dS = P * (dP - delta) * scale, dQ = dS K, dK = dS^T Q, dV = P^T dO.

Shapes follow the models' convention: q/k/v are (B, S, H, D). Blocks are
multiples of 128 rows; unaligned sequence lengths pad up to the block size:
padded KEY rows are masked by a valid-length compare; padded QUERY rows
produce unobserved garbage and are sliced away.

Which engine runs the kernels follows the platform each program is LOWERED
for (`_pallas_call`): lowered for a TPU — attached or merely described —
they compile through Mosaic; lowered for the CPU (tests, the virtual-device
mesh) the same kernel bodies run in the Pallas interpreter, under a named
scope that shows in the program's text. The interpreter checks the
kernels' arithmetic; only the TPU's compiler checks that they lower
(`tests/test_tpu_compile.py`) and only the chip that they are right there
(`chip_smoke.py`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

#: finite "masked" score: exp() is exactly 0.0 without nan risk
_NEG_INF = -1e30



def _pallas_call(kernel, name, **kwargs):
    """`pl.pallas_call` whose execution mode follows the platform the
    program is LOWERED for, never the process's default backend: a program
    lowered for a TPU (attached, or a described topology) gets the Mosaic
    kernel, a program lowered for the CPU gets the Pallas interpreter, and
    no setting can route a TPU program through the interpreter. The
    interpreted branch is named, so `compiled.as_text()` shows
    ``flash_attention_interpreted`` where it was taken and
    ``tpu_custom_call`` where it was not.

    ``name`` (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``) is the
    kernel's stable name: Mosaic's kernel name, which names the custom call
    in a device trace, and a named scope in the `op_name` of whatever
    implements the call, so a reduction finds the kernel after a refactor
    renumbers the program's instructions."""
    mosaic = pl.pallas_call(kernel, name=name, **kwargs)
    interpreted = pl.pallas_call(kernel, name=name, interpret=True, **kwargs)

    def on_cpu(*args):
        with jax.named_scope("flash_attention_interpreted"):
            return interpreted(*args)

    def call(*args):
        with jax.named_scope(name):
            return jax.lax.platform_dependent(
                *args, cpu=on_cpu, default=mosaic)

    return call


def _pad_seq(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[1]) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))


def _positions(start, shape, dim):
    return start + jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _masked_scores(q, k, *, q_start, k_start, k_origin, k_len, scale,
                   causal, blk_q, blk_k, transposed=False):
    """Shared by all three kernels: f32 scores with invalid entries at the
    ``_NEG_INF`` sentinel, plus the validity mask itself. ``transposed``
    gives both as (blk_k, blk_q) — keys on sublanes, queries on lanes —
    which is the orientation the backward kernels work in.

    Callers must mask their exp() THROUGH ``valid`` (``where(valid,
    exp(...), 0)``), never infer it back from the scores: a fully-masked
    row's running max / lse lands exactly on the sentinel, so
    ``exp(s - m)`` would be 1 there, not 0."""
    lhs, rhs = (k, q) if transposed else (q, k)
    shape = (blk_k, blk_q) if transposed else (blk_q, blk_k)
    q_dim, k_dim = (1, 0) if transposed else (0, 1)
    s = jax.lax.dot_general(
        lhs, rhs, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    k_pos = _positions(k_start, shape, k_dim)
    valid = k_pos - k_origin < k_len  # mask padded key rows
    if causal:
        q_pos = _positions(q_start, shape, q_dim)
        valid = jnp.logical_and(valid, k_pos <= q_pos)
    return jnp.where(valid, s, _NEG_INF), valid


# -- forward -------------------------------------------------------------------
#
# Per-query-row statistics (running max, denominator, logsumexp, delta)
# cross HBM as ROWS: shape (BH, 1, Sq), block (1, 1, blk_q). Mosaic wants
# a block's last two dims divisible by (8, 128) or equal to the array's;
# (1, blk_q) over (1, Sq) is, (1, blk_q) over (BH, Sq) is not, and a
# (blk_q, 1) column would pad every value to a 128-lane row in HBM.


def _fwd_kernel(qo_ref, ko_ref, kl_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, blk_q, blk_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qo_ref[0] + qi * blk_q  # global position of this block's row 0
    k_start = ko_ref[0] + ki * blk_k

    # Skip K blocks entirely in this Q block's causal future.
    live = (not causal) or (k_start <= q_start + (blk_q - 1))

    @pl.when(live)
    def _block():
        q = q_ref[0]  # (blk_q, D)
        k = k_ref[0]  # (blk_k, D)
        v = v_ref[0]
        s, valid = _masked_scores(
            q, k, q_start=q_start, k_start=k_start, k_origin=ko_ref[0],
            k_len=kl_ref[0], scale=scale, causal=causal,
            blk_q=blk_q, blk_k=blk_k,
        )
        m_prev = m_ref[:, :1]  # (blk_q, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # (blk_q, blk_k) f32
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_k - 1)
    def _emit():
        l = l_ref[...]  # (blk_q, 128), every lane the same value
        # fully-masked (padded) query rows: den 0 -> emit 0, lse -inf
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe[:, :1]).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m_ref[...] + jnp.log(safe), _NEG_INF)
        # column -> row: the lane-broadcast tile transposes on the XLU to
        # (128, blk_q), whose every sublane is the row we want.
        lse_ref[0] = lse.T[:1]


def _row_spec(blk_q, index_map):
    return pl.BlockSpec((1, 1, blk_q), index_map)


def _fwd(q3, k3, v3, qo, ko, kl, *, scale, causal, blk_q, blk_k,
         out_dtype):
    """q3: (BH, Sq, D); k3/v3: (BH, Sk, D) -> (o3, lse (BH, 1, Sq) f32)."""
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k
    )
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    return _pallas_call(
        kernel, "flash_fwd",
        grid=(BH, Sq // blk_q, Sk // blk_k),
        in_specs=[
            scalar, scalar, scalar,
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            _row_spec(blk_q, lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), out_dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((blk_q, 128), jnp.float32),  # running denominator l
            pltpu.VMEM((blk_q, D), jnp.float32),  # output accumulator
        ],
    )(qo, ko, kl, q3, k3, v3)


# -- backward ------------------------------------------------------------------
#
# Both kernels work on TRANSPOSED tiles, (blk_k, blk_q): the per-query
# lse/delta rows then broadcast down the sublanes as they arrive, and
# dV = P^T dO and dK = dS^T Q become plain matmuls.


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *, q_start,
              k_start, k_origin, k_len, scale, causal, blk_q, blk_k):
    """(P^T, dS^T, dO) for one (key block, query block) pair, f32."""
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0].astype(jnp.float32)  # (blk_q, D)
    s_t, valid = _masked_scores(
        q, k, q_start=q_start, k_start=k_start, k_origin=k_origin,
        k_len=k_len, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
        transposed=True,
    )
    p_t = jnp.where(valid, jnp.exp(s_t - lse_ref[0]), 0.0)  # (blk_k, blk_q)
    dp_t = jax.lax.dot_general(
        v.astype(jnp.float32), do, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds_t = p_t * (dp_t - delta_ref[0]) * scale
    return p_t, ds_t, do


def _bwd_dq_kernel(qo_ref, ko_ref, kl_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, acc_ref,
                   *, scale, causal, blk_q, blk_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qo_ref[0] + qi * blk_q
    k_start = ko_ref[0] + ki * blk_k
    live = (not causal) or (k_start <= q_start + (blk_q - 1))

    @pl.when(live)
    def _block():
        _, ds_t, _ = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start=q_start, k_start=k_start, k_origin=ko_ref[0],
            k_len=kl_ref[0], scale=scale, causal=causal,
            blk_q=blk_q, blk_k=blk_k,
        )
        acc_ref[...] += jax.lax.dot_general(
            ds_t, k_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dS K: (blk_q, D)

    @pl.when(ki == n_k - 1)
    def _emit():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qo_ref, ko_ref, kl_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, blk_q, blk_k):
    ki, qi = pl.program_id(1), pl.program_id(2)  # note: K outer, Q inner
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qo_ref[0] + qi * blk_q
    k_start = ko_ref[0] + ki * blk_k
    live = (not causal) or (k_start <= q_start + (blk_q - 1))

    @pl.when(live)
    def _block():
        p_t, ds_t, do = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start=q_start, k_start=k_start, k_origin=ko_ref[0],
            k_len=kl_ref[0], scale=scale, causal=causal,
            blk_q=blk_q, blk_k=blk_k,
        )
        dv_acc[...] += jnp.dot(
            p_t, do, preferred_element_type=jnp.float32
        )  # P^T dO: (blk_k, D)
        dk_acc[...] += jnp.dot(
            ds_t, q_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # dS^T Q: (blk_k, D)

    @pl.when(qi == n_q - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q3, k3, v3, o3, lse, do3, dlse, qo, ko, kl, *, scale, causal,
         blk_q, blk_k):
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    # dL/ds_ij = p_ij (dp_ij - delta_i) for the out path PLUS p_ij * dlse_i
    # for the lse path (dlse/ds = softmax row) — the lse cotangent folds
    # into delta with a sign flip. dlse is zeros when lse wasn't consumed.
    delta = jnp.sum(
        do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1
    )[:, None, :] - dlse.astype(jnp.float32)  # (BH, 1, Sq)

    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    q_spec = pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0))
    row_spec = _row_spec(blk_q, lambda b, i, j: (b, 0, i))
    k_spec = pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0))

    dq = _pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k),
        "flash_bwd_dq",
        grid=(BH, Sq // blk_q, Sk // blk_k),
        in_specs=[scalar, scalar, scalar,
                  q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q3.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
    )(qo, ko, kl, q3, k3, v3, do3, lse, delta)

    # K outer / Q inner: the accumulators belong to the K block.
    q_spec_t = pl.BlockSpec((1, blk_q, D), lambda b, j, i: (b, i, 0))
    row_spec_t = _row_spec(blk_q, lambda b, j, i: (b, 0, i))
    k_spec_t = pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0))
    dk, dv = _pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k),
        "flash_bwd_dkv",
        grid=(BH, Sk // blk_k, Sq // blk_q),
        in_specs=[scalar, scalar, scalar,
                  q_spec_t, k_spec_t, k_spec_t, q_spec_t,
                  row_spec_t, row_spec_t],
        out_specs=[
            pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k3.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, D), jnp.float32),
            pltpu.VMEM((blk_k, D), jnp.float32),
        ],
    )(qo, ko, kl, q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


# -- public entrypoint ---------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9)
)
def _flash(q3, k3, v3, offsets, kl, scale, causal, blk_q, blk_k, out_dtype):
    qo, ko = offsets
    return _fwd(q3, k3, v3, qo, ko, kl, scale=scale, causal=causal,
                blk_q=blk_q, blk_k=blk_k, out_dtype=out_dtype)


def _flash_fwd(q3, k3, v3, offsets, kl, scale, causal, blk_q, blk_k,
               out_dtype):
    qo, ko = offsets
    o3, lse = _fwd(q3, k3, v3, qo, ko, kl, scale=scale, causal=causal,
                   blk_q=blk_q, blk_k=blk_k, out_dtype=out_dtype)
    return (o3, lse), (q3, k3, v3, o3, lse, qo, ko, kl)


def _flash_bwd(scale, causal, blk_q, blk_k, out_dtype, res, cts):
    q3, k3, v3, o3, lse, qo, ko, kl = res
    do3, dlse = cts
    dq, dk, dv = _bwd(q3, k3, v3, o3, lse, do3, dlse, qo, ko, kl,
                      scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset=0,
    k_offset=0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    return_lse: bool = False,
):
    """Blockwise-online attention. q: (B, Sq, H, D); k/v: (B, Sk, H, D).

    ``q_offset``/``k_offset`` are the GLOBAL positions of row 0 (ints or
    traced scalars) — sequence-parallel callers pass their shard offsets
    and causality is evaluated in global coordinates, exactly like
    `_ring_attention_local`'s mask. Differentiable via the flash backward
    kernels (custom VJP), including through the logsumexp when
    ``return_lse=True`` (returns ``(out, lse)``: out stays f32 so ring
    hops merge at accumulator precision — callers downcast once after the
    final merge; lse is (B, H, Sq) f32, with rows that see no keys at the
    finite ``_NEG_INF`` sentinel) — the ring layer merges per-hop
    (out, lse) pairs associatively and gradients flow through both.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    # Unpinned blocks resolve through the on-chip-swept tuning table
    # (ops/flash_tuning.py); 128x128 wherever the table is silent.
    if block_q is None or block_k is None:
        from edl_tpu.ops import flash_tuning

        tq, tk = flash_tuning.lookup(Sk, D, q.dtype)
        block_q = block_q if block_q is not None else tq
        block_k = block_k if block_k is not None else tk

    def round_up(n, m):
        return ((n + m - 1) // m) * m

    # Tile alignment: both extents are multiples of 128 (scores and their
    # transposes are whole MXU tiles, statistics rows are whole lane rows);
    # short sequences shrink the block to one tile and pad up to it, with
    # padded keys masked via the valid-length compare.
    blk_q = min(block_q, round_up(Sq, 128))
    blk_k = min(block_k, round_up(Sk, 128))

    def to3(x):  # (B, S, H, D) -> (B*H, S, D)
        Bx, Sx, Hx, Dx = x.shape
        return x.transpose(0, 2, 1, 3).reshape(Bx * Hx, Sx, Dx)

    q3 = _pad_seq(to3(q), blk_q)
    k3 = _pad_seq(to3(k), blk_k)
    v3 = _pad_seq(to3(v), blk_k)

    qo = jnp.asarray([q_offset], jnp.int32)
    ko = jnp.asarray([k_offset], jnp.int32)
    kl = jnp.asarray([Sk], jnp.int32)  # valid key length (pre-padding)

    # With lse (the ring's hop engine) the partial output stays f32: hops
    # merge at accumulator precision and the CALLER downcasts once after
    # the final merge — the same discipline the einsum ring engine had.
    out_dtype = jnp.float32 if return_lse else q.dtype
    o3, lse3 = _flash(q3, k3, v3, (qo, ko), kl, scale, causal,
                      blk_q, blk_k, jnp.dtype(out_dtype))
    out = o3[:, :Sq].reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    if not return_lse:
        return out
    return out, lse3[:, 0, :Sq].reshape(B, H, Sq)
