"""Flash attention: three Pallas TPU kernels for blockwise-online attention.

The transformer's hot op. The plain path (`parallel.ring_attention.
dense_attention`) materializes the (S, S) score matrix per head — O(S^2)
HBM traffic and memory; these kernels stream K/V through VMEM with the
online-softmax recurrence (running max / numerator / denominator), so
scores never leave on-chip memory and the sequence-length memory cost is
O(S) per head.

Tiling (see "tiling" below): a grid step holds one block of the operand
that owns the accumulator and a SPAN of up to `_MAX_SPAN` rows of the one
that streams past it; a loop inside the step walks the span in TILES of
(block_q x block_k) scores and stops at the causal diagonal, so a call is
some hundreds to a few thousand grid steps whatever the sequence, and a
tile no query can see costs neither a step nor a DMA. The tile is `_TILE`
square, clamped to the sequence.

Arithmetic: every matmul feeds the MXU operands of the INPUT's dtype (P,
P^T and dS are rounded to it at their matmul, as the dense oracle rounds P)
and accumulates in f32 (``preferred_element_type``); scores, exp, the
running statistics, the accumulators, lse and delta are f32 on the VPU.
`flash_fwd` and `flash_bwd_dkv` work on TRANSPOSED tiles (keys on sublanes,
queries on lanes), so per-query statistics are dense lane rows and the
reductions over keys run down the sublanes; `flash_bwd_dq` works on (query,
key) tiles and turns its two statistics rows into columns once a step.

Causality uses GLOBAL positions (``q_offset`` / ``k_offset``, traced
scalars in SMEM), so the ring layer can hand the kernel any (query block,
key block) pair with the same masking semantics as `_ring_attention_local`'s
compare — the kernel is the within-block engine; `ppermute` stays the
between-device engine. Every causal bound in a kernel is computed from them
at run time.

A SELECTION (``flash_attention(..., selection=...)``, PR 32) narrows the
mask to pairs a caller chose, the same for every head: one more operand, a
byte a (query, key) pair, which each kernel reads tile by tile where it
makes its causal mask (`_chosen`), laid out as the kernel's tiles are (keys
on sublanes for `flash_fwd` and `flash_bwd_dkv`, which read its transpose;
queries on sublanes for `flash_bwd_dq`). The loops' trip counts do not know
it: every tile up to the causal diagonal is computed, and a row's softmax and
gradients see the selected causal pairs alone. Without the operand nothing
of this is traced, and the kernels are the programs they were.

Backward is the standard two-kernel flash recipe: forward also emits the
per-row logsumexp ``L = m + log(den)``; backward recomputes ``P = exp(S -
L)`` tile by tile (never storing it) with ``delta = rowsum(dO * O)`` folded
in: dS = P * (dP - delta) * scale, dQ = dS K, dK = dS^T Q, dV = P^T dO.
delta stays one XLA reduction over dO and O where they lie (computing it in
`flash_bwd_dq`, which holds a query block's dO, was measured and cost that
kernel more than the reduction takes: PERF.md, PR 30).

Shapes follow the models' convention, q is (B, S, H, D) and k/v (B, S, Hkv,
D) with Hkv dividing H (grouped-query attention: query head j reads K/V head
j // (H / Hkv); see "groups" below, PR 33), and the
kernels address those arrays as they lie in memory: viewed as (B, S, H*D)
(a reshape, no operation), a block is (1, rows, W) with W the least common
multiple of D and 128 lanes where that divides H*D, else all of H*D
(`_lanes`). No transpose and no copy of q, k, v, o or a cotangent stands
between `flash_attention`'s arguments and the `pallas_call`s. A block so
holds g = W / D heads side by side on its lanes: one at D = 128 or 256, two
at D = 64, every head where H*D is under 128 (the CPU tests). The grid is
(B, H/g, blocks, spans) and a grid step walks its g heads in a static loop
over one body, each head's operands read as its D lanes of the block (see
"heads on lanes" below). With groups a step's query heads are (part of) one
K/V head's group and its K/V block that head's lanes of the UNREPEATED array:
K, V, the selection's block and the mask made from it cross HBM and the VPU
once a step for all the step's heads, and dK/dV leave as the group's f32 sum
in k's shape. What a call moves by operand (`_tiling`, logged once a trace)
at the sparse cell's (1, 16384, 32 on 4, 128) with its selection: `flash_fwd`
and `flash_bwd_dq` 32 MiB of K/V and 1 GiB of selection (8 and 8 a head a step
on K/V repeated to 32 heads in two spans), `flash_bwd_dkv` 8 GiB of Q and dO
and 1 GiB of selection (8 and 8). Tiles are multiples of 128 rows; unaligned sequence
lengths pad up to the tile: padded KEY rows are masked by a valid-length
compare; padded QUERY rows produce unobserved garbage and are sliced away.

Which engine runs the kernels follows the platform each program is LOWERED
for (`_pallas_call`): lowered for a TPU — attached or merely described —
they compile through Mosaic; lowered for the CPU (tests, the virtual-device
mesh) the same kernel bodies run in the Pallas interpreter, under a named
scope that shows in the program's text. The interpreter checks the
kernels' arithmetic; only the TPU's compiler checks that they lower
(`tests/test_tpu_compile.py`) and only the chip that they are right there
(`chip_smoke.py`) and how long they take (`onchip_flash_sweep.py`).
"""

from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_log = logging.getLogger(__name__)

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


# -- tiling --------------------------------------------------------------------
#
# Two extents, chosen apart. A TILE (blk_q x blk_k) is what one pass of the
# arithmetic holds: a scores matrix that size, in f32. A SPAN is what one
# grid step holds of the operand that streams past the tile's owner (keys and
# values past a query block in `flash_fwd` and `flash_bwd_dq`, queries and
# dO past a key block in `flash_bwd_dkv`): the step's DMA moves a whole span
# and a loop inside the step walks its tiles, stopping where causality or
# the keys' valid length says no later tile can count. A grid step costs
# about 0.35 us before it does anything, more than the arithmetic of a
# 128 x 128 tile; a loop trip costs a few scalar instructions, and a tile
# the loop never reaches costs neither a step nor a DMA.

#: Most rows of the streamed operand one grid step takes. A span that holds
#: the whole sequence is fetched once a head and not once a block of the
#: owner, and leaves no grid step in the causal future: at S = 4096 spans of
#: 1024, 2048 and 4096 rows took the forward 8.31, 7.81 and 6.33 ms (chip
#: sweep, PR 26), at (2, 8192, 32, 128) spans of 4096 and 8192 rows took
#: the three kernels 13.05, 14.01, 15.81 and 10.47, 11.40, 13.80 ms (chip
#: sweep, PR 30), and at the sparse cell's (1, 16384, 32 on 4, 128) with its
#: selection spans of 8192 and 16384 rows took them 19.63, 21.77, 31.20 and
#: 18.61, 20.83, 30.05 ms (chip sweep, PR 33: two spans leave a quarter of
#: the steps in the causal future, each moving its blocks to compute
#: nothing). 16384 rows of K and V, double-buffered, are 16 MiB of VMEM at
#: 128 lanes a block in bf16 beside 16 MiB of selection; longer was not
#: measured.
_MAX_SPAN = 16384


def _span(rows: int, blk: int) -> int:
    """Rows of the streamed operand a grid step takes: the most whole tiles
    that divide ``rows`` (already a multiple of ``blk``) evenly and stay
    within `_MAX_SPAN`, one tile at least."""
    tiles = rows // blk
    most = max(1, _MAX_SPAN // blk)
    return blk * max(d for d in range(1, most + 1) if tiles % d == 0)


def _block_bytes(blk_q, blk_k, span_q, span_k, q_lanes, k_lanes, itemsize,
                 selected):
    """Bytes of VMEM a step's blocks take: two operands on the query side
    (``q_lanes`` wide: all the block's query heads) and two on the key side
    (``k_lanes``), each a block and a span of rows, double-buffered, at
    ``itemsize`` bytes an element; ``selected`` adds the selection's block,
    a byte a (query, key) pair of the step's owner by its span,
    double-buffered. The count the scoped-VMEM request is made from
    (`_params`) and the count `_step_heads` holds a group's block to."""
    blocks = 2 * 2 * itemsize * (q_lanes * (blk_q + span_q)
                                 + k_lanes * (blk_k + span_k))
    if selected:
        blocks += 2 * max(blk_q * span_k, blk_k * span_q)
    return blocks


def _params(blocks, blk_q, blk_k, heads):
    """Compiler parameters of one kernel. The scoped-VMEM request counts
    every block a step may hold (``blocks``: `_block_bytes`) and eight f32
    temporaries of a tile for each of two heads in flight, doubled for what
    the compiler adds; 32 MiB at least (v5e's default scope is 16 of its
    128) and 96 at most."""
    tiles = min(heads, 2) * 8 * 4 * blk_q * blk_k
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(max(2 * (blocks + tiles), 32 * 2**20),
                             96 * 2**20))


def _visible(shape, k_dim, *, diag, k_left, causal, ragged, window=None):
    """Which (query, key) pairs of one tile count, or None where all do.
    ``k_dim`` is the axis of ``shape`` the keys lie on. ``diag`` is the
    global position of the tile's first query less that of its first key
    (a pair is causal-visible when its key index less its query index is at
    most that); ``k_left`` is how many of the tile's key rows hold real keys
    and not padding (looked at only where ``ragged`` says the key length is
    no multiple of the tile). Every tile of a causal call is masked, those
    below the diagonal too: a loop of their own without the mask was
    measured and bought nothing (PERF.md, PR 26), the VPU is not the
    limit. The mask is the same for every head of a block. Under a
    ``window`` a query also sees only its latest ``window`` keys, itself
    included: the key's index less the query's is over ``diag - window``."""
    if not (causal or ragged):
        return None
    k_idx = jax.lax.broadcasted_iota(jnp.int32, shape, k_dim)
    valid = k_idx < k_left if ragged else None
    if causal:
        q_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - k_dim)
        seen = k_idx - q_idx <= diag
        if window is not None:
            seen = jnp.logical_and(seen, k_idx - q_idx > diag - window)
        valid = seen if valid is None else jnp.logical_and(valid, seen)
    return valid


def _chosen(valid, picked):
    """``valid`` (`_visible`'s, or None) narrowed to the pairs a selection
    kept: ``picked`` is the tile of the selection operand, a byte a pair,
    non-zero where the query attends to the key, laid out as the tile's
    scores are. Widened to 32 bits first, so that the mask has the layout of
    the float32 scores it selects among."""
    picked = picked.astype(jnp.int32) != 0
    return picked if valid is None else jnp.logical_and(valid, picked)


def _nt(a, b):
    """a @ b.T on the MXU with f32 accumulation: (m, D), (n, D) -> (m, n)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _column(row):
    """(1, n) -> (n, 1): a statistics row as it crosses HBM, turned once a
    grid step into the column a (query, key)-oriented tile broadcasts. The
    row fills a (128, n) tile's sublanes, the XLU transposes it, and every
    lane of the result is the column."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _live_key_tiles(*, q_first, k_first, k_left, causal, blk_q, blk_k,
                    span_k, window=None):
    """``(first, stop)``: the span's key tiles that hold a key some query of
    the block sees. ``q_first``/``k_first`` are the global positions of the
    block's first query and the span's first key, ``k_left`` the span's
    count of real keys; all traced, so a ring hop in the causal future
    comes out empty. Without a ``window`` the first is tile 0; under one it
    is the tile of the earliest key the block's FIRST query sees, so a
    block walks ``window / blk_k + 1`` tiles wherever it lies."""
    stop = k_left
    if causal:  # keys at or before the block's last query
        stop = jnp.minimum(stop, q_first + blk_q - k_first)
    stop = (jnp.clip(stop, 0, span_k) + (blk_k - 1)) // blk_k
    if window is None:
        return 0, stop
    return jnp.clip(q_first - (window - 1) - k_first, 0, span_k) // blk_k, stop


def _live_query_tiles(*, q_first, k_first, causal, blk_q, blk_k, span_q,
                      window=None):
    """``(first, stop)``: the span's query tiles that hold a query which
    sees some key of the block `flash_bwd_dkv` owns. The first is the tile
    whose LAST row is at or after the block's first key (every tile before
    it lies wholly in the keys' past); without a ``window`` the rest of the
    span follows, under one the last is the tile of the latest query that
    still sees the block's LAST key."""
    first, stop = 0, span_q // blk_q
    if causal:
        first = jnp.clip(k_first - q_first, 0, span_q) // blk_q
    if window is not None:
        stop = (jnp.clip(k_first + blk_k + window - 1 - q_first, 0, span_q)
                + (blk_q - 1)) // blk_q
    return first, stop


# -- heads on lanes --------------------------------------------------------------
#
# A block is W lanes of the (B, S, H*D) array: g = W / D heads side by side,
# head h on lanes [h D, (h+1) D). A grid step walks them in a static loop
# INSIDE the tile loop (a loop of tiles for each head left the scheduler one
# chain at a time and cost the backward kernels 7 and 10%) and reads each
# operand of head h as that slice of the block's lanes; at g = 1 the slice
# is the block. The other way to meet a slab, measured on the chip at
# (32, 1024, 16, 64) and not kept (PERF.md, PR 30): zero the other heads'
# lanes of the step's own operand and contract over all W lanes, which is
# exact and needs no lane shift, and keep of each W-wide product the head's
# own rows or lanes. The MXU's time did not hold still as its 128-wide
# passes suggest: `flash_fwd` took 1.99 ms a call that way and 1.71 with
# slices, the backward kernels the same either way.


def _lanes(heads: int, head_dim: int) -> int:
    """Lanes of a block over a (B, S, heads * head_dim) array: the least
    common multiple of ``head_dim`` and 128 where whole blocks of that
    width tile the array, else the array's whole width."""
    lanes = math.lcm(head_dim, 128)
    return lanes if (heads * head_dim) % lanes == 0 else heads * head_dim


def _heads(ref, head_dim):
    """The lane slices of the heads a block holds, in order."""
    return [slice(at, at + head_dim)
            for at in range(0, ref.shape[2], head_dim)]


# -- groups ------------------------------------------------------------------------
#
# K and V may have fewer heads than q: (B, Sk, Hkv, D) with Hkv dividing H,
# query head j on K/V head j // (H / Hkv). Nothing repeats K or V: a grid
# step's block of query heads is (part of) one K/V head's GROUP, its K/V
# block that head's D lanes of the unrepeated (B, Sk, Hkv*D) array, chosen by
# the block spec's index map, so what the group shares crosses HBM once a
# step for all the step's heads: K, V, the selection's block and the mask
# made from it. The static loop over the step's query heads reads one K and V
# tile for all of them. Where a step holds less than the group (`_step_heads`)
# consecutive steps of the lane axis map to one K/V block index and the
# pipeline does not fetch it again. `flash_bwd_dkv` owns a key block of the
# K/V head and sums dK and dV over the group in its f32 scratch, the group's
# steps INSIDE the span axis: the transposed selection's block keeps its
# index while they go by. With Hkv = H a group is one head and every block,
# grid and index map is what it was without groups.

#: Most bytes of blocks (`_block_bytes`) a step may hold where the number of
#: a group's heads in a step is a choice. `flash_fwd` and `flash_bwd_dq` hold
#: any group met so far whole (42.5 MB at the sparse cell: K, V and the
#: selection of a 16,384-row span, eight heads' q and o); it is
#: `flash_bwd_dkv`, whose streamed Q and dO spans are the group wide, that it
#: bounds: four heads of an 8,192-row span (36.7 MB; eight are 72.4, ran, and
#: took 14.81 for 15.03 ms at the hybrid cell), two of a 16,384-row span
#: beside its selection (52.4 MB; four are 87 MB and with the tile's
#: temporaries pass the 96 MiB a kernel may ask for). Chip sweep, PR 33.
_MAX_BLOCK_BYTES = 64 * 2**20

#: Most query heads of a group one step walks in its static loop: the most
#: measured. At the hybrid cell's (2, 8192, 32 on 2, 128) 16, 8, 4, 2 and 1
#: heads a step took `flash_fwd` 9.47, 9.60, 9.88, 10.54, 10.61 ms and
#: `flash_bwd_dq` 11.65, 11.82, 12.10, 12.75, 13.48; at the sparse cell's 8,
#: 4, 2, 1 took them 19.63, 21.27, 26.19, 32.95 and 21.77, 23.14, 27.27,
#: 29.40, `flash_bwd_dkv` (8, 4, 2, 1) 30.29, 31.20, 32.53, 38.86 (chip
#: sweep, PR 33, host clock around a jitted call). The loop's body is traced
#: once a head, so a kernel's compile time grows with it (9 s for 4 at 16).
_MAX_STEP_HEADS = 16


def _step_heads(heads, kv_heads, head_dim, itemsize, blk_q, blk_k, span_q,
                span_k, selected):
    """``(query heads, K/V heads, bytes of blocks)`` of one grid step of the
    kernel with these extents. Without groups: `_lanes`' heads of both, and
    the bytes counted at four an element whatever the operands (the request
    those programs have had). With a K/V block that is one head (D a multiple
    of 128 lanes, or one K/V head in all): the most heads of its group, a
    divisor of the group in whole lane rows, that `_MAX_STEP_HEADS` allows
    and whose blocks stay within `_MAX_BLOCK_BYTES`, the least such divisor
    where none does. With a K/V block of several heads (two of 64): those
    heads' whole groups, which lie side by side in q."""
    group = heads // kv_heads
    kv = _lanes(kv_heads, head_dim) // head_dim

    def blocks(q_heads):
        return _block_bytes(blk_q, blk_k, span_q, span_k, q_heads * head_dim,
                            kv * head_dim, 4 if group == 1 else itemsize,
                            selected)

    if group == 1 or kv > 1:
        return kv * group, kv, blocks(kv * group)
    whole = [d for d in range(1, group + 1) if group % d == 0
             and ((d * head_dim) % 128 == 0 or d == group)]
    fit = [d for d in whole if d <= _MAX_STEP_HEADS
           and blocks(d) <= _MAX_BLOCK_BYTES]
    q_heads = max(fit) if fit else min(whole)
    return q_heads, 1, blocks(q_heads)


class _Plan(NamedTuple):
    """One kernel of a call: the query and K/V heads a step holds, the bytes
    of its blocks, its grid, and how many blocks of query heads go to one
    block of K/V heads (consecutive lane blocks that share a K/V block in
    `flash_fwd` and `flash_bwd_dq`; the steps a span in which
    `flash_bwd_dkv` walks its K/V heads' groups)."""
    heads: int
    kv_heads: int
    blocks: int
    grid: tuple
    ratio: int


def _plan(B, Sq, Sk, H, Hkv, D, itemsize, blk_q, blk_k, selected, own_keys):
    """The `_Plan` of `flash_bwd_dkv` (``own_keys``: a step owns a key block
    and streams spans of queries) or of the other two (it owns a query block
    and streams spans of keys), at padded lengths ``Sq`` and ``Sk``."""
    span_q, span_k = ((_span(Sq, blk_q), blk_k) if own_keys
                      else (blk_q, _span(Sk, blk_k)))
    g, g_kv, blocks = _step_heads(H, Hkv, D, itemsize, blk_q, blk_k, span_q,
                                  span_k, selected)
    ratio = H // g * g_kv // Hkv
    grid = ((B, Hkv // g_kv, Sk // blk_k, Sq // span_q * ratio) if own_keys
            else (B, H // g, Sq // blk_q, Sk // span_k))
    return _Plan(g, g_kv, blocks, grid, ratio)


# -- forward -------------------------------------------------------------------
#
# Per-query-row statistics (running max, denominator, logsumexp, delta)
# cross HBM as ROWS: shape (B*H, 1, Sq), block (g, 1, blk_q), the block's
# heads. Mosaic wants a block's last two dims divisible by (8, 128) or equal
# to the array's; (1, blk_q) over (1, Sq) is, (1, blk_q) over (B*H, Sq) is
# not, and a (blk_q, 1) column would pad every value to a 128-lane row in
# HBM.
#
# The forward works on TRANSPOSED tiles, (blk_k, blk_q), and accumulates
# O^T: the statistics of a query are then one lane of a dense (1, blk_q)
# row, the reductions over keys run down the sublanes on the VPU, and lse
# leaves as the row it is stored as. On (blk_q, blk_k) tiles each statistic
# was a (blk_q, 128) array rewritten for every tile and each reduction a
# lane reduction on the XLU per 8 rows per tile, which cost more than the
# tile's matmuls (PERF.md, PR 26). O^T is (W, blk_q), head h on rows
# [h D, (h+1) D), and turns once a query block, at the end, into the
# block's (blk_q, W).


def _fwd_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, *rest,
                scale, causal, k_len, blk_q, blk_k, head_dim, selected,
                window=None):
    # the selection's block, (1, span_k, blk_q) of the TRANSPOSED selection,
    # stands after v where the call has one
    sel_ref = rest[0] if selected else None
    o_ref, lse_ref, m_ref, l_ref, acc_ref = rest[1:] if selected else rest
    qi, si = pl.program_id(2), pl.program_id(3)
    n_s = pl.num_programs(3)
    span_k = k_ref.shape[1]
    heads, kv_heads = _heads(q_ref, head_dim), _heads(k_ref, head_dim)
    per = len(heads) // len(kv_heads)  # query heads on each K/V head

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_first = qo_ref[0] + qi * blk_q  # global position of the block's row 0
    k_first = ko_ref[0] + si * span_k
    k_left = k_len - si * span_k
    q = [q_ref[0, :, lanes] for lanes in heads]  # each (blk_q, D)

    def tile(j, _):
        at = pl.multiple_of(j * blk_k, blk_k)
        rows = pl.ds(at, blk_k)
        valid = _visible((blk_k, blk_q), 0, diag=q_first - (k_first + at),
                         k_left=k_left - at, causal=causal,
                         ragged=k_len % blk_k != 0, window=window)
        if sel_ref is not None:
            valid = _chosen(valid, sel_ref[0, rows, :])
        for h, lanes in enumerate(heads):
            if h % per == 0:  # the K/V head's tile, once for its query heads
                k = k_ref[0, rows, kv_heads[h // per]]  # (blk_k, D)
                v = v_ref[0, rows, kv_heads[h // per]]
            s_t = _nt(k, q[h]) * scale  # (blk_k, blk_q) f32
            if valid is not None:
                s_t = jnp.where(valid, s_t, _NEG_INF)
            m_prev = m_ref[h]  # (1, blk_q)
            m_new = jnp.maximum(m_prev, s_t.max(axis=0, keepdims=True))
            # A query that has seen no key yet keeps its max ON the
            # sentinel, where exp(s - m) of a masked score would be exp(0):
            # shift such a query by 0, and every masked score gives exactly
            # 0 without a second select.
            p_t = jnp.exp(s_t - jnp.where(m_new > _NEG_INF, m_new, 0.0))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + p_t.sum(axis=0, keepdims=True)
            acc_ref[lanes, :] = acc_ref[lanes, :] * alpha + jax.lax.dot_general(
                v, p_t.astype(v.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # V^T P^T: (D, blk_q)
            m_ref[h] = m_new

    jax.lax.fori_loop(*_live_key_tiles(
        q_first=q_first, k_first=k_first, k_left=k_left, causal=causal,
        blk_q=blk_q, blk_k=blk_k, span_k=span_k, window=window), tile, None)

    @pl.when(si == n_s - 1)
    def _emit():
        for h, lanes in enumerate(heads):
            l = l_ref[h]
            # queries that saw no key (padding, a hop in the future): den 0
            # -> emit 0, lse at the sentinel
            safe = jnp.where(l > 0, l, 1.0)
            acc_ref[lanes, :] = acc_ref[lanes, :] / safe
            lse_ref[h] = jnp.where(l > 0, m_ref[h] + jnp.log(safe), _NEG_INF)
        o_ref[0] = acc_ref[...].T.astype(o_ref.dtype)


#: Grid axes of every call: batch row, block of lanes, block of the operand
#: that OWNS the step (queries in `flash_fwd` and `flash_bwd_dq`, keys in
#: `flash_bwd_dkv`), span of the operand that streams past it. The lane
#: axis counts blocks of QUERY heads in `flash_fwd` and `flash_bwd_dq` and
#: blocks of K/V heads in `flash_bwd_dkv`, whose span axis also counts the
#: ``walk`` steps in which it walks those heads' groups a span (1 where a
#: step holds the groups whole, and without groups).
_OWN, _SPAN = 2, 3


def _span_at(step, walk):
    """The span of a step of the span axis."""
    return step if walk == 1 else step // walk


def _lanes_at(at, shared, walk):
    """The block of lanes of an operand at grid position ``at``: the lane
    axis's, or, for a K/V block under ``shared`` consecutive blocks of its
    group's query heads, the one they share; for a query-side block of
    `flash_bwd_dkv` walking a group in ``walk`` steps, the step's."""
    if walk != 1:
        return at[1] * walk + at[_SPAN] % walk
    return at[1] if shared == 1 else at[1] // shared


def _rows_at(at, axis, walk):
    """The block of rows along grid ``axis`` at grid position ``at``."""
    return _span_at(at[axis], walk) if axis == _SPAN else at[axis]


def _slab(rows, lanes, axis, shared=1, walk=1):
    """``rows`` x ``lanes`` of a (B, S, H*D) array, the rows by grid
    ``axis``, the lanes by `_lanes_at`."""
    return pl.BlockSpec((1, rows, lanes), lambda *at: (
        at[0], _rows_at(at, axis, walk), _lanes_at(at, shared, walk)))


def _pairs(q_rows, q_axis, k_rows, k_axis, transposed, walk=1):
    """A block of the selection, a byte a (query, key) pair over (B, Sq, Sk),
    or over (B, Sk, Sq) where ``transposed``: ``q_rows`` queries by grid
    axis ``q_axis`` and ``k_rows`` keys by ``k_axis``, the same for every
    head."""
    if transposed:
        return pl.BlockSpec((1, k_rows, q_rows), lambda *at: (
            at[0], at[k_axis], _rows_at(at, q_axis, walk)))
    return pl.BlockSpec((1, q_rows, k_rows),
                        lambda *at: (at[0], at[q_axis], at[k_axis]))


def _stat_rows(rows, heads, slabs, axis, walk=1):
    """The statistics rows of a block's ``heads`` query heads over (B*H, 1,
    S), ``slabs`` such blocks a batch row."""
    return pl.BlockSpec((heads, 1, rows), lambda *at: (
        at[0] * slabs + _lanes_at(at, 1, walk), 0, _rows_at(at, axis, walk)))


def _fwd(q, k, v, qo, ko, selection=None, *, scale, causal, k_len, blk_q,
         blk_k, head_dim, out_dtype, window=None):
    """q: (B, Sq, H*D); k/v: (B, Sk, Hkv*D) -> (o, lse (B*H, 1, Sq) f32).
    ``selection``, where there is one: ``(pairs (B, Sq, Sk), the same
    transposed)``, a byte a pair."""
    B, Sq, HD = q.shape
    Sk = k.shape[1]
    H, Hkv = HD // head_dim, k.shape[2] // head_dim
    span_k = _span(Sk, blk_k)
    chosen = [] if selection is None else [selection[1]]
    plan = _plan(B, Sq, Sk, H, Hkv, head_dim, q.dtype.itemsize, blk_q, blk_k,
                 bool(chosen), own_keys=False)
    g, W, slabs = plan.heads, plan.heads * head_dim, H // plan.heads
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    q_spec = _slab(blk_q, W, _OWN)
    k_spec = _slab(span_k, plan.kv_heads * head_dim, _SPAN, plan.ratio)
    return _pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          k_len=k_len, blk_q=blk_q, blk_k=blk_k,
                          head_dim=head_dim, selected=bool(chosen),
                          window=window),
        "flash_fwd",
        grid=plan.grid,
        in_specs=[scalar, scalar, q_spec, k_spec, k_spec]
        + [_pairs(blk_q, _OWN, span_k, _SPAN, True)] * len(chosen),
        out_specs=[q_spec, _stat_rows(blk_q, g, slabs, _OWN)],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, HD), out_dtype),
            jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1, blk_q), jnp.float32),  # running max m
            pltpu.VMEM((g, 1, blk_q), jnp.float32),  # running denominator l
            pltpu.VMEM((W, blk_q), jnp.float32),  # output accumulator, O^T
        ],
        compiler_params=_params(plan.blocks, blk_q, blk_k, g),
    )(qo, ko, q, k, v, *chosen)


# -- backward ------------------------------------------------------------------
#
# `flash_bwd_dq` owns a query block and walks key tiles like the forward,
# on (blk_q, blk_k) tiles; its lse/delta rows become columns once a step.
# `flash_bwd_dkv` owns a key block and walks QUERY tiles on TRANSPOSED
# (blk_k, blk_q) tiles: the rows of lse/delta then broadcast down the
# sublanes as they arrive, and dV = P^T dO and dK = dS^T Q are plain
# matmuls. Neither masks the scores: exp(s - lse) of an unseen pair may be
# anything, inf included, and the one select on P discards it. The factor
# ``scale`` of dS = P (dP - delta) scale is applied to the f32 sums at the
# end, not to every tile.


def _bwd_dq_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, *rest,
                   scale, causal, k_len, blk_q, blk_k, head_dim, selected,
                   window=None):
    sel_ref = rest[0] if selected else None  # (1, blk_q, span_k)
    dq_ref, acc_ref = rest[1:] if selected else rest
    qi, si = pl.program_id(2), pl.program_id(3)
    n_s = pl.num_programs(3)
    span_k = k_ref.shape[1]
    heads, kv_heads = _heads(q_ref, head_dim), _heads(k_ref, head_dim)
    per = len(heads) // len(kv_heads)  # query heads on each K/V head

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_first = qo_ref[0] + qi * blk_q
    k_first = ko_ref[0] + si * span_k
    k_left = k_len - si * span_k
    q = [q_ref[0, :, lanes] for lanes in heads]  # each (blk_q, D)
    do = [do_ref[0, :, lanes] for lanes in heads]
    lse = [_column(lse_ref[h]) for h in range(len(heads))]  # each (blk_q, 1)
    delta = [_column(delta_ref[h]) for h in range(len(heads))]

    def tile(j, _):
        at = pl.multiple_of(j * blk_k, blk_k)
        rows = pl.ds(at, blk_k)
        valid = _visible((blk_q, blk_k), 1, diag=q_first - (k_first + at),
                         k_left=k_left - at, causal=causal,
                         ragged=k_len % blk_k != 0, window=window)
        if sel_ref is not None:
            valid = _chosen(valid, sel_ref[0, :, rows])
        for h in range(len(heads)):
            if h % per == 0:  # the K/V head's tile, once for its query heads
                k = k_ref[0, rows, kv_heads[h // per]]  # (blk_k, D)
                v = v_ref[0, rows, kv_heads[h // per]]
            p = jnp.exp(_nt(q[h], k) * scale - lse[h])  # (blk_q, blk_k) f32
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            ds = p * (_nt(do[h], v) - delta[h])
            acc_ref[h] += jnp.dot(ds.astype(k.dtype), k,
                                  preferred_element_type=jnp.float32)  # dS K

    jax.lax.fori_loop(*_live_key_tiles(
        q_first=q_first, k_first=k_first, k_left=k_left, causal=causal,
        blk_q=blk_q, blk_k=blk_k, span_k=span_k, window=window), tile, None)

    @pl.when(si == n_s - 1)
    def _emit():
        for h, lanes in enumerate(heads):
            dq_ref[0, :, lanes] = (acc_ref[h] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, *rest, scale, causal, k_len, blk_q,
                    blk_k, head_dim, selected, walk, window=None):
    sel_ref = rest[0] if selected else None  # (1, blk_k, span_q), transposed
    dk_ref, dv_ref, dk_acc, dv_acc = rest[1:] if selected else rest
    # K outer, Q streams; the last axis counts a span's ``walk`` steps
    # through the K/V heads' groups (1: the step holds them whole)
    ki, step = pl.program_id(2), pl.program_id(3)
    si, n_s = _span_at(step, walk), pl.num_programs(3)
    span_q = q_ref.shape[1]
    heads, kv_heads = _heads(q_ref, head_dim), _heads(k_ref, head_dim)
    per = len(heads) // len(kv_heads)  # the step's query heads a K/V head

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_first = qo_ref[0] + si * span_q
    k_first = ko_ref[0] + ki * blk_k
    k = [k_ref[0, :, lanes] for lanes in kv_heads]  # each (blk_k, D)
    v = [v_ref[0, :, lanes] for lanes in kv_heads]

    def tile(j, _):
        at = pl.multiple_of(j * blk_q, blk_q)
        rows = pl.ds(at, blk_q)
        valid = _visible((blk_k, blk_q), 0, diag=q_first + at - k_first,
                         k_left=k_len - ki * blk_k, causal=causal,
                         ragged=k_len % blk_k != 0, window=window)
        if sel_ref is not None:
            valid = _chosen(valid, sel_ref[0, :, rows])
        for h, lanes in enumerate(heads):
            q = q_ref[0, rows, lanes]  # (blk_q, D)
            do = do_ref[0, rows, lanes]
            lse = lse_ref[h, :, rows]  # (1, blk_q)
            delta = delta_ref[h, :, rows]
            # (blk_k, blk_q) f32
            p_t = jnp.exp(_nt(k[h // per], q) * scale - lse)
            if valid is not None:
                p_t = jnp.where(valid, p_t, 0.0)
            ds_t = p_t * (_nt(v[h // per], do) - delta)
            # a K/V head's dV = P^T dO and dK = dS^T Q, summed over its group
            dv_acc[h // per] += jnp.dot(p_t.astype(do.dtype), do,
                                        preferred_element_type=jnp.float32)
            dk_acc[h // per] += jnp.dot(ds_t.astype(q.dtype), q,
                                        preferred_element_type=jnp.float32)

    jax.lax.fori_loop(*_live_query_tiles(
        q_first=q_first, k_first=k_first, causal=causal, blk_q=blk_q,
        blk_k=blk_k, span_q=span_q, window=window), tile, None)

    @pl.when(step == n_s - 1)
    def _emit():
        for h, lanes in enumerate(kv_heads):
            dk_ref[0, :, lanes] = (dk_acc[h] * scale).astype(dk_ref.dtype)
            dv_ref[0, :, lanes] = dv_acc[h].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, dlse, qo, ko, selection=None, *, scale,
         causal, k_len, blk_q, blk_k, head_dim, window=None):
    B, Sq, HD = q.shape
    Sk = k.shape[1]
    H, Hkv = HD // head_dim, k.shape[2] // head_dim
    span_q, span_k = _span(Sq, blk_q), _span(Sk, blk_k)
    # dL/ds_ij = p_ij (dp_ij - delta_i) for the out path PLUS p_ij * dlse_i
    # for the lse path (dlse/ds = softmax row) — the lse cotangent folds
    # into delta with a sign flip. dlse is zeros when lse wasn't consumed.
    # rowsum(dO * O) is over each head's D where dO and O lie, its sums
    # written as the rows they cross HBM in: (B, H, Sq), the batch
    # dimensions in that order (XLA makes one reduction of it in both
    # models' steps; summing and then transposing cost the dense step a
    # copy of an operand: PERF.md, PR 30).
    by_head = lambda x: x.reshape(B, Sq, H, head_dim)
    delta = jax.lax.dot_general(
        by_head(do), by_head(o), (((3,), (3,)), ((0, 2, 1), (0, 2, 1))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).reshape(B * H, 1, Sq) - dlse.astype(jnp.float32)
    selected = selection is not None
    kernel_kw = dict(scale=scale, causal=causal, k_len=k_len, blk_q=blk_q,
                     blk_k=blk_k, head_dim=head_dim, selected=selected,
                     window=window)
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)

    plan = functools.partial(_plan, B, Sq, Sk, H, Hkv, head_dim,
                             q.dtype.itemsize, blk_q, blk_k, selected)

    own = plan(own_keys=False)
    g, W, slabs = own.heads, own.heads * head_dim, H // own.heads
    q_spec = _slab(blk_q, W, _OWN)
    k_spec = _slab(span_k, own.kv_heads * head_dim, _SPAN, own.ratio)
    row_spec = _stat_rows(blk_q, g, slabs, _OWN)
    dq = _pallas_call(
        functools.partial(_bwd_dq_kernel, **kernel_kw),
        "flash_bwd_dq",
        grid=own.grid,
        in_specs=[scalar, scalar,
                  q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
        + [_pairs(blk_q, _OWN, span_k, _SPAN, False)] * selected,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, HD), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, blk_q, head_dim), jnp.float32)],
        compiler_params=_params(own.blocks, blk_q, blk_k, g),
    )(qo, ko, q, k, v, do, lse, delta, *(selection or ())[:1])

    # K outer / Q streams: the accumulators belong to the K block, whose
    # heads' groups the span axis walks in ``walk`` steps a span.
    own = plan(own_keys=True)
    g, g_kv, walk = own.heads, own.kv_heads, own.ratio
    q_spec = _slab(span_q, g * head_dim, _SPAN, walk=walk)
    k_spec = _slab(blk_k, g_kv * head_dim, _OWN)
    row_spec = _stat_rows(span_q, g, H // g, _SPAN, walk)
    dk, dv = _pallas_call(
        functools.partial(_bwd_dkv_kernel, walk=walk, **kernel_kw),
        "flash_bwd_dkv",
        grid=own.grid,
        in_specs=[scalar, scalar,
                  q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
        + [_pairs(span_q, _SPAN, blk_k, _OWN, True, walk)] * selected,
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((g_kv, blk_k, head_dim), jnp.float32),
            pltpu.VMEM((g_kv, blk_k, head_dim), jnp.float32),
        ],
        compiler_params=_params(own.blocks, blk_q, blk_k, g),
    )(qo, ko, q, k, v, do, lse, delta, *(selection or ())[1:])
    return dq, dk, dv


# -- what a call builds ----------------------------------------------------------


def _fetches(grid, sees):
    """How many times a call's pipeline fetches an operand's block: a step
    whose block index is the step before's fetches nothing. ``sees`` maps the
    grid axes the index follows to how many values it takes along each."""
    live = [axis for axis, values in sees.items() if values > 1]
    if not live:
        return 1
    return math.prod(grid[:max(live)]) * sees[max(live)]


def live_tiles(Sq, Sk, blk_q, blk_k, window=None):
    """How many (query tile, key tile) pairs of a causal call at offsets 0
    hold a pair some query sees, a head: what the kernels' loops walk. 528
    of 1,024 at 16,384 x 16,384 in tiles of 512; 252 under a window of
    4,096 (nine key tiles a query block, fewer at the sequence's start)."""
    tiles = 0
    for q_first in range(0, Sq, blk_q):
        first = 0 if window is None else max(q_first - (window - 1), 0)
        stop = min(q_first + blk_q, Sk)
        tiles += max(0, -(-stop // blk_k) - first // blk_k)
    return tiles


def _tiling(B, Sq, Sk, H, Hkv, D, itemsize, blk_q, blk_k, selected,
            window=None):
    """What a call of these (padded) extents builds, by kernel: the grid, the
    query and K/V heads a step holds, and the MiB a call's pipeline moves
    between HBM and VMEM by operand. `flash_attention` logs it once a trace;
    it is the record of whether a group's K, V and selection cross HBM once
    for the group (`tests/test_tpu_compile.py` pins it at the cells). Under
    a ``window`` also the tiles a head's loops walk, of those causality
    alone leaves (`live_tiles`): the blocks a step FETCHES are the same
    either way, whole spans, so a window saves arithmetic and no DMA."""
    span_q, span_k = _span(Sq, blk_q), _span(Sk, blk_k)
    plan = functools.partial(_plan, B, Sq, Sk, H, Hkv, D, itemsize, blk_q,
                             blk_k, selected)
    MiB = lambda n: round(n / 2**20, 1)
    out = {"tile": (blk_q, blk_k), "heads": (H, Hkv)}
    if window is not None:
        out["window"] = (window, live_tiles(Sq, Sk, blk_q, blk_k, window),
                         live_tiles(Sq, Sk, blk_q, blk_k))
    g, g_kv, _, grid, _ = plan(own_keys=False)
    own = blk_q * g * D * itemsize * math.prod(grid[:3])
    streamed = {
        "k+v": 2 * span_k * g_kv * D * itemsize * _fetches(
            grid, {0: B, 1: Hkv // g_kv, _SPAN: grid[_SPAN]}),
        "selection": selected * blk_q * span_k * _fetches(
            grid, {0: B, _OWN: grid[_OWN], _SPAN: grid[_SPAN]})}
    for name, blocks in (("flash_fwd", {"q+o": 2 * own}),
                         ("flash_bwd_dq", {"q+do+dq": 3 * own})):
        out[name] = {"grid": grid, "heads_a_step": (g, g_kv), "MiB": {
            k: MiB(n) for k, n in {**streamed, **blocks}.items()}}
    g, g_kv, _, grid, _ = plan(own_keys=True)
    out["flash_bwd_dkv"] = {"grid": grid, "heads_a_step": (g, g_kv), "MiB": {
        "q+do": MiB(2 * span_q * g * D * itemsize * _fetches(
            grid, {0: B, 1: grid[1], _SPAN: grid[_SPAN]})),
        "selection": MiB(selected * blk_k * span_q * _fetches(
            grid, {0: B, _OWN: grid[_OWN], _SPAN: Sq // span_q})),
        "k+v+dk+dv": MiB(4 * blk_k * g_kv * D * itemsize
                         * math.prod(grid[:3]))}}
    return out


# -- public entrypoint ---------------------------------------------------------


def _flash_fwd(q, k, v, offsets, selection, scale, causal, k_len, blk_q,
               blk_k, head_dim, out_dtype, window):
    o, lse = _fwd(q, k, v, *offsets, selection, scale=scale, causal=causal,
                  k_len=k_len, blk_q=blk_q, blk_k=blk_k, head_dim=head_dim,
                  out_dtype=out_dtype, window=window)
    return (o, lse), (q, k, v, o, lse, offsets, selection)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(5, 13)))
def _flash(*args):
    return _flash_fwd(*args)[0]


def _flash_bwd(scale, causal, k_len, blk_q, blk_k, head_dim, out_dtype,
               window, res, cts):
    q, k, v, o, lse, (qo, ko), selection = res
    do, dlse = cts
    dq, dk, dv = _bwd(q, k, v, o, lse, do, dlse, qo, ko, selection,
                      scale=scale, causal=causal, k_len=k_len, blk_q=blk_q,
                      blk_k=blk_k, head_dim=head_dim, window=window)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)

#: `_flash` for the calls with groups. A kernel whose static loop walks a
#: group's heads is 8 or 16 bodies to trace, and a model that does not scan
#: its layers calls it once a layer a pass (24 call sites in the sparse
#: cell's step, each traced for both engines): under `jax.jit` equal calls
#: share one trace and one lowering, and XLA inlines them. Tracing and
#: lowering that step took 9 to 12 s a call site a time and 5 to 6 s so (6
#: to 7 before groups; the sandbox's CPU, PR 33), and the cell's run does it
#: three times under a limit of 360 s. Without groups the call stays bare:
#: those programs' text is what it was.
_flash_traced_once = jax.jit(_flash, static_argnums=tuple(range(5, 13)))


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


#: The tile, for every call: rows of queries by rows of keys in one pass of
#: the arithmetic. One number because the chip sweep of PR 26
#: (`onchip_flash_sweep.py`, bf16, causal, v5e; PERF.md, Findings) found one:
#: 512 x 512 was the quickest tile for each of the three kernels at (S, D) =
#: (1024, 64), (2048, 64) and (1024, 128) and within 6% of the quickest
#: (1024 x 1024) at (4096, 64); at the hybrid cell's (8192, 128) it is within
#: 2% of the quickest (512 x 1024; PR 30). Smaller tiles waste less of the causal
#: diagonal but pay a loop trip's fill and drain more often; larger ones
#: compute more of the future. A sequence shorter than the tile gets one
#: tile of its own length, in whole 128-row MXU tiles.
_TILE = 512


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
    selection: Optional[jax.Array] = None,
    window: Optional[int] = None,
):
    """Blockwise-online attention. q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D),
    Hkv dividing H: query head j reads K/V head j // (H / Hkv), and nothing
    repeats K or V (see "groups"); dK and dV come back in k's and v's shape,
    each the sum over the head's group, made in f32.

    ``selection`` (B, Sq, Sk), a byte a pair (int8), non-zero where the
    query attends to the key, the same for every head: the softmax of a row
    and both backward kernels then run over the pairs that are selected AND
    pass the causal test, and see no other. It carries no gradient. The
    kernels read it tile by tile beside the causal test, `flash_fwd` and
    `flash_bwd_dkv` from its transpose (one XLA transpose here, where their
    tiles have keys on sublanes). Every tile up to the causal diagonal is
    still computed: a selection saves no arithmetic. Left out, the kernels'
    programs are the ones they were without this operand.

    ``window`` (a static count, causal calls only): a query sees its latest
    ``window`` keys, itself included, and no earlier one: key s of query t
    where ``0 <= t - s < window``, in global positions. The mask gains the
    lower edge and the loops walk only the tiles that hold such a pair
    (`_live_key_tiles`; `flash_bwd_dkv` stops at the last query that sees
    its key block): a call's arithmetic follows the window, not the
    sequence. A window at or over the sequence changes nothing but the
    program. Left out, the kernels are the programs they were.

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

    ``block_q``/``block_k`` pin the tile (multiples of 128); left out it is
    `_TILE`, or the sequence's length where that is shorter.

    The kernels read and write the arrays where they lie, as (B, S, H*D),
    a block `_lanes(H, D)` lanes wide: nothing is transposed or copied on
    the way in or out. Where H*D is no multiple of the least common multiple
    of D and 128 (an odd head count at D = 64, say) a block is ALL heads
    wide: right, but a span of 16,384 rows of some thousand lanes, twice for
    K and V and twice for the double buffer, passes the 96 MiB of VMEM a
    kernel may ask for and Mosaic refuses the program when it compiles;
    such a model pads its heads or passes a shorter sequence a call.
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    if H % Hkv or v.shape != k.shape:
        raise ValueError(
            f"flash_attention: q has {H} heads and k {k.shape}, v {v.shape}: "
            "k and v want one shape whose heads divide q's (query head j "
            "reads K/V head j // (H / Hkv))")
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"flash_attention: window {window} of a "
            f"{'causal' if causal else 'non-causal'} call: a window is a "
            "count of at least one key, the query's own included, and "
            "bounds a CAUSAL query's keys from below")
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    # Tile alignment: both extents are multiples of 128 (scores and their
    # transposes are whole MXU tiles, statistics rows are whole lane rows);
    # short sequences shrink the tile to one of 128 rows and pad up to it,
    # with padded keys masked via the valid-length compare.
    blk_q = min(block_q or _TILE, _round_up(Sq, 128))
    blk_k = min(block_k or _TILE, _round_up(Sk, 128))

    # heads side by side on the last axis: the arrays as they lie in memory
    q2 = _pad_seq(q.reshape(B, Sq, H * D), blk_q)
    k2 = _pad_seq(k.reshape(B, Sk, Hkv * D), blk_k)
    v2 = _pad_seq(v.reshape(B, Sk, Hkv * D), blk_k)
    if _log.isEnabledFor(logging.DEBUG):
        # once a trace, for whoever reads a profile: which tiling was built
        _log.debug("flash_attention q%s k%s %s: %s", q.shape, k.shape,
                   q.dtype, _tiling(B, q2.shape[1], k2.shape[1], H, Hkv, D,
                                    q.dtype.itemsize, blk_q, blk_k,
                                    selection is not None, window))

    offsets = (jnp.asarray([q_offset], jnp.int32),
               jnp.asarray([k_offset], jnp.int32))

    # With lse (the ring's hop engine) the partial output stays f32: hops
    # merge at accumulator precision and the CALLER downcasts once after
    # the final merge — the same discipline the einsum ring engine had.
    out_dtype = jnp.float32 if return_lse else q.dtype
    if selection is not None:
        pairs = jnp.pad(selection.astype(jnp.int8), (
            (0, 0), (0, q2.shape[1] - Sq), (0, k2.shape[1] - Sk)))
        selection = (pairs, pairs.swapaxes(1, 2))
    flash = _flash if Hkv == H else _flash_traced_once
    o2, lse = flash(q2, k2, v2, offsets, selection, scale, causal, Sk, blk_q,
                    blk_k, D, jnp.dtype(out_dtype), window)
    out = o2[:, :Sq].reshape(B, Sq, H, D)
    if not return_lse:
        return out
    return out, lse[:, 0, :Sq].reshape(B, H, Sq)
