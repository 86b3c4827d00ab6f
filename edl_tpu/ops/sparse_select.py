"""The learned sparse-attention selection as two Pallas TPU kernels: the
lightning indexer's scores, and the top-k keys of every query.

`indexer_scores`: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for every
causal (query, key) pair, written TRANSPOSED, ``(B, S keys, S queries)``
float32. A grid step holds a block of queries and a tile of keys and walks
the indexer's heads: each head's ``kI qI^T`` on the MXU (bf16 operands,
float32 accumulation), relu and the head's weight on the VPU, the weight a
row that broadcasts down the sublanes. Tiles wholly in a block's future are
skipped and left unwritten; `top_k_select` never reads them.

`top_k_select`: of each query's causal scores the ``min(t + 1, k)`` largest,
ties to the earlier key, as bytes: ``(B, S keys, S queries)`` int8, the
layout `flash_fwd` and `flash_bwd_dkv` read their selection in. A grid step
holds ALL the keys of a block of 128 queries in VMEM, keys on sublanes and
queries on lanes, so a query's statistics are one lane of a dense row and a
count over keys is a sum down the sublanes (the layout of `flash_fwd`'s
tiles, for the same reason). The k-th largest score of a query comes by
bisection on the float's bits: the scores are mapped once to int32 keys
whose signed order is the floats', and 32 passes each fix one bit of the
threshold by counting the keys at or above a candidate; 14 more passes find,
among the keys ON the threshold, how many of the earliest take the places
left. A pass walks the block's live tiles in VMEM and touches no HBM: the
same passes in plain XLA read the block from HBM 32 times.

Both kernels run through the interpreter where the program is lowered for
the CPU, as the flash kernels do (`flash_attention._pallas_call`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops.flash_attention import _nt, _pallas_call

__all__ = ["indexer_scores", "top_k_select"]

#: queries a grid step of `indexer_scores` holds, and keys a tile
_SCORE_BLOCK = 512
#: queries a grid step of `top_k_select` holds: one lane row
_SELECT_BLOCK = 128
#: keys a pass of `top_k_select` takes from VMEM at a time
_KEY_TILE = 512

_LOWEST = -2 ** 31  # under every key of a real score


def _scores_kernel(q_ref, k_ref, w_ref, out_ref, *, heads, head_dim, blk):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki <= qi)  # some key of the tile is at or before some query
    def _():
        k = k_ref[0]  # (blk keys, Di)
        acc = jnp.zeros((blk, blk), jnp.float32)
        for h in range(heads):
            q = q_ref[0, :, h * head_dim:(h + 1) * head_dim]  # (blk, Di)
            acc += w_ref[0, h:h + 1, :] * jnp.maximum(_nt(k, q), 0.0)
        out_ref[0] = acc


def indexer_scores(qI: jax.Array, kI: jax.Array, w: jax.Array) -> jax.Array:
    """qI (B, S, Hi, Di) and kI (B, S, Di) in the MXU's dtype, w (B, S, Hi)
    float32 (the score's scales folded in) -> ``I`` TRANSPOSED, (B, S keys, S
    queries) float32. Pairs in a query's future hold anything (tiles wholly
    in the future are never written). S a multiple of 128."""
    B, S, Hi, Di = qI.shape
    blk = min(_SCORE_BLOCK, S)
    assert S % blk == 0 and blk % 128 == 0, (S, blk)
    return _pallas_call(
        functools.partial(_scores_kernel, heads=Hi, head_dim=Di, blk=blk),
        "indexer_scores",
        grid=(B, S // blk, S // blk),
        in_specs=[
            pl.BlockSpec((1, blk, Hi * Di), lambda b, qi, ki: (b, qi, 0)),
            # a tile in the future is not computed: fetch the diagonal's
            pl.BlockSpec((1, blk, Di),
                         lambda b, qi, ki: (b, jnp.minimum(ki, qi), 0)),
            pl.BlockSpec((1, Hi, blk), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, blk, blk), lambda b, qi, ki: (b, ki, qi)),
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 2**20),
    )(qI.reshape(B, S, Hi * Di), kI, w.swapaxes(1, 2))


def _select_kernel(scores_ref, out_ref, keys_ref, *, topk, blk, tile):
    S = scores_ref.shape[1]
    first = pl.program_id(1) * blk  # position of the block's first query
    live = (first + blk + tile - 1) // tile  # tiles with a causal pair
    t = first + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
    want = jnp.minimum(t + 1, topk).astype(jnp.float32)  # (1, blk)

    def rows(j):
        return pl.ds(pl.multiple_of(j * tile, tile), tile)

    def key_index(j):  # the position of each key of tile j, (tile, blk)
        return j * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, blk), 0)

    def to_keys(j, _):  # float32 -> int32 of the same order; the future lowest
        x = scores_ref[0, rows(j), :]
        bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x),
                                            jnp.int32)
        keys = bits ^ ((bits >> 31) & 0x7FFFFFFF)
        keys_ref[rows(j), :] = jnp.where(key_index(j) <= t, keys, _LOWEST)

    jax.lax.fori_loop(0, live, to_keys, None)

    def count(test):  # per query, the keys of the live tiles that pass
        def one(j, n):
            hit = test(keys_ref[rows(j), :], j)
            return n + jnp.sum(jnp.where(hit, 1.0, 0.0), axis=0,
                               keepdims=True)
        return jax.lax.fori_loop(0, live, one,
                                 jnp.zeros((1, blk), jnp.float32))

    def value_bit(i, kth):  # the sign bit first: is the k-th largest >= +0.0?
        cand = jnp.where(i == 0, 0, kth | jnp.left_shift(1, 31 - i))
        enough = count(lambda keys, j: keys >= cand) >= want
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, value_bit,
                            jnp.full((1, blk), _LOWEST, jnp.int32))
    # the places left for keys ON the k-th largest; the earliest take them:
    # the largest position `last` with fewer than `short` such keys before it
    short = want - count(lambda keys, j: keys > kth)

    bits = (S - 1).bit_length()  # of a key's position

    def index_bit(i, last):
        cand = last | jnp.left_shift(1, bits - 1 - i)
        few = count(lambda keys, j: (keys == kth) & (key_index(j) < cand)) \
            < short
        return jnp.where(few, cand, last)

    last = jax.lax.fori_loop(0, bits, index_bit,
                             jnp.zeros((1, blk), jnp.int32))

    def emit(j, _):
        keys, at = keys_ref[rows(j), :], key_index(j)
        picked = (keys > kth) | ((keys == kth) & (at <= last))
        out_ref[0, rows(j), :] = jnp.where(picked & (at <= t), 1, 0).astype(
            jnp.int8)

    jax.lax.fori_loop(0, live, emit, None)

    def blank(j, _):  # the future: nothing is selected there
        out_ref[0, rows(j), :] = jnp.zeros((tile, blk), jnp.int8)

    jax.lax.fori_loop(live, S // tile, blank, None)


def top_k_select(scores_t: jax.Array, topk: int) -> jax.Array:
    """``scores_t`` (B, S keys, S queries) float32, query t's causal scores in
    column t -> int8 of the same shape, 1 on the ``min(t + 1, topk)`` keys ``s
    <= t`` of largest score, ties to the earlier key, 0 elsewhere. S a
    multiple of 128 and under 2^24."""
    B, S, _ = scores_t.shape
    blk = min(_SELECT_BLOCK, S)
    tile = min(_KEY_TILE, S)
    assert S % blk == 0 and S % tile == 0 and S < 2 ** 24, S
    column = pl.BlockSpec((1, S, blk), lambda b, i: (b, 0, i))
    return _pallas_call(
        functools.partial(_select_kernel, topk=topk, blk=blk, tile=tile),
        "top_k_select",
        grid=(B, S // blk),
        in_specs=[column],
        out_specs=column,
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.int8),
        scratch_shapes=[pltpu.VMEM((S, blk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 2**20),
    )(scores_t)
