"""Flash attention (Pallas) vs the dense oracle: values and gradients.

On the CPU test platform the kernels run in Pallas interpret mode — the
identical program the TPU compiles, executed by the interpreter — so these
tests validate the kernel logic itself, not a CPU reimplementation.
"""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops import flash_attention
from edl_tpu.parallel.ring_attention import dense_attention

#: the module; `edl_tpu.ops` exports the function under the same name
fa = importlib.import_module("edl_tpu.ops.flash_attention")


def rand_qkv(rng, B, S, H, D, dtype=jnp.float32, Sk=None, Hkv=None):
    Sk, Hkv = Sk or S, Hkv or H
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Sk, Hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Sk, Hkv, D)), dtype)
    return q, k, v


def repeated(q, k, v):
    """K and V repeated to q's heads, query head j on K/V head j // group:
    what a caller did before the kernels took groups, and what the oracles
    take."""
    group = q.shape[2] // k.shape[2]
    return q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [
    (1, 16, 1, 8),    # tiny, single block
    (2, 64, 2, 16),   # multi-head
    (1, 300, 2, 32),  # unaligned S -> padding path, multiple q blocks
])
def test_matches_dense_oracle(shape, causal):
    B, S, H, D = shape
    rng = np.random.default_rng(0)
    q, k, v = rand_qkv(rng, B, S, H, D)
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_multiple_kv_blocks_accumulate():
    """S larger than one K block: the online-softmax recurrence must fold
    several visiting blocks into one normalized result."""
    rng = np.random.default_rng(1)
    q, k, v = rand_qkv(rng, 1, 384, 1, 16)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_global_offsets_match_ring_semantics():
    """A (query block, key block) pair with global offsets must mask like
    the ring layer's global-position compare: keys strictly in the query
    block's future contribute nothing."""
    rng = np.random.default_rng(2)
    S = 32
    q, k, v = rand_qkv(rng, 1, S, 1, 8, Sk=S)
    # full sequence oracle over 2 shards' worth of positions
    q_full = jnp.concatenate([q, q], axis=1)
    k_full = jnp.concatenate([k, k], axis=1)
    v_full = jnp.concatenate([v, v], axis=1)
    want = dense_attention(q_full, k_full, v_full, causal=True)

    # shard 1's queries attending shard 0's keys (all visible) ...
    m0, l0 = _merge_piece(q, k, v, q_off=S, k_off=0)
    # ... merged with shard 1's own keys (causal within the block)
    m1, l1 = _merge_piece(q, k, v, q_off=S, k_off=S)
    out = _merge((m0, l0), (m1, l1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want[:, S:]),
                               rtol=2e-5, atol=2e-5)


def _merge_piece(q, k, v, q_off, k_off):
    """Unnormalized (num, den) for one K block via the kernel's lse output:
    reconstruct num = out * den from out and lse."""
    out = flash_attention(q, k, v, causal=True, q_offset=q_off,
                          k_offset=k_off)
    # recompute lse densely for the merge (test-side only)
    import math

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    qpos = q_off + jnp.arange(q.shape[1])
    kpos = k_off + jnp.arange(k.shape[1])
    s = jnp.where(kpos[None, :] <= qpos[:, None], s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)  # (B, H, Sq)
    return out, lse


def _merge(a, b):
    (oa, la), (ob, lb) = a, b
    m = jnp.maximum(la, lb)
    wa = jnp.exp(la - m)[..., None].transpose(0, 2, 1, 3)
    wb = jnp.exp(lb - m)[..., None].transpose(0, 2, 1, 3)
    return (oa * wa + ob * wb) / (wa + wb)


def test_gradients_match_dense_oracle():
    rng = np.random.default_rng(3)
    q, k, v = rand_qkv(rng, 1, 160, 2, 16)  # unaligned: padding in bwd too

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )


def test_bfloat16_inputs():
    rng = np.random.default_rng(4)
    q, k, v = rand_qkv(rng, 1, 64, 2, 16, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True)
    want = dense_attention(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_jit_and_traced_offsets():
    """Offsets may be traced scalars (the ring passes axis_index-derived
    values); the kernel must compile once and mask correctly."""
    rng = np.random.default_rng(5)
    q, k, v = rand_qkv(rng, 1, 32, 1, 8)

    @jax.jit
    def f(q, k, v, off):
        return flash_attention(q, k, v, causal=True, q_offset=off,
                               k_offset=0)

    # q_offset >= Sk: every key visible -> equals non-causal attention
    got = f(q, k, v, jnp.int32(32))
    want = dense_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fully_masked_rows_within_live_block():
    """Ring-offset case: k_offset slightly above q_offset leaves the block
    'live' while some query rows have NO visible keys. Those rows must
    output exactly zero (and their gradients must vanish) — the masked-
    score sentinel colliding with the running-max init used to make them
    emit mean(V)."""
    rng = np.random.default_rng(6)
    S = 16
    q, k, v = rand_qkv(rng, 1, S, 1, 8)
    off = 5  # keys start 5 positions into the queries' future
    out = flash_attention(q, k, v, causal=True, q_offset=0, k_offset=off)
    # oracle: dense attention over globally-positioned scores
    import math

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(8)
    qpos = jnp.arange(S)
    kpos = off + jnp.arange(S)
    mask = kpos[None, :] <= qpos[:, None]
    s = jnp.where(mask[None, None], s, -1e30)
    p = jnp.where(mask[None, None], jax.nn.softmax(s, axis=-1), 0.0)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.allclose(np.asarray(out)[0, :off], 0.0)  # rows with no keys

    g = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, causal=True, q_offset=0, k_offset=off) ** 2
    ))(q)
    assert np.allclose(np.asarray(g)[0, :off], 0.0)
    assert bool(np.isfinite(np.asarray(g)).all())


def test_randomized_shapes_and_offsets_property():
    """Property sweep over the input space the ring can produce: random
    (B, Sq, Sk, H, D), random global offsets (including key blocks fully
    or partially in the queries' future), values AND gradients vs a
    globally-positioned dense oracle."""
    import math

    rng = np.random.default_rng(42)
    for trial in range(8):
        B = int(rng.integers(1, 3))
        H = int(rng.integers(1, 3))
        D = int(rng.choice([4, 8, 16]))
        Sq = int(rng.integers(3, 70))
        Sk = int(rng.integers(3, 70))
        q_off = int(rng.integers(0, 50))
        k_off = int(rng.integers(0, 50))
        causal = bool(rng.integers(0, 2))
        q, k, v = rand_qkv(rng, B, Sq, H, D, Sk=Sk)

        def oracle(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
            if causal:
                qpos = q_off + jnp.arange(Sq)
                kpos = k_off + jnp.arange(Sk)
                mask = kpos[None, :] <= qpos[:, None]
                s = jnp.where(mask[None, None], s, -1e30)
                p = jnp.where(mask[None, None],
                              jax.nn.softmax(s, axis=-1), 0.0)
            else:
                p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        got = flash_attention(q, k, v, causal=causal,
                              q_offset=q_off, k_offset=k_off)
        want = oracle(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5,
            err_msg=f"trial {trial}: B={B} Sq={Sq} Sk={Sk} H={H} D={D} "
                    f"qo={q_off} ko={k_off} causal={causal}",
        )
        gf = jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=causal, q_offset=q_off, k_offset=k_off) ** 2))(q)
        gd = jax.grad(lambda q: jnp.sum(oracle(q, k, v) ** 2))(q)
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=3e-4, atol=3e-4,
            err_msg=f"grad trial {trial}",
        )


# -- tiles and spans -----------------------------------------------------------
#
# A grid step holds a span of several tiles of the streamed operand and a
# loop inside it stops at the causal diagonal or at the keys' valid length
# (flash_attention.py, "tiling"). The cases below put more than one tile in
# a span, more than one span in a sequence, the diagonal inside a tile, and
# padding inside the last tile, at B=1, H=2, D=16 to keep the interpreter
# quick.


def positioned_oracle(q, k, v, *, causal, q_off=0, k_off=0):
    """Dense attention over globally positioned scores -> (out, lse). A row
    that sees no key gives zeros and the kernel's finite sentinel. K and V
    of fewer heads than q are repeated to them."""
    q, k, v = repeated(q, k, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(q.shape[-1])
    seen = jnp.ones(s.shape[-2:], bool)
    if causal:
        q_pos = q_off + jnp.arange(q.shape[1])
        k_pos = k_off + jnp.arange(k.shape[1])
        seen = k_pos[None, :] <= q_pos[:, None]
    s = jnp.where(seen, s, -1e30)
    p = jnp.where(seen, jax.nn.softmax(s, axis=-1), 0.0)
    lse = jnp.where(seen.any(-1), jax.nn.logsumexp(s, axis=-1), -1e30)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out, lse


def _grads(fn, q, k, v):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2))(q, k, v)


def _assert_matches_dense(q, k, v, *, causal, block_q, block_k):
    """Output and the three gradients against `dense_attention`."""
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    for name, a, b in zip("qkv", _grads(flash, q, k, v),
                          _grads(dense, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")


TILE_CASES = [
    # (S, D, block_q, block_k, causal)
    (384, 16, 256, 256, True),    # pads to 512: padding inside the last tile
    (384, 16, 256, 256, False),
    (640, 16, 256, 256, True),    # three tiles a side, the last ragged
    (640, 16, 512, 512, True),    # two tiles, 384 rows of padding
    (640, 16, 256, 512, False),
    (640, 16, 128, 256, True),
    (1024, 16, 256, 256, True),   # aligned: no key mask, 4 x 4 tiles
    (1024, 16, 512, 512, True),
    (1024, 16, 512, 256, False),  # aligned and not causal: no mask at all
    (1024, 16, 256, 512, True),
    # was test_flash_tuning's test_kernel_correct_with_explicit_nondefault_blocks
    (512, 64, 256, 128, True),
    (512, 64, 128, 256, True),
    (512, 64, 256, 256, True),
]


@pytest.mark.parametrize("S,D,block_q,block_k,causal", TILE_CASES)
def test_tiles_match_dense_oracle(S, D, block_q, block_k, causal):
    rng = np.random.default_rng(S + block_q + block_k)
    q, k, v = rand_qkv(rng, 1, S, 2, D)
    _assert_matches_dense(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k)


@pytest.mark.parametrize("causal", [True, False])
def test_spans_shorter_than_the_sequence(monkeypatch, causal):
    """Several grid steps along the streamed axis, as sequences past
    `_MAX_SPAN` rows get: statistics and accumulators carry across steps,
    and steps wholly in the causal future add nothing."""
    monkeypatch.setattr(fa, "_MAX_SPAN", 256)
    assert fa._span(768, 128) == 256  # three spans of two tiles
    rng = np.random.default_rng(7)
    q, k, v = rand_qkv(rng, 1, 700, 2, 16)
    _assert_matches_dense(q, k, v, causal=causal, block_q=128, block_k=128)


@pytest.mark.parametrize("H,Hkv,D", [
    (2, 2, 16),   # every head in one block narrower than a lane row
    (4, 4, 64),   # two blocks of 128 lanes, two heads each (g = 2)
    (4, 2, 64),   # groups of two on a K/V block of two heads of 64
    (4, 1, 128),  # a group of four on one K/V head of 128
])
@pytest.mark.parametrize("q_off,k_off", [
    (512, 0),    # a hop wholly in the past: every tile live, none masked
    (0, 513),    # a hop wholly in the future (k_offset > q_offset + Sq)
    (100, 300),  # partly dead: the first 200 queries see no key
    (300, 100),  # the diagonal crosses the tiles off their corners
])
def test_ring_hops_at_multi_tile_blocks(q_off, k_off, H, Hkv, D):
    """What `_ring_flash_local` asks of one hop, at a block of 2 x 2 tiles:
    out and lse against the positioned oracle, and gradients through BOTH
    (the lse cotangent folds into delta). A dead hop is zeros, the
    sentinel, and zero gradients."""
    rng = np.random.default_rng(q_off + k_off)
    q, k, v = rand_qkv(rng, 1, 512, H, D, Hkv=Hkv)
    w = jnp.asarray(rng.standard_normal((1, H, 512)), jnp.float32)

    def loss(attend):
        def f(q, k, v):
            out, lse = attend(q, k, v)
            # the sentinel is a constant: it carries no gradient
            return (jnp.sum(out ** 2)
                    + jnp.sum(jnp.where(lse > -1e29, lse, 0.0) * w))
        return f

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, q_offset=q_off, k_offset=k_off,
        block_q=256, block_k=256, return_lse=True)
    oracle = lambda q, k, v: positioned_oracle(
        q, k, v, causal=True, q_off=q_off, k_off=k_off)
    out, lse = flash(q, k, v)
    want_out, want_lse = oracle(q, k, v)
    assert out.dtype == jnp.float32 and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert bool(np.isfinite(np.asarray(a)).all()), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")
    if k_off > q_off + 512:
        assert not np.asarray(out).any() and (np.asarray(lse) == -1e30).all()
        assert not any(np.asarray(g).any() for g in got)


@pytest.mark.parametrize("H,D", [(2, 16), (2, 64)])  # (2, 64): g = 2
def test_bfloat16_gradients_within_an_ulp_of_the_tensor(H, D):
    """bf16 in, bf16 on the MXU: P (forward), P^T and dS (backward) are
    rounded to bf16 at their matmuls and the gradients leave as bf16, while
    the statistics and the sums stay f32. Against the f32 oracle on the
    same (bf16-valued) inputs an element of a gradient is a sum of terms
    that each carry half a bf16 ulp (2^-9) of their own size, so the sum's
    error rides the TERMS' magnitude, not the element's: the bound is one
    bf16 ulp (2^-8) of the tensor's largest entry, as in test_pipeline's
    and test_collective's tolerance tests, plus the same relative."""
    rng = np.random.default_rng(8)
    q, k, v = rand_qkv(rng, 1, 640, H, D, dtype=jnp.bfloat16)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=256, block_k=256)
    oracle = lambda q, k, v: positioned_oracle(q, k, v, causal=True)[0]
    got = _grads(flash, q, k, v)
    want = _grads(oracle, *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == jnp.bfloat16
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), b, rtol=2.0 ** -8,
            atol=float(np.abs(b).max()) * 2.0 ** -8, err_msg=f"d{name}")


# -- heads on lanes --------------------------------------------------------------
#
# The kernels address (B, S, H*D) as it lies: a block is `_lanes(H, D)` lanes
# wide and holds g = W / D heads side by side, each met through a copy of the
# step's own operand with the other heads' lanes zeroed (flash_attention.py,
# "heads on lanes").


@pytest.mark.parametrize("H,D,lanes", [
    (16, 64, 128), (20, 64, 128),  # the GPT-2 configurations: two heads
    (32, 128, 128),                # the hybrid cell: one head
    (2, 256, 256), (4, 32, 128), (8, 96, 384),
    (3, 16, 48), (15, 64, 960),    # no whole blocks: every head in one
])
def test_a_block_is_the_least_whole_lane_rows_of_whole_heads(H, D, lanes):
    assert fa._lanes(H, D) == lanes
    assert lanes % D == 0 and (H * D) % lanes == 0


def _distinct_heads(rng, B, S, H, D, Hkv=None, Sk=None):
    """q, k, v whose heads differ in scale as well as in their draws: a head
    served from its neighbour's lanes cannot pass for its own."""
    size = lambda x: (1.0 + 0.5 * jnp.arange(
        x.shape[2], dtype=jnp.float32))[None, None, :, None]
    return tuple(x * size(x) for x in rand_qkv(rng, B, S, H, D, Sk=Sk,
                                               Hkv=Hkv))


@pytest.mark.parametrize("return_lse", [False, True])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("H,Hkv,D", [
    (2, 2, 128),  # one head a block, two blocks
    (4, 4, 64),   # two heads a block, two blocks
    (2, 2, 64),   # two heads, one block
    (4, 4, 32),   # four heads a block
    (3, 3, 16),   # 48 lanes: no whole lane row, every head in one block
    (2, 2, 256),  # a head of two lane rows
    # groups: K/V heads of fewer than q's, nothing repeated
    (4, 2, 128),   # groups of two, a group a step, two K/V blocks
    (8, 2, 128),   # groups of four
    (16, 2, 128),  # groups of eight
    (8, 4, 64),    # groups of two on K/V blocks of two heads of 64
    (8, 1, 64),    # one K/V head of 64 (its block the whole array), eight on it
    (6, 2, 16),    # groups of three in one block of every head
])
def test_heads_on_lanes_match_dense_oracle(H, Hkv, D, causal, return_lse):
    """Forward and the three gradients at every way a block can hold heads
    and a K/V head its group, two batch rows (the statistics' row index
    counts both) and two tiles a side. With ``return_lse`` the logsumexp and
    the gradient through it."""
    rng = np.random.default_rng(1000 * H + D + (Hkv != H) * Hkv)
    B, S = 2, 256
    q, k, v = _distinct_heads(rng, B, S, H, D, Hkv)
    w = jnp.asarray(rng.standard_normal((B, H, S)), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128, return_lse=return_lse)

    def dense(q, k, v):
        if return_lse:
            return positioned_oracle(q, k, v, causal=causal)
        return dense_attention(*repeated(q, k, v), causal=causal)

    def loss(attend):
        def f(q, k, v):
            got = attend(q, k, v)
            if return_lse:
                return jnp.sum(got[0] ** 2) + jnp.sum(got[1] * w)
            return jnp.sum(got ** 2)
        return f

    got, want = flash(q, k, v), dense(q, k, v)
    for a, b in zip(*((got, want) if return_lse else ((got,), (want,)))):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)
    g_got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_got, g_want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4,
            atol=5e-4 * float(np.abs(np.asarray(b)).max()),
            err_msg=f"d{name}")


def _primitives_outside_kernels(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives_outside_kernels(sub)


@pytest.mark.parametrize("H,D", [(16, 64), (32, 128)])
@pytest.mark.parametrize("grad", [False, True])
def test_no_layout_work_between_the_model_and_the_kernels(H, D, grad):
    """At an aligned shape `flash_attention` and its gradient hand the
    kernels the arrays they were given: the program holds the three
    `pallas_call`s and no `pad` and no `transpose` of an operand, a result
    or a cotangent (the reshapes to (B, S, H*D) and back move nothing);
    delta's sums over D leave their `dot_general` as the (B, H, S) rows the
    kernels read."""
    qkv = (jax.ShapeDtypeStruct((2, 1024, H, D), jnp.bfloat16),) * 3

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else (
        lambda q, k, v: flash_attention(q, k, v, causal=True))
    seen = list(_primitives_outside_kernels(jax.make_jaxpr(fn)(*qkv).jaxpr))
    assert seen.count("pallas_call") == (6 if grad else 2)  # each x 2 engines
    assert "transpose" not in seen and "pad" not in seen, seen


# -- groups --------------------------------------------------------------------------
#
# K and V of fewer heads than q (flash_attention.py, "groups") against the
# SAME kernels on K and V repeated to q's heads, which is what the callers
# did: a query head's arithmetic is the same tile for tile, so the output,
# the logsumexp and dQ are equal to the last bit; dK and dV are the group's
# sum, made in f32 in the kernel's scratch, against the f32 sum of the
# repeated call's per-head gradients.

GROUP_CASES = [
    # H, Hkv, D, heads a step at most, selection, dtype
    (4, 2, 64, 8, False, jnp.float32),    # groups of 2, K/V blocks of two
    (4, 2, 128, 8, True, jnp.float32),    # groups of 2, a group a step
    (8, 2, 128, 8, False, jnp.float32),   # groups of 4
    (8, 2, 128, 2, True, jnp.float32),    # ... two heads a step: two steps
    (8, 1, 128, 8, True, jnp.float32),    # a group of 8, whole
    (8, 1, 128, 4, False, jnp.float32),   # ... in two steps
    (8, 1, 128, 1, True, jnp.float32),    # ... a head a step, eight steps
    (8, 1, 64, 8, True, jnp.float32),     # a group of 8 at D = 64
    (16, 2, 64, 8, False, jnp.float32),   # groups of 8 on two K/V heads of 64
    (8, 2, 128, 8, True, jnp.bfloat16),
    (8, 1, 64, 2, False, jnp.bfloat16),
]


def _group_sum(g, like):
    """The per-head gradient of a repeated K or V, summed over each group in
    f32: (B, Sk, H, D) -> ``like``'s (B, Sk, Hkv, D)."""
    B, Sk, Hkv, D = like.shape
    return g.astype(jnp.float32).reshape(B, Sk, Hkv, -1, D).sum(3)


@pytest.mark.parametrize("H,Hkv,D,most,selected,dtype", GROUP_CASES)
@pytest.mark.parametrize("return_lse", [False, True])
def test_groups_are_the_repeated_call(monkeypatch, H, Hkv, D, most, selected,
                                      dtype, return_lse):
    """Causal with offsets that put the diagonal inside a tile, a key length
    that is no multiple of the tile (padding in the last), two batch rows,
    with and without a selection, the group whole in a step and in several
    (`_MAX_STEP_HEADS`), through the logsumexp where it is returned."""
    monkeypatch.setattr(fa, "_MAX_STEP_HEADS", most)
    rng = np.random.default_rng(H * D + most)
    B, Sq, Sk = 2, 256, 300
    q, k, v = (x.astype(dtype) for x in _distinct_heads(
        rng, B, Sq, H, D, Hkv, Sk=Sk))
    probe = jnp.asarray(rng.standard_normal((B, Sq, H, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, H, Sq)), jnp.float32)
    picked = None
    if selected:
        picked = jnp.asarray(rng.random((B, Sq, Sk)) < 0.4, jnp.int8)

    def attend(q, k, v):
        return flash_attention(
            q, k, v, causal=True, q_offset=70, k_offset=20, block_q=128,
            block_k=128, return_lse=return_lse, selection=picked)

    def loss(q, k, v):
        got = attend(q, k, v)
        if return_lse:
            return jnp.sum(got[0] * probe) + jnp.sum(
                jnp.where(got[1] > -1e29, got[1], 0.0) * w)
        return jnp.sum(got.astype(jnp.float32) * probe)

    got, want = attend(q, k, v), attend(*repeated(q, k, v))
    for a, b in zip(*((got, want) if return_lse else ((got,), (want,)))):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    dq, dk, dv = jax.grad(loss, (0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(loss, (0, 1, 2))(*repeated(q, k, v))
    assert dk.shape == k.shape and dv.shape == v.shape and dk.dtype == dtype
    assert np.array_equal(np.asarray(dq, np.float32),
                          np.asarray(rq, np.float32))
    for name, a, b in (("dk", dk, _group_sum(rk, k)),
                       ("dv", dv, _group_sum(rv, v))):
        b = np.asarray(b)
        # f32: the same terms in another order; bf16: the repeated call
        # rounded each head's gradient before the sum, this one the sum
        tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
        np.testing.assert_allclose(
            np.asarray(a, np.float32), b, rtol=tol,
            atol=tol * float(np.abs(b).max()), err_msg=name)


def test_kv_heads_that_do_not_divide_the_query_heads_are_refused():
    q, k, v = rand_qkv(np.random.default_rng(0), 1, 128, 6, 16, Hkv=4)
    with pytest.raises(ValueError, match="heads divide"):
        flash_attention(q, k, v)
    with pytest.raises(ValueError, match="one shape"):
        flash_attention(q, k[:, :, :3], v[:, :, :2])


@pytest.mark.parametrize("seq,H,Hkv,D,itemsize,selected,want", [
    (1024, 16, 16, 64, 2, False, ((2, 2), (2, 2))),   # no groups: `_lanes`
    (8192, 32, 32, 128, 2, False, ((1, 1), (1, 1))),
    (1024, 20, 20, 64, 2, False, ((2, 2), (2, 2))),
    (16384, 32, 4, 128, 2, True, ((8, 1), (2, 1))),   # the sparse cell
    (8192, 32, 2, 128, 2, False, ((16, 1), (4, 1))),  # the hybrid cell
    (8192, 64, 2, 128, 2, False, ((16, 1), (4, 1))),  # `_MAX_STEP_HEADS`
    (8192, 8, 4, 64, 2, False, ((4, 2), (4, 2))),     # K/V blocks of two heads
    (4096, 8, 1, 64, 4, False, ((8, 1), (8, 1))),     # f32, one K/V head
    (8192, 8, 1, 64, 4, False, ((8, 1), (4, 1))),     # ... half the heads fit
    (16384, 8, 1, 64, 4, False, ((8, 1), (2, 1))),    # ... a quarter
    (1024, 6, 2, 16, 4, False, ((6, 2), (6, 2))),     # every head in one block
])
def test_the_heads_a_step_holds_are_read_off_the_call(seq, H, Hkv, D, itemsize,
                                                      selected, want):
    """`_step_heads` in tiles of 512: (query heads, K/V heads) of a step of
    `flash_fwd` and `flash_bwd_dq`, and of `flash_bwd_dkv`, whose Q and dO
    spans are the larger blocks; no flag and no model's name, the call's
    shapes alone."""
    span = fa._span(seq, 512)
    fwd = fa._step_heads(H, Hkv, D, itemsize, 512, 512, 512, span, selected)
    dkv = fa._step_heads(H, Hkv, D, itemsize, 512, 512, span, 512, selected)
    assert (fwd[:2], dkv[:2]) == want
    for q_heads, kv_heads, _ in (fwd, dkv):
        assert H % q_heads == 0 and Hkv % kv_heads == 0
        assert q_heads % kv_heads == 0
