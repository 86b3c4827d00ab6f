"""The hybrid decoder's sliding-window family (`edl_tpu/models/hybrid.py`:
``*`` and ``W`` attention layers, ``E`` layers whose frozen router reads the
attention sublayer's normed input, gated relu experts) against its plain
reference (`edl_tpu/models/window_reference.py`) on the CPU at toy widths;
the flash kernels' ``window`` against masked dense attention through the
Pallas interpreter, and the tiles their loops walk; the window's counters;
the share of a deployment."""

import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import hybrid, window_reference as ref
from edl_tpu.obs.metrics import get_registry
from edl_tpu.ops import flash_attention
from edl_tpu.parallel import MeshSpec, build_mesh
from edl_tpu.parallel.ring_attention import (dense_attention, ring_attention,
                                             visible_pairs)
from edl_tpu.runtime import Trainer, TrainerConfig

#: the module; `edl_tpu.ops` exports the function under the same name
fa = importlib.import_module("edl_tpu.ops.flash_attention")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = 128
WINDOW = 40

#: bf16 matmuls (8 mantissa bits) against float32 `highest`: a value of order
#: 1 moves by up to about 1e-2, a gradient by a few percent of the tensor's
#: largest entry. A term left out moves either by its own size.
VALUE_TOL = 2e-2
GRAD_TOL = 5e-2

#: batch 2 everywhere: a bf16 `dot` at batch 1 fails on the CPU backend
SIZES = dict(pattern="*EWEWEWE", seq_len=S, vocab_size=256, d_model=64,
             n_heads=14, n_kv_heads=2, head_dim=16, window=WINDOW,
             rope_theta=1.5e6, router_score="softmax", expert_act="relu",
             shared_width=0, routed_scale=1.0, n_experts=8, experts_count=4,
             experts_first=2, top_k=2, expert_width=32, norm_eps=1e-6,
             router_input="previous", router_frozen=True, embed_std=1.0)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshSpec({"data": 1}), jax.devices()[:1])


@pytest.fixture(scope="module")
def tiny(mesh):
    model = hybrid.make_model(**SIZES)
    return model, model.init(jax.random.PRNGKey(0), mesh)


def normed(seed, d=64, batch=2):
    h = jax.random.normal(jax.random.PRNGKey(seed), (batch, S, d), jnp.float32)
    return h.astype(jnp.bfloat16).astype(jnp.float32)


def close(got, want, tol, least=1e-6):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.abs(want).max()), least)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


def scaled(p, scale=20.0):
    """Matrices scaled up so that every term is of a size that shows."""
    return {k: v * scale if v.ndim >= 2 else v for k, v in p.items()}


def leaf_distances(got, want):
    out = {}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        d, n = float(jnp.sum((g - w) ** 2)), float(jnp.sum(w ** 2))
        out[jax.tree_util.keystr(path)] = (d / n) ** 0.5 if n else (
            0.0 if d == 0 else float("inf"))
    return out


def test_the_reference_is_kept_twice_byte_for_byte():
    with open(os.path.join(REPO, "benchmarks", "reference_window.py")) as f, \
            open(ref.__file__) as g:
        assert f.read() == g.read()


# -- the flash kernels' window ---------------------------------------------------------


@pytest.mark.parametrize("seq, heads, kv_heads, head_dim, window, block", [
    (640, 7, 1, 128, 200, 128), (512, 2, 2, 128, 130, 128),
    (1024, 14, 2, 128, 300, 256), (300, 7, 1, 64, 77, 128),
    (200, 3, 3, 64, 200, None), (200, 4, 1, 64, 1000, None),
    (384, 1, 1, 128, 1, 128), (1024, 2, 2, 128, 512, 512)],
    ids=["group_of_7_window_no_multiple_of_the_tile", "group_of_1_D128",
         "two_groups_of_7_tiles_of_256", "ragged_keys_group_of_7_D64",
         "window_at_the_sequence_one_tile", "window_above_the_sequence",
         "window_of_the_query_alone", "window_of_one_tile_of_512"])
def test_flash_with_a_window_is_masked_dense_attention(seq, heads, kv_heads,
                                                       head_dim, window,
                                                       block):
    """The forward and the three gradients of the interpreted kernels under
    a window against explicit scores under ``s <= t and t - s < window``,
    float32 in and out: windows below, at and above the sequence and of one
    key; a window that is no multiple of the tile; key lengths that are no
    multiple of it (padded and masked); groups of 7, of 1 and two groups a
    call; D 64 and 128."""
    key = jax.random.split(jax.random.PRNGKey(seq + window), 4)
    q, probe = (jax.random.normal(k, (2, seq, heads, head_dim), jnp.float32)
                for k in key[:2])
    k, v = (jax.random.normal(k, (2, seq, kv_heads, head_dim), jnp.float32)
            for k in key[2:])

    def ours(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=block, block_k=block)

    def theirs(q, k, v):
        k, v = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (k, v))
        return dense_attention(q, k, v, causal=True, window=window)

    close(jax.jit(ours)(q, k, v), jax.jit(theirs)(q, k, v), 2e-3)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(ours(*a) * probe),
                           (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(theirs(*a) * probe),
                            (0, 1, 2)))(q, k, v)
    for a, b in zip(got, want):  # a window of one key: dQ and dK are zero
        close(a, b, 2e-3, least=1e-2)
    plain = flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    if window >= seq:  # nothing but the program changes
        assert np.array_equal(np.asarray(plain), np.asarray(ours(q, k, v)))
    else:  # and it is no all-pass
        assert float(jnp.abs(plain - ours(q, k, v)).max()) > 0.05


def test_dense_attention_under_a_window_sees_the_window_alone():
    seen = np.asarray(visible_pairs(6, 3))
    assert seen.sum(1).tolist() == [1, 2, 3, 3, 3, 3]
    assert seen[5].tolist() == [False, False, False, True, True, True]
    assert np.array_equal(np.asarray(visible_pairs(6)), np.tri(6, dtype=bool))
    # one key a query: the output is that key's value
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 6, 2, 8))
               for i in range(3))
    close(dense_attention(q, k, v, window=1), v, 1e-6)


def brute_tiles(Sq, Sk, blk_q, blk_k, window, q0=0, k0=0):
    """Which (query tile, key tile) pairs hold a visible pair: booleans."""
    t = q0 + np.arange(Sq)[:, None]
    s = k0 + np.arange(Sk)[None, :]
    seen = (s <= t) & ((t - s < window) if window else True)
    return seen.reshape(Sq // blk_q, blk_q, Sk // blk_k, blk_k).any((1, 3))


@pytest.mark.parametrize("window", [None, 1, 100, 128, 300, 512, 4096])
@pytest.mark.parametrize("q0, k0", [(0, 0), (1024, 0), (0, 1024), (256, 512)])
def test_the_loops_walk_the_tiles_that_hold_a_visible_pair(window, q0, k0):
    """`_live_key_tiles` (`flash_fwd`, `flash_bwd_dq`) and `_live_query_
    tiles` (`flash_bwd_dkv`) against a brute count over the mask: every tile
    with a visible pair lies inside ``[first, stop)`` and no other does: a
    block of 128 queries under a window of 300 walks four key tiles of 128,
    not the sequence's eight."""
    Sq = Sk = 1024
    blk = 128
    live = brute_tiles(Sq, Sk, blk, blk, window, q0, k0)
    for i in range(Sq // blk):
        first, stop = (int(x) for x in fa._live_key_tiles(
            q_first=q0 + i * blk, k_first=k0, k_left=Sk, causal=True,
            blk_q=blk, blk_k=blk, span_k=Sk, window=window))
        seen = np.flatnonzero(live[i])
        if len(seen):
            assert first <= seen[0] and seen[-1] < stop
            assert (first, stop) == (seen[0], seen[-1] + 1)
        else:
            assert first >= stop or not live[i, first:stop].any()
    for j in range(Sk // blk):
        first, stop = (int(x) for x in fa._live_query_tiles(
            q_first=q0, k_first=k0 + j * blk, causal=True, blk_q=blk,
            blk_k=blk, span_q=Sq, window=window))
        seen = np.flatnonzero(live[:, j])
        if len(seen):
            assert (first, stop) == (seen[0], seen[-1] + 1)
        else:
            assert first >= stop or not live[first:stop, j].any()


def test_live_tiles_at_the_cell():
    """252 of a causal call's 528 tiles a head at 16,384 under 4,096 in
    tiles of 512 (ISSUE 35), and the pairs they hold: 0.4375."""
    assert fa.live_tiles(16384, 16384, 512, 512) == 528
    assert fa.live_tiles(16384, 16384, 512, 512, 4096) == 252
    assert fa.live_tiles(16384, 16384, 512, 512, 4096) == int(
        brute_tiles(16384, 16384, 512, 512, 4096).sum())
    assert (ref.visible_pairs(16384, 4096), ref.visible_pairs(16384)) \
        == (58_722_304, 134_225_920)
    tiling = fa._tiling(1, 16384, 16384, 28, 4, 128, 2, 512, 512, False, 4096)
    assert tiling["window"] == (4096, 252, 528)
    # a group of 7 to a step; `flash_bwd_dkv` a head a step, the group in 7
    assert tiling["flash_fwd"]["heads_a_step"] == (7, 1)
    assert tiling["flash_fwd"]["grid"] == (1, 4, 32, 1)
    assert tiling["flash_bwd_dkv"]["heads_a_step"] == (1, 1)
    assert tiling["flash_bwd_dkv"]["grid"] == (1, 4, 32, 7)
    assert "window" not in fa._tiling(1, 16384, 16384, 28, 4, 128, 2, 512,
                                      512, False)


def test_a_window_is_refused_where_it_has_no_meaning(mesh):
    q = jnp.zeros((1, 128, 2, 64))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, window=0)
    with pytest.raises(ValueError, match="window"):
        dense_attention(q, q, q, causal=False, window=8)
    with pytest.raises(NotImplementedError, match="window"):
        ring_attention(q, q, q, mesh, window=8)
    for bad in (dict(pattern="W", window=0), dict(pattern="W", head_dim=15),
                dict(pattern="E*", router_input="previous"),
                dict(pattern="*ME", router_input="previous"),
                dict(router_input="next"), dict(expert_act="gelu")):
        with pytest.raises(ValueError):
            hybrid.make_model(**bad)


# -- the layers against the reference ------------------------------------------------


def check_mixer(program, reference, p, h, wrt):
    """Values, and gradients of a random projection of them with respect to
    the input and the leaves ``wrt``."""
    probe = jax.random.normal(jax.random.PRNGKey(9), h.shape, jnp.float32)

    def ours(p, h):
        return program(p, h.astype(jnp.bfloat16)).astype(jnp.float32)

    def theirs(p, h):
        return jnp.stack([reference(p, row) for row in h])

    close(jax.jit(ours)(p, h), jax.jit(theirs)(p, h), VALUE_TOL)
    got = jax.jit(jax.grad(
        lambda p, h: jnp.sum(ours(p, h) * probe), (0, 1)))(p, h)
    want = jax.jit(jax.grad(
        lambda p, h: jnp.sum(theirs(p, h) * probe), (0, 1)))(p, h)
    close(got[1], want[1], GRAD_TOL)
    for name in wrt:
        close(got[0][name], want[0][name], GRAD_TOL)


@pytest.mark.parametrize("flash", [True, False],
                         ids=["interpreted_flash_kernel", "dense_path"])
@pytest.mark.parametrize("kind", ["W", "*"])
def test_window_and_global_attention_are_the_reference(tiny, flash, kind):
    """A ``W`` layer (rotary over the whole head, the window) and a ``*``
    layer (no positions, every causal key) on groups of 7 query heads a K/V
    head against the reference's explicit scores."""
    model, params = tiny
    cfg = hybrid.HybridConfig(**dict(SIZES, flash=flash))
    p = scaled(params["layers"]["02W" if kind == "W" else "00*"])
    mixer = hybrid._MIXERS[kind][1]
    check_mixer(lambda p, h: mixer(cfg, h, p),
                lambda p, row: ref.attention_mixer(cfg, p, row, kind == "W"),
                p, normed(14), ("wq", "wk", "wv", "wo"))
    # the window matters at this size: the two kinds differ
    h = normed(14).astype(jnp.bfloat16)
    other = hybrid._MIXERS["*" if kind == "W" else "W"][1]
    assert float(jnp.abs(mixer(cfg, h, p).astype(jnp.float32)
                         - other(cfg, h, p).astype(jnp.float32)).max()) > 0.1


@pytest.mark.parametrize("tile", [64, 1024], ids=["tiles_of_64", "one_tile"])
@pytest.mark.parametrize("frozen", [True, False],
                         ids=["frozen_router", "router_learns"])
def test_relu_gated_experts_routed_from_another_input_are_the_reference(
        tiny, monkeypatch, tile, frozen):
    """The expert layer with gated relu experts, holding experts 2 to 5 of
    8, its routing made from ANOTHER input than its own (the attention
    sublayer's normed input) and handed to it: values and gradients against
    the reference's loop over the held experts, with the sorted assignments
    walked two tiles and more at a time and in one. Frozen, the router and
    the routing's input take no gradient on either side; learning, both do,
    and agree."""
    monkeypatch.setattr(hybrid, "_ROW_TILE", tile)
    model, params = tiny
    cfg = hybrid.HybridConfig(**dict(SIZES, router_frozen=frozen))
    p, h, n1 = scaled(params["layers"]["01E"]), normed(15), normed(16)
    assert sorted(p) == ["norm", "router", "w_down", "w_up"]
    assert p["w_up"].shape == (4, 64, 64)  # [G | U], 32 wide each
    probe = jax.random.normal(jax.random.PRNGKey(9), h.shape, jnp.float32)

    def ours(p, h, n1):
        routing = hybrid._routing(cfg, n1.astype(jnp.bfloat16).reshape(
            -1, 64), p)
        return hybrid._moe(cfg, h.astype(jnp.bfloat16), p, *routing)

    def theirs(p, h, n1):
        return jnp.stack([ref.moe_mixer(cfg, p, row, by)
                          for row, by in zip(h, n1)])

    close(jax.jit(ours)(p, h, n1), jax.jit(theirs)(p, h, n1), VALUE_TOL)
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(ours(*a) * probe), (0, 1, 2)))(p, h, n1)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(theirs(*a) * probe), (0, 1, 2)))(p, h, n1)
    close(got[1], want[1], GRAD_TOL)
    for name in ("w_up", "w_down"):
        close(got[0][name], want[0][name], GRAD_TOL)
    if frozen:
        for side in (got, want):
            assert not np.any(np.asarray(side[0]["router"]))
            assert not np.any(np.asarray(side[2]))
    else:
        close(got[0]["router"], want[0]["router"], GRAD_TOL)
        close(got[2], want[2], GRAD_TOL)
        assert np.any(np.asarray(want[0]["router"]))
    # the weights are a softmax over the chosen logits
    chosen, weights = hybrid._route(cfg, n1.reshape(-1, 64), p)
    logits = n1.reshape(-1, 64) @ p["router"].T
    top, index = jax.lax.top_k(logits, cfg.top_k)
    assert np.array_equal(chosen, index)
    close(weights, jax.nn.softmax(top, -1), 1e-5)
    _, sizes = hybrid._dispatch_plan(chosen, cfg.experts_held)
    passes = int(hybrid._passes(sizes, np.gcd(chosen.size, tile)))
    assert passes >= 2 if tile == 64 else passes == 1


def test_four_ranks_of_sixteen_experts_sum_to_the_uncut_layer(mesh):
    """The cell's share at toy widths: a router over 64 experts, top 6, four
    ranks of 16 experts each, the routing made from the attention sublayer's
    input. The routed parts that all the shares give add up to what the
    uncut reference gives for the whole layer (there is no shared expert to
    count once), and one share alone does not."""
    sizes = dict(SIZES, pattern="*E", d_model=32, expert_width=16,
                 n_experts=64, top_k=6)
    uncut = hybrid.make_model(**dict(sizes, experts_first=0,
                                     experts_count=64))
    whole = scaled(uncut.init(jax.random.PRNGKey(4), mesh)["layers"]["01E"])
    h, n1 = normed(16, d=32), normed(17, d=32)
    total = 0.0
    for rank in range(4):
        cfg = hybrid.HybridConfig(**dict(sizes, experts_first=16 * rank,
                                         experts_count=16))
        held = slice(16 * rank, 16 * rank + 16)
        share = dict(whole, w_up=whole["w_up"][held],
                     w_down=whole["w_down"][held])
        routing = hybrid._routing(cfg, n1.astype(jnp.bfloat16).reshape(
            -1, 32), share)
        part = hybrid._moe(cfg, h.astype(jnp.bfloat16), share, *routing)
        total = total + part
    want = jnp.stack([ref.moe_mixer(uncut.config, whole, row, by)
                      for row, by in zip(h, n1)])
    close(total, want, VALUE_TOL)
    assert float(jnp.abs(part - total).max()) > 10 * VALUE_TOL \
        * float(jnp.abs(total).max())
    # the reference takes a share too: its part is the program's
    close(part, jnp.stack([ref.moe_mixer(cfg, share, row, by)
                           for row, by in zip(h, n1)]), VALUE_TOL)


# -- the whole model ------------------------------------------------------------------


@pytest.mark.parametrize("flash", [True, False],
                         ids=["interpreted_flash_kernels", "plain_path"])
def test_loss_and_every_leafs_gradient_are_the_references(tiny, mesh, flash):
    """bf16 program, per-layer remat, against the float32 reference: the
    loss and every leaf's gradient. The routers' leaves read zero on both
    sides. An expert layer's leaves rest on some tens of rows at this size,
    and a token whose two largest logits tie to bf16's rounding goes to
    another expert on one side: they get the room of one such token."""
    _, params = tiny
    model = hybrid.make_model(**dict(SIZES, remat=True, flash=flash))
    batch = model.synthetic_batch(np.random.default_rng(0), 2)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, mesh)))(params)
    assert abs(float(loss) - ref.reference_loss(
        model.config, params, batch)) < 2e-3
    want_loss, want = ref.reference_loss_and_grads(model.config, params,
                                                   batch)
    assert abs(want_loss - float(loss)) < 2e-3
    far = leaf_distances(grads, want)
    for leaf, d in far.items():
        assert d < (0.2 if "E']" in leaf else 0.05), far
    for layer in ("01E", "03E", "05E", "07E"):
        assert not np.any(np.asarray(grads["layers"][layer]["router"]))
        assert not np.any(np.asarray(want["layers"][layer]["router"]))
        assert far[f"['layers']['{layer}']['router']"] == 0.0
    # the traced batch loss and the gradients alone are the same numbers
    assert abs(float(jax.jit(lambda p: ref.loss_fn(
        model.config, p, batch))(params)) - want_loss) < 1e-5
    assert max(leaf_distances(ref.reference_grads(
        model.config, params, batch), want).values()) < 1e-6


def test_at_float32_with_a_learning_router_every_leaf_agrees(tiny, mesh,
                                                             monkeypatch):
    """Every operand float32 in the program (the plain path), the routers
    learning on both sides: the loss and every leaf's gradient agree, the
    routers' and the attention norms' (which the routing reads) too."""
    monkeypatch.setattr(hybrid, "bf16", jnp.float32)
    _, params = tiny
    model = hybrid.make_model(**dict(SIZES, flash=False, router_frozen=False))
    batch = model.synthetic_batch(np.random.default_rng(2), 2)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, mesh)))(params)
    want_loss, want = ref.reference_loss_and_grads(model.config, params,
                                                   batch)
    assert abs(float(loss) - want_loss) < 1e-4
    far = leaf_distances(grads, want)
    assert max(far.values()) < 0.02, far
    assert np.any(np.asarray(want["layers"]["01E"]["router"]))


def test_three_steps_through_the_trainer_leave_the_routers_where_they_were(
        mesh):
    model = hybrid.make_model(**dict(SIZES, remat=True))
    trainer = Trainer(model, mesh, TrainerConfig(optimizer="adam",
                                                 learning_rate=1e-3))
    state = trainer.init_state()
    first = jax.device_get(state.params)
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(3):
        state, loss = trainer.train_step(
            state, trainer.place_batch(model.synthetic_batch(rng, 2)))
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    last = jax.device_get(state.params)
    for layer in ("01E", "07E"):
        assert np.array_equal(first["layers"][layer]["router"],
                              last["layers"][layer]["router"])
        assert not np.array_equal(first["layers"][layer]["w_up"],
                                  last["layers"][layer]["w_up"])
    for layer in ("00*", "02W"):
        assert not np.array_equal(first["layers"][layer]["wq"],
                                  last["layers"][layer]["wq"])


@pytest.mark.parametrize("flash", [True, False],
                         ids=["counted_by_the_kernels", "counted_by_the_mask"])
def test_window_stats_count_the_pairs_exactly(mesh, flash):
    model = hybrid.make_model(**dict(SIZES, flash=flash))
    params = model.init(jax.random.PRNGKey(0), mesh)
    batch = model.synthetic_batch(np.random.default_rng(1), 2)
    counter = get_registry().counter(
        "edl_window_pairs_visible_total", "", labelnames=("layer",))
    before = counter.value(layer="02W")
    stats = model.window_stats(params, batch)
    assert sorted(stats) == ["00*", "02W", "04W", "06W"]
    causal = 2 * S * (S + 1) // 2
    inside = 2 * (WINDOW * (WINDOW + 1) // 2 + (S - WINDOW) * WINDOW)
    assert stats["00*"] == {"visible": causal, "causal": causal}
    for layer in ("02W", "04W", "06W"):
        assert stats[layer] == {"visible": inside, "causal": causal}
    assert inside == 2 * ref.visible_pairs(S, WINDOW)
    assert counter.value(layer="02W") - before == inside
    # a model without W layers has no such hook; one without E layers that
    # read another input still routes from its own
    assert hybrid.make_model(seq_len=S).window_stats is None
    # the routing hook sees the routing `_stack` handed the layer
    routing = model.routing_stats(params, batch)
    assert sorted(routing) == ["01E", "03E", "05E", "07E"]
    for st in routing.values():
        assert st["made"] == 2 * S * 2 and st["dropped"] == 0
        assert sum(st["per_expert"]) == st["held"] > 0


def test_the_program_counts_the_work_the_costs_count():
    model = hybrid.make_model(**SIZES)
    per = hybrid.forward_flops_per_token(model.config)
    q, D = 14 * 16, 64
    seen = (WINDOW * (WINDOW + 1) / 2 + (S - WINDOW) * WINDOW) / S
    assert per["W"] == 2 * D * (q + 2 * 32) + 2 * q * D + 4 * seen * q
    assert per["*"] == 2 * D * (q + 2 * 32) + 2 * q * D + 0.5 * 4 * S * q
    assert per["E"] == 2 * D * 8 + 2 * 4 / 8 * 6 * D * 32
