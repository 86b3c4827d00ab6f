"""The hybrid decoder's sparse-attention layers (`edl_tpu/models/hybrid.py`,
kinds ``S`` and ``E`` with a softmax router and gated experts) against their
plain reference (`edl_tpu/models/sparse_reference.py`) on the CPU at toy
widths; the flash kernels' ``selection`` operand against explicit masked
attention through the Pallas interpreter; the selection's properties; the
share of a deployment; and that WITHOUT a selection the two accepted models'
train steps lower to the text they lowered to at the parent commit."""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import hybrid, sparse_reference as ref, transformer
from edl_tpu.obs.metrics import get_registry
from edl_tpu.ops import flash_attention
from edl_tpu.parallel import MeshSpec, build_mesh
from edl_tpu.runtime import Trainer, TrainerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = 128
TOPK = 32

#: bf16 matmuls (8 mantissa bits) against float32 `highest`: a value of order
#: 1 moves by up to about 1e-2, a gradient by a few percent of the tensor's
#: largest entry. A term left out moves either by its own size.
VALUE_TOL = 2e-2
GRAD_TOL = 5e-2

SIZES = dict(pattern="SESE", seq_len=S, vocab_size=256, d_model=64, n_heads=4,
             n_kv_heads=2, head_dim=16, indexer_heads=4, indexer_head_dim=16,
             indexer_topk=TOPK, router_score="softmax", expert_act="silu",
             shared_width=0, routed_scale=1.0, n_experts=8, experts_count=4,
             experts_first=2, top_k=2, expert_width=32, norm_eps=1e-6,
             rope_theta=1e7)


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


def close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


def check_mixer(program, reference, p, h, wrt):
    """Values, and gradients of a random projection of them with respect to
    the input and the leaves ``wrt``."""
    probe = jax.random.normal(jax.random.PRNGKey(9), h.shape, jnp.float32)

    def ours(p, h):
        return program(p, h.astype(jnp.bfloat16)).astype(jnp.float32)

    def theirs(p, h):
        return jnp.stack([reference(p, row, i) for i, row in enumerate(h)])

    close(jax.jit(ours)(p, h), jax.jit(theirs)(p, h), VALUE_TOL)
    got = jax.jit(jax.grad(
        lambda p, h: jnp.sum(ours(p, h) * probe), (0, 1)))(p, h)
    want = jax.jit(jax.grad(
        lambda p, h: jnp.sum(theirs(p, h) * probe), (0, 1)))(p, h)
    close(got[1], want[1], GRAD_TOL)
    for name in wrt:
        close(got[0][name], want[0][name], GRAD_TOL)


def test_the_reference_is_kept_twice_byte_for_byte():
    with open(os.path.join(REPO, "benchmarks", "reference_sparse.py")) as f, \
            open(ref.__file__) as g:
        assert f.read() == g.read()


# -- the flash kernels' selection operand -------------------------------------------


def masked_attention(q, k, v, selection, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    Sq, Sk = s.shape[-2:]
    seen = (jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None]) \
        & (selection[:, None] != 0)
    w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v,
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("seq, heads, head_dim", [
    (1024, 2, 128), (640, 4, 64), (200, 3, 64)],
    ids=["D128_tiles_of_512", "D64_two_heads_a_block_padded", "one_tile"])
def test_flash_with_a_selection_is_explicit_masked_attention(seq, heads,
                                                             head_dim):
    """A selection that differs by query (each query keeps itself and a
    random third of its past), the same for every head: the forward and the
    three gradients of the interpreted kernels against explicit scores under
    the mask, float32 in and out. A row's softmax and both backward kernels
    see exactly the selected causal pairs; pairs selected in the FUTURE (the
    operand has some) are still unseen."""
    key = jax.random.split(jax.random.PRNGKey(seq), 5)
    q, k, v, probe = (jax.random.normal(key[i], (2, seq, heads, head_dim),
                                        jnp.float32) for i in range(4))
    picked = jax.random.uniform(key[4], (2, seq, seq)) < 0.33
    picked = (picked | jnp.eye(seq, dtype=bool)[None]).astype(jnp.int8)
    scale = head_dim ** -0.5

    def ours(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               selection=picked)

    def theirs(q, k, v):
        return masked_attention(q, k, v, picked, scale)

    close(jax.jit(ours)(q, k, v), jax.jit(theirs)(q, k, v), 2e-3)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(ours(*a) * probe),
                           (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(theirs(*a) * probe),
                            (0, 1, 2)))(q, k, v)
    for a, b in zip(got, want):
        close(a, b, 2e-3)
    # and it is no all-pass: without the operand the result differs
    plain = flash_attention(q, k, v, causal=True, scale=scale)
    assert float(jnp.abs(plain - ours(q, k, v)).max()) > 0.05


def test_a_selection_of_every_causal_key_changes_nothing():
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 256, 2, 64),
                                 jnp.bfloat16) for i in range(3))
    every = jnp.ones((1, 256, 256), jnp.int8)
    with_it = flash_attention(q, k, v, selection=every)
    assert np.array_equal(np.asarray(with_it, np.float32), np.asarray(
        flash_attention(q, k, v), np.float32))


#: sha256 of `Trainer._jit_step.lower(...).as_text()` for a small model of
#: each accepted family, Adam, remat, 2 x 128 tokens, lowered for the CPU
#: (the kernels' bodies inline through the interpreter), taken at the PARENT
#: commit of PR 32 (66c9864) and equal on this tree: the flash kernels
#: without a selection, `_experts_held`, `_route` and `_stack` trace to the
#: programs they traced to. A PR that changes these programs on purpose
#: records the new text's hash here and says so.
#: PR 33 re-recorded "hybrid" ON PURPOSE (it was 12753bf7...f662a039):
#: `_attention` hands the flash kernels K and V as projected, a K/V head's
#: group of query heads to a grid step, so the step has no `jnp.repeat` of
#: K/V, no sum over the repeats, dK/dV leave `flash_bwd_dkv` in k's shape,
#: and a call with groups goes through `jax.jit` (equal calls share a
#: trace). "transformer" (no groups) is the parent's of PR 32, untouched.
#: Both re-recorded ON PURPOSE when `Trainer.init_state` began to commit
#: every leaf to the sharding the step returns, in the spelling it returns
#: (they were 6cda28d2...0c08d and 40b71001...58a08): the step and its
#: inputs' values are unchanged, only the lowering's input shardings. Each
#: new hash is the earlier tree's step lowered on the state that its own
#: first step returned, the program its steps 2 on ran: checked by
#: lowering on the earlier tree (init_state(), one `_jit_step`, lower on
#: its output) and on this tree (init_state()), with the same model, mesh
#: and batch as below.
LOWERED = {
    "transformer": "f9b0a9221daf9c13cf5de3db0dcb43564ba8abedde8866b99f6f31e95072528a",
    "hybrid": "2be4484728c4ab6970b0725e348564a57f421ee20923f60fea402508ced674e1",
}


@pytest.mark.parametrize("family", sorted(LOWERED))
def test_without_a_selection_the_accepted_steps_lower_as_at_the_parent(
        mesh, family):
    model = {
        "transformer": lambda: transformer.make_model(
            vocab_size=256, d_model=64, n_heads=4, d_ff=128, n_layers=2,
            seq_len=128, remat=True),
        "hybrid": lambda: hybrid.make_model(seq_len=128, remat=True),
    }[family]()
    trainer = Trainer(model, mesh, TrainerConfig(optimizer="adam"))
    tokens = jnp.zeros((2, 128), jnp.int32)
    text = trainer._jit_step.lower(
        trainer.init_state(), {"tokens": tokens, "targets": tokens}).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[family]


# -- the selection ------------------------------------------------------------------


def sorted_topk(scores, topk):
    """What a stable sort keeps: per row the ``min(t + 1, topk)`` causal keys
    of largest score, ties to the earlier key."""
    scores = np.asarray(scores)
    out = np.zeros(scores.shape, np.int8)
    for b, block in enumerate(scores):
        for t, row in enumerate(block):
            order = np.argsort(-row[:t + 1], kind="stable")
            out[b, t, order[:min(t + 1, topk)]] = 1
    return out


@pytest.mark.parametrize("case", ["normal", "ties", "zeros_of_both_signs"])
def test_select_keeps_what_a_sort_keeps(case):
    """`top_k_select` (interpreted) on (B, keys, queries) scores: count
    ``min(t + 1, k)``, nothing in the future whatever the future holds (NaN
    here), equal to a stable sort's top k: on distinct scores, on scores with
    many ties ON the threshold (small integers: the earliest tied keys take
    the places left), on zeros of both signs (equal, as a sort of floats has
    them), and on rows shorter than k. Three blocks of 128 queries, tiles of
    384 keys."""
    n = 384
    scores = jax.random.normal(jax.random.PRNGKey(3), (2, n, n))
    if case == "ties":
        scores = jnp.round(scores * 2)
    if case == "zeros_of_both_signs":
        scores = jnp.where(scores > 0.3, scores, jnp.where(
            scores > 0, 0.0, -0.0))
    seen = np.arange(n)[None, :] <= np.arange(n)[:, None]
    handed = jnp.where(seen, scores, jnp.nan).swapaxes(1, 2)
    got = np.asarray(jax.jit(lambda x: hybrid._select(x, TOPK))(
        handed)).swapaxes(1, 2)
    t = np.arange(n)
    assert np.array_equal(got.sum(-1), np.broadcast_to(
        np.minimum(t + 1, TOPK), (2, n)))
    assert not np.any(got * ~seen)
    assert np.array_equal(got, sorted_topk(scores, TOPK))


def test_a_sequence_that_is_no_multiple_of_128_is_padded(mesh):
    """S = 200: the kernels take 256 positions, and the 56 after the last
    real one are in every real query's future."""
    model = hybrid.make_model(**dict(SIZES, seq_len=200))
    params = model.init(jax.random.PRNGKey(0), mesh)
    batch = model.synthetic_batch(np.random.default_rng(0), 1)
    for st in model.selection_stats(params, batch).values():
        assert st["picked"].shape[-1] == 200
        assert (st["future"], st["miscounted"]) == (0, 0)
        assert st["selected"] == TOPK * (TOPK + 1) // 2 + (200 - TOPK) * TOPK


def test_selection_is_the_references_own_at_float32(tiny, monkeypatch):
    """With float32 operands in the program's indexer the whole selection
    equals the reference's own, bit for bit but for rounding's few keys."""
    model, params = tiny
    monkeypatch.setattr(hybrid, "bf16", jnp.float32)
    p = params["layers"]["00S"]
    x = normed(11)
    got = jax.jit(lambda x: hybrid._selection(model.config, x, p))(x)
    for i, row in enumerate(x):
        want = ref.own_selection(model.config, p, ref.rmsnorm(
            row, p["norm"], model.config.norm_eps))
        assert np.mean(np.asarray(got[i]) != np.asarray(want)) < 2e-4


def test_indexer_scores_are_the_references(tiny):
    model, params = tiny
    cfg, p = model.config, params["layers"]["00S"]
    h = normed(12)
    qI, kI, w = hybrid._indexer_proj(cfg, h.astype(jnp.bfloat16), p)
    got = hybrid._scores(qI, kI, w).swapaxes(1, 2)  # (B, queries, keys)
    rows = jnp.arange(S)
    for i, row in enumerate(h):
        want = ref.indexer_scores(cfg, p, row, rows)
        seen = np.asarray(want) > -np.inf
        close(np.where(seen, got[i], 0), np.where(seen, want, 0), VALUE_TOL)


def test_selection_stats_counts_and_samples(tiny):
    model, params = tiny
    batch = model.synthetic_batch(np.random.default_rng(1), 2)
    counter = get_registry().counter(
        "edl_sparse_keys_selected_total", "", labelnames=("layer",))
    before = counter.value(layer="00S")
    stats = model.selection_stats(params, batch)
    assert sorted(stats) == ["00S", "02S"]
    rows = hybrid.sampled_rows(S, TOPK)
    assert rows[0] == 0 and rows[-1] == S - 1
    assert {TOPK // 2, TOPK - 1, TOPK, TOPK + 1} <= set(rows)
    kept = TOPK * (TOPK + 1) // 2 + (S - TOPK) * TOPK
    whole = model.selection_stats(params, batch, whole=True)
    for layer, st in stats.items():
        assert st["rows"] == rows
        assert (st["selected"], st["visible"], st["future"],
                st["miscounted"]) == (2 * kept, 2 * S * (S + 1) // 2, 0, 0)
        assert st["picked"].shape == (2, len(rows), S)
        assert st["input"].shape == (2, S, 64)
        assert np.array_equal(st["picked"].sum(-1)[0],
                              np.minimum(np.asarray(rows) + 1, TOPK))
        assert "selection" not in st
        assert np.array_equal(np.asarray(
            whole[layer]["selection"])[:, list(rows)], st["picked"])
        # against the reference's own indexer on the same layer input
        for i in range(2):
            d = ref.selection_distances(
                model.config, params["layers"][layer], st["input"][i], rows,
                st["picked"][i])
            assert not d["future"].any()
            assert d["differ"].max() <= 2 / TOPK and d["band"].max() < 0.05
    # selection_stats ran twice: the registry's counter saw both
    assert counter.value(layer="00S") - before == 2 * stats["00S"]["selected"]
    # a model without S layers has no such hook
    assert hybrid.make_model(seq_len=S).selection_stats is None


def test_selection_distances_read_a_wrong_selection(tiny):
    """The latest k keys in place of the top k: the right count, and nearly
    every key differs, far from the threshold."""
    model, params = tiny
    cfg, p = model.config, params["layers"]["00S"]
    x = np.asarray(normed(13)[0])
    rows = np.asarray(hybrid.sampled_rows(S, TOPK))
    at = np.arange(S)
    recent = (at[None, :] <= rows[:, None]) \
        & (at[None, :] > rows[:, None] - TOPK)
    d = ref.selection_distances(cfg, p, x, rows, recent.astype(np.int8))
    assert np.array_equal(d["count"], np.minimum(rows + 1, TOPK))
    assert d["differ"][-1] > 0.5 and d["band"].max() > 1.0
    assert d["differ"][0] == 0  # a row that keeps every key cannot differ


# -- the layers against the reference ------------------------------------------------


def scaled(p, scale=20.0):
    """Matrices scaled up so that every term is of a size that shows."""
    return {k: v * scale if v.ndim >= 2 else v for k, v in p.items()}


@pytest.mark.parametrize("flash", [True, False],
                         ids=["interpreted_flash_kernel", "dense_path"])
def test_rotary_qk_norm_and_selected_attention_are_the_reference(tiny, flash):
    """`_sparse_attention` under a handed selection (the program's own for
    these inputs) against the reference given the same: rotary over the
    whole head, the per-head RMS norm of q and k with their learned scales
    (set away from 1), grouped heads, the softmax over the selected keys."""
    model, params = tiny
    cfg = hybrid.HybridConfig(**dict(SIZES, flash=flash))
    p = scaled(params["layers"]["00S"])
    p["q_norm"] = 1.0 + 0.3 * jnp.cos(jnp.arange(16.0))
    p["k_norm"] = 1.0 - 0.3 * jnp.sin(jnp.arange(16.0))
    h = normed(14)
    picked = jax.jit(lambda h: hybrid._selection(cfg, h, p))(h)
    assert int(picked.sum()) == 2 * (TOPK * (TOPK + 1) // 2
                                     + (S - TOPK) * TOPK)
    check_mixer(lambda p, h: hybrid._sparse_attention(cfg, h, p, picked),
                lambda p, row, i: ref.attention_mixer(cfg, p, row, picked[i]),
                p, h, ("wq", "wk", "wv", "wo", "q_norm", "k_norm"))


def test_rope_is_a_rotation_by_position():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 3, 16))
    got = hybrid._rope(x, 1e4)
    close(got[0], ref.rope(x[0], jnp.arange(8), 1e4), 1e-5)
    assert np.allclose(got[0, 0], x[0, 0])  # position 0 is left alone
    assert np.allclose(jnp.linalg.norm(got, axis=-1),
                       jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # q at t against k at s depends on t - s alone
    q, k = x[:, :, 0], x[:, :, 1]
    dots = jnp.einsum("td,sd->ts", hybrid._rope(jnp.broadcast_to(
        q[:, :1], q.shape), 1e4)[0], hybrid._rope(jnp.broadcast_to(
            k[:, :1], k.shape), 1e4)[0])
    assert np.allclose(dots[3, 1], dots[6, 4], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("tile", [64, 1024], ids=["tiles_of_64", "one_tile"])
def test_gated_experts_under_a_softmax_router_are_the_reference(
        tiny, monkeypatch, tile):
    """The expert layer with a softmax router (no bias, renormalised, no
    scale) and gated experts, no shared one, holding experts 2 to 5 of 8:
    values and gradients against the reference's loop over the held experts,
    with the sorted assignments walked two tiles and more at a time and in
    one."""
    monkeypatch.setattr(hybrid, "_ROW_TILE", tile)
    model, params = tiny
    cfg, p, h = model.config, scaled(params["layers"]["01E"]), normed(15)
    assert sorted(p) == ["norm", "router", "w_down", "w_up"]
    tok = h.reshape(-1, 64)
    chosen, weights = hybrid._route(cfg, tok, p)
    s = jax.nn.softmax(tok @ p["router"].T, axis=-1)
    top, index = jax.lax.top_k(s, cfg.top_k)
    assert np.array_equal(chosen, index)
    close(weights, top / top.sum(-1, keepdims=True), 1e-5)
    _, sizes = hybrid._dispatch_plan(chosen, cfg.experts_held)
    passes = int(hybrid._passes(sizes, np.gcd(chosen.size, tile)))
    assert passes >= 2 if tile == 64 else passes == 1
    check_mixer(lambda p, h: hybrid._moe(cfg, h, p),
                lambda p, row, i: ref.moe_mixer(cfg, p, row), p, h,
                ("router", "w_up", "w_down"))


def test_eight_ranks_of_sixteen_experts_sum_to_the_uncut_layer(mesh):
    """The cell's share at toy widths: a router over 128 experts, top 8,
    eight ranks of 16 experts each. The routed parts that all the shares
    give add up to what the uncut reference gives for the whole layer (there
    is no shared expert to count once), and one share alone does not."""
    sizes = dict(SIZES, pattern="E", d_model=32, expert_width=16,
                 n_experts=128, top_k=8)
    uncut = hybrid.make_model(**dict(sizes, experts_first=0,
                                     experts_count=128))
    whole = scaled(uncut.init(jax.random.PRNGKey(4), mesh)["layers"]["00E"])
    h = normed(16, d=32)
    total = 0.0
    for rank in range(8):
        cfg = hybrid.HybridConfig(**dict(sizes, experts_first=16 * rank,
                                         experts_count=16))
        held = slice(16 * rank, 16 * rank + 16)
        share = dict(whole, w_up=whole["w_up"][held],
                     w_down=whole["w_down"][held])
        part = hybrid._moe(cfg, h.astype(jnp.bfloat16), share)
        total = total + part
    want = jnp.stack([ref.moe_mixer(uncut.config, whole, row) for row in h])
    close(total, want, VALUE_TOL)
    assert float(jnp.abs(part - total).max()) > 10 * VALUE_TOL \
        * float(jnp.abs(total).max())
    # the reference takes a share too: its part is the program's
    close(part, jnp.stack([ref.moe_mixer(cfg, share, row) for row in h]),
          VALUE_TOL)


# -- the whole model ------------------------------------------------------------------


def leaf_distances(got, want):
    out = {}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        d, n = float(jnp.sum((g - w) ** 2)), float(jnp.sum(w ** 2))
        out[jax.tree_util.keystr(path)] = (d / n) ** 0.5 if n else (
            0.0 if d == 0 else float("inf"))
    return out


def test_loss_and_gradients_with_a_handed_selection(tiny, mesh):
    """bf16 program, flash kernels interpreted, per-layer remat, against the
    reference GIVEN the program's selection: the loss and every leaf's
    gradient; the indexer's leaves read zero on both sides."""
    _, params = tiny
    model = hybrid.make_model(**dict(SIZES, remat=True))
    batch = model.synthetic_batch(np.random.default_rng(0), 2)
    given = {layer: st["selection"] for layer, st in model.selection_stats(
        params, batch, whole=True).items()}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, mesh)))(params)
    assert abs(float(loss) - ref.reference_loss(
        model.config, params, batch, given)) < 2e-3
    want = ref.reference_grads(model.config, params, batch, given)
    both = ref.reference_loss_and_grads(model.config, params, batch, given)
    assert abs(both[0] - float(loss)) < 2e-3
    assert max(leaf_distances(both[1], want).values()) < 1e-6
    far = leaf_distances(grads, want)
    assert max(far.values()) < 0.05, far
    for layer in ("00S", "02S"):
        for leaf in ("ix_wq", "ix_wk", "ix_ww", "ix_norm", "ix_norm_b"):
            assert not np.any(np.asarray(grads["layers"][layer][leaf]))
            assert not np.any(np.asarray(want["layers"][layer][leaf]))
    # the traced batch loss is the same number
    assert abs(float(jax.jit(lambda p: ref.loss_fn(
        model.config, p, batch, given))(params)) - float(loss)) < 2e-3


def test_loss_and_gradients_with_the_references_own_selection_at_float32(
        tiny, mesh, monkeypatch):
    """Every operand float32 in the program (the dense path: explicit scores
    under the selection), the reference left to its OWN indexer: the two
    selections agree, and so do the loss and every leaf's gradient."""
    monkeypatch.setattr(hybrid, "bf16", jnp.float32)
    _, params = tiny
    model = hybrid.make_model(**dict(SIZES, flash=False))
    batch = model.synthetic_batch(np.random.default_rng(2), 2)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, mesh)))(params)
    assert abs(float(loss) - ref.reference_loss(
        model.config, params, batch)) < 1e-4
    want = ref.reference_grads(model.config, params, batch)
    far = leaf_distances(grads, want)
    assert max(far.values()) < 0.02, far
    # the runner's path, a layer at a time, is the same reference
    both = ref.reference_loss_and_grads(model.config, params, batch)
    assert abs(both[0] - ref.reference_loss(
        model.config, params, batch)) < 1e-5
    assert max(leaf_distances(both[1], want).values()) < 1e-5


def test_three_steps_through_the_trainer_leave_the_indexer_where_it_was(mesh):
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
    for leaf in ("ix_wq", "ix_wk", "ix_ww", "ix_norm", "ix_norm_b"):
        assert np.array_equal(first["layers"]["00S"][leaf],
                              last["layers"]["00S"][leaf])
    assert not np.array_equal(first["layers"]["00S"]["wq"],
                              last["layers"]["00S"]["wq"])
    assert not np.array_equal(first["layers"]["01E"]["w_up"],
                              last["layers"]["01E"]["w_up"])


def test_a_bad_score_activation_or_head_is_refused():
    for bad in (dict(router_score="tanh"), dict(expert_act="gelu"),
                dict(pattern="S", head_dim=15),
                dict(pattern="S", indexer_topk=0)):
        with pytest.raises(ValueError):
            hybrid.make_model(**bad)
