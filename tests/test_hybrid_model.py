"""The hybrid decoder (`edl_tpu/models/hybrid.py`) against its plain reference
(`edl_tpu/models/hybrid_reference.py`) on the CPU at a tiny preset: widths of
32 to 64, pattern ``ME*E-M``, 8 experts top-2, chunks of 8. Every mixer's
values and gradients, the expert layer's share of a deployment, the whole
model, and the heterogeneous state through `Trainer` and `ElasticWorker`."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.coordinator import InProcessCoordinator
from edl_tpu.models import hybrid, hybrid_reference as ref, resolve
from edl_tpu.parallel import MeshSpec, build_mesh
from edl_tpu.runtime import Trainer, TrainerConfig
from edl_tpu.runtime.data import SyntheticShardSource, shard_names
from edl_tpu.runtime.elastic import ElasticConfig, ElasticWorker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = 32  # four chunks of 8

#: bf16 matmuls (8 mantissa bits) against float32 `highest`: a value of
#: order 1 moves by up to about 1e-2, a gradient by a few percent of the
#: tensor's largest entry. A term left out moves either by its own size.
VALUE_TOL = 2e-2
GRAD_TOL = 5e-2


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshSpec({"data": 1}), jax.devices()[:1])


@pytest.fixture(scope="module")
def tiny(mesh):
    model = hybrid.make_model(seq_len=S)
    return model, model.init(jax.random.PRNGKey(0), mesh)


def normed(seed, d=64, batch=2):
    """A batch of normed layer inputs: float32 for the reference, the same
    numbers rounded to bf16 for the program."""
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
        return jnp.stack([reference(p, row) for row in h])

    close(jax.jit(ours)(p, h), jax.jit(theirs)(p, h), VALUE_TOL)
    got = jax.jit(jax.grad(
        lambda p, h: jnp.sum(ours(p, h) * probe), (0, 1)))(p, h)
    want = jax.jit(jax.grad(
        lambda p, h: jnp.sum(theirs(p, h) * probe), (0, 1)))(p, h)
    close(got[1], want[1], GRAD_TOL)
    for name in wrt:
        close(got[0][name], want[0][name], GRAD_TOL)


def test_the_reference_is_kept_twice_byte_for_byte():
    with open(os.path.join(REPO, "benchmarks", "reference_hybrid.py")) as f, \
            open(ref.__file__) as g:
        assert f.read() == g.read()
    with open(ref.__file__) as f:
        assert "models.hybrid " not in f.read().replace("import hybrid", "")


def test_the_references_blocks_change_nothing(mesh, monkeypatch):
    """The reference walks the recurrence and the attention's queries in
    blocks under `jax.checkpoint` so that its gradient fits at 8,192
    positions: blocks of 8 give what one block gives, loss and gradients."""
    model = hybrid.make_model(seq_len=S, pattern="M*")
    params = model.init(jax.random.PRNGKey(2), mesh)
    batch = model.synthetic_batch(np.random.default_rng(5), 1)
    whole = ref.reference_grads(model.config, params, batch)
    loss = ref.reference_loss(model.config, params, batch)
    monkeypatch.setattr(ref, "BLOCK", 8)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    assert abs(ref.reference_loss(model.config, params, batch) - loss) < 1e-6
    for a, b in zip(jax.tree_util.tree_leaves(whole), jax.tree_util.tree_leaves(
            ref.reference_grads(model.config, params, batch))):
        close(b, a, 1e-4)


#: the scan's cases: batch, length, heads, head width P, groups, state N,
#: chunk Q, the inputs' dtype, and whether only the first chunk has input
SCAN_CASES = {
    # today's tiny shape: four chunks of 8, the last ragged
    "tiny_ragged": (2, 29, 4, 16, 2, 16, 8, jnp.float32, False),
    # the cell's widths cut in extent: one group of 8 heads of 64, N 128,
    # chunks of 128 (what Mosaic tiles), three chunks with a ragged tail
    "lane_aligned_ragged": (1, 293, 8, 64, 1, 128, 128, jnp.float32, False),
    # input in the first chunk alone: whatever the four later chunks give
    # is the state carried from chunk to chunk, and nothing else
    "state_carried_over_chunks": (2, 40, 4, 16, 2, 16, 8, jnp.float32, True),
    "bf16_inputs": (2, 29, 4, 16, 2, 16, 8, jnp.bfloat16, False),
}


@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_chunked_scan_is_the_literal_recurrence(case):
    """`_ssd` (the kernels of `ops/ssd.py`, through the interpreter) over
    several chunks, so a non-zero state is carried from chunk to chunk,
    against one position at a time: the value, and the gradients of a random
    projection of it with respect to x, dt, A, B, C and the skip's D."""
    Bz, length, H, P, G, N, Q, dtype, first_chunk_only = SCAN_CASES[case]
    cfg = hybrid.HybridConfig(chunk_size=Q)
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (Bz, length, H, P)).astype(dtype)
    # steps of the published initialiser's size, so a chunk's decay is mild
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, length, H)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (Bz, length, G, N)).astype(dtype)
    Cm = jax.random.normal(ks[4], (Bz, length, G, N)).astype(dtype)
    D = jax.random.normal(ks[5], (H,))
    if first_chunk_only:
        x = x.at[:, Q:].set(0)

    def literal(x, dt, A, Bm, Cm, D):
        x, Bm, Cm = (a.astype(jnp.float32) for a in (x, Bm, Cm))
        Bh, Ch = (jnp.repeat(a, H // G, axis=2) for a in (Bm, Cm))

        def step(state, t):
            x_t, dt_t, B_t, C_t = t
            state = jnp.exp(dt_t * A)[..., None, None] * state \
                + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :]
            return state, jnp.einsum("bhpn,bhn->bhp", state, C_t,
                                    precision="highest")

        _, y = jax.lax.scan(step, jnp.zeros((Bz, H, P, N)), tuple(
            a.swapaxes(0, 1) for a in (x, dt, Bh, Ch)))
        return y.swapaxes(0, 1) + D[:, None] * x

    def chunked(x, dt, A, Bm, Cm, D):
        return hybrid._ssd(cfg, x, dt, A, Bm, Cm, D)

    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    args = (x, dt, A, Bm, Cm, D)
    got, want = jax.jit(chunked)(*args), jax.jit(literal)(*args)
    assert got.dtype == jnp.float32
    close(got, want, VALUE_TOL)
    if first_chunk_only:  # the later chunks read the carried state alone
        later = np.asarray(want - D[:, None] * x.astype(jnp.float32))[:, 3 * Q:]
        assert np.abs(later).max() > 1e-2
        close(got[:, 3 * Q:], want[:, 3 * Q:], VALUE_TOL)
    got, want = (jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * probe),
                                  tuple(range(6))))(*args)
                 for fn in (chunked, literal))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        close(g, w, GRAD_TOL)


def test_mamba_mixer_is_the_reference(tiny):
    model, params = tiny
    cfg, p = model.config, params["layers"]["00M"]
    # away from the initialiser's zeros and ones, so every term counts
    p = dict(p, conv_b=0.1 * jnp.arange(cfg.conv_dim, dtype=jnp.float32) % 0.7,
             D=jnp.linspace(0.5, 1.5, cfg.mamba_heads),
             gate_norm=jnp.linspace(0.5, 1.5, cfg.mamba_inner),
             in_proj=p["in_proj"] * 20, out_proj=p["out_proj"] * 50)
    check_mixer(lambda p, h: hybrid._mamba(cfg, h, p),
                lambda p, h: ref.mamba_mixer(cfg, p, h), p, normed(3),
                ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                 "gate_norm", "out_proj"))


@pytest.mark.parametrize("flash", [True, False],
                         ids=["interpreted_flash_kernel", "dense_path"])
def test_grouped_query_attention_is_the_reference(tiny, flash):
    model, params = tiny
    cfg = hybrid.HybridConfig(seq_len=S, flash=flash)
    p = {k: v * 20 for k, v in params["layers"]["02*"].items()}
    check_mixer(lambda p, h: hybrid._attention(cfg, h, p),
                lambda p, h: ref.attention_mixer(cfg, p, h), p, normed(4),
                ("wq", "wk", "wv", "wo"))


def moe_params(params, bias=None, scale=20.0):
    p = {k: v * scale for k, v in params["layers"]["01E"].items()}
    p["router_bias"] = jnp.zeros_like(p["router_bias"]) if bias is None \
        else jnp.asarray(bias, jnp.float32)
    return p


def test_expert_layer_with_a_selection_bias_is_the_reference(tiny):
    """The bias changes which experts are chosen and not the weights, which
    are the unbiased scores of the chosen, renormalised and scaled."""
    model, params = tiny
    cfg = model.config
    bias = [0.6, -0.6, 0.3, 0.0, -0.3, 0.5, 0.0, -0.5]
    p, h = moe_params(params, bias), normed(5)
    tok = h.reshape(-1, cfg.d_model)
    chosen, weights = hybrid._route(cfg, tok, p)
    plain, _ = hybrid._route(cfg, tok, moe_params(params))
    assert np.any(np.sort(chosen, 1) != np.sort(plain, 1))
    s = jax.nn.sigmoid(tok @ p["router"].T)
    picked = jnp.take_along_axis(s, chosen, 1)
    close(weights, picked / picked.sum(-1, keepdims=True) * cfg.routed_scale,
          1e-4)
    check_mixer(lambda p, h: hybrid._moe(cfg, h, p),
                lambda p, h: ref.moe_mixer(cfg, p, h), p, h,
                ("router", "w_up", "w_down", "shared_up", "shared_down"))
    # the selection bias takes no gradient
    g = jax.jit(jax.grad(lambda p: jnp.sum(hybrid._moe(
        cfg, h.astype(jnp.bfloat16), p))))(p)
    assert not np.any(np.asarray(g["router_bias"]))


def test_every_token_routed_to_one_held_expert_is_kept(mesh, monkeypatch):
    """Total imbalance: the bias sends every token's first choice to expert
    5, of the two experts held: more rows than a tile of the sorted buffer
    has, so the held experts' loop makes several passes. Nothing is dropped
    (`routing_stats` counts the rows the layer's product gave a value) and
    the layer equals the reference, whose loop over the experts has no
    capacity to run out of."""
    monkeypatch.setattr(hybrid, "_ROW_TILE", 32)
    model = hybrid.make_model(seq_len=S, pattern="E", experts_first=4,
                              experts_count=2)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(1), mesh)
    name = cfg.layer_names[0]
    p = {k: v * 20 for k, v in params["layers"][name].items()}
    p["router_bias"] = jnp.zeros((8,)).at[5].set(10.0)
    params["layers"][name] = p
    batch = model.synthetic_batch(np.random.default_rng(0), 2)
    stats = model.routing_stats(params, batch)[name]
    tokens = 2 * S
    assert stats["made"] == tokens * cfg.top_k and stats["dropped"] == 0
    assert stats["per_expert"][1] == tokens  # expert 5: every token
    assert int(hybrid._passes(jnp.asarray(stats["per_expert"]), 32)) >= 3
    assert sum(stats["per_expert"]) == stats["held"]
    h = normed(6)
    close(hybrid._moe(cfg, h.astype(jnp.bfloat16), p),
          jnp.stack([ref.moe_mixer(cfg, p, row) for row in h]), VALUE_TOL)


def test_shares_sum_to_the_uncut_layer(tiny):
    """Four ranks of two experts each: the routed parts that all the shares
    give, with the shared expert (which every rank computes alike) counted
    once, add up to what the uncut reference gives for the whole layer."""
    model, params = tiny
    whole = moe_params(params, [0.2, 0.0, -0.2, 0.1, 0.0, -0.1, 0.3, 0.0])
    h = normed(7)
    total = 0.0
    for rank in range(4):
        cfg = hybrid.HybridConfig(seq_len=S, experts_first=2 * rank,
                                  experts_count=2)
        share = dict(whole, w_up=whole["w_up"][2 * rank:2 * rank + 2],
                     w_down=whole["w_down"][2 * rank:2 * rank + 2])
        if rank:  # the shared expert counted once
            share["shared_down"] = jnp.zeros_like(share["shared_down"])
        total = total + hybrid._moe(cfg, h.astype(jnp.bfloat16), share)
    uncut = hybrid.HybridConfig(seq_len=S)  # holds all 8
    close(total, jnp.stack([ref.moe_mixer(uncut, whole, row) for row in h]),
          VALUE_TOL)
    # and a share alone is not the layer: the cut is real
    one = hybrid._moe(hybrid.HybridConfig(seq_len=S, experts_count=2), h.astype(
        jnp.bfloat16), dict(whole, w_up=whole["w_up"][:2],
                            w_down=whole["w_down"][:2]))
    assert float(jnp.abs(one - total).max()) > 10 * VALUE_TOL \
        * float(jnp.abs(total).max())


@pytest.mark.parametrize("tile", [16, 128], ids=["tiles_of_16", "one_tile"])
def test_tiles_of_the_sorted_assignments_give_what_one_product_gives(tiny,
                                                                     tile):
    """`_experts_held` walking the sorted assignments in tiles (groups
    straddle their edges; the loop stops after the last held one) gives the
    values and, by the backward loop that is written out, the gradients that
    autodiff gives for one grouped product over all T x k sorted rows,
    gathered back and weighed."""
    model, params = tiny
    cfg = hybrid.HybridConfig(seq_len=S, experts_first=2, experts_count=4)
    p = moe_params(params)
    tok = normed(8).reshape(-1, 64).astype(jnp.bfloat16)
    chosen, weights = hybrid._route(cfg, tok, p)
    order, sizes = hybrid._dispatch_plan(chosen, cfg.experts_held)
    held, total = int(sizes.sum()), chosen.size
    assert 16 < held < total - 16 and int(hybrid._passes(sizes, tile)) \
        == -(-held // tile)

    def whole(tok, weights, up, down):
        out = hybrid._experts_of(tok[order // cfg.top_k],
                                 up.astype(jnp.bfloat16),
                                 down.astype(jnp.bfloat16), sizes)
        back = out[jnp.argsort(order)].reshape(-1, cfg.top_k, 64)
        return jnp.einsum("tkd,tk->td", back.astype(jnp.float32), weights)

    def sum_of_squares(routed):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(routed(*a) ** 2), (0, 1, 2, 3)))(
                tok, weights, p["w_up"][2:6], p["w_down"][2:6])

    got = sum_of_squares(lambda *a: hybrid._experts_held(
        *a, order, sizes, tile))
    want = sum_of_squares(whole)
    close(got[0], want[0], 1e-5)
    for a, b in zip(got[1], want[1]):  # a tile's part is rounded to bf16
        close(a, b, 1e-2)


def test_no_held_assignment_still_runs_one_tile():
    sizes = jnp.zeros((4,), jnp.int32)
    assert int(hybrid._passes(sizes, 16)) == 1
    out, computed = hybrid._experts_held_loop(
        jnp.ones((16, 8), jnp.bfloat16), jnp.ones((16, 2)),
        jnp.ones((4, 8, 8)), jnp.ones((4, 8, 8)), jnp.arange(32), sizes, 16)
    assert out.shape == (16, 8) and not np.any(np.asarray(out))
    assert int(computed) == 0


def test_whole_model_loss_and_gradients_are_the_reference(tiny, mesh):
    model, params = tiny
    cfg = model.config
    for name in cfg.layer_names:  # a non-zero selection bias in every E layer
        if name[-1] == "E":
            params = jax.tree_util.tree_map(lambda x: x, params)
            params["layers"][name]["router_bias"] = jnp.linspace(-0.3, 0.3, 8)
    batch = model.synthetic_batch(np.random.default_rng(0), 2)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, mesh)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(cfg, p, batch)))(params)
    # 64 tokens at a loss of 6.2: bf16 moves a token's loss by about 1e-2
    assert abs(float(loss) - float(want)) < 2e-3
    assert abs(float(want) - ref.reference_loss(cfg, params, batch)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want_leaf in zip(flat, jax.tree_util.tree_leaves(
            want_grads)):
        if np.abs(np.asarray(want_leaf)).max() > 1e-5:  # A_log, dt_bias: ~1e-6
            close(got, want_leaf, GRAD_TOL), jax.tree_util.keystr(path)


def test_remat_and_the_chunked_loss_change_nothing(mesh):
    batch = hybrid.make_model(seq_len=S).synthetic_batch(
        np.random.default_rng(3), 2)
    got = []
    for kw in ({}, {"remat": True, "loss_chunk": 8}):
        model = hybrid.make_model(seq_len=S, pattern="ME", **kw)
        params = model.init(jax.random.PRNGKey(0), mesh)
        got.append(jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, batch, mesh)))(params))
    assert abs(float(got[0][0]) - float(got[1][0])) < 1e-5
    # the same arithmetic in another order: bf16 roundings fall differently
    for a, b in zip(*(jax.tree_util.tree_leaves(g[1]) for g in got)):
        close(a, b, 2e-2)


def test_three_steps_through_the_trainer():
    """The heterogeneous tree (no stacked ``blocks`` leaf) inits, places and
    steps through `Trainer` as any model; two virtual devices share the
    batch. The selection bias, which takes no gradient, stays put."""
    model = hybrid.make_model(seq_len=S, pattern="M*E-")
    mesh = build_mesh(MeshSpec({"data": 2}), jax.devices()[:2])
    trainer = Trainer(model, mesh, TrainerConfig(optimizer="adam",
                                                 learning_rate=3e-3))
    state = trainer.init_state()
    assert "blocks" not in state.params
    before = jax.device_get(state.params)
    rng = np.random.default_rng(0)
    batch = trainer.place_batch(model.synthetic_batch(rng, 8))
    losses = []
    for _ in range(3):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert int(state.step) == 3 and all(np.isfinite(losses))
    assert losses[2] < losses[0]
    after = jax.device_get(state.params)
    for name in model.config.layer_names:
        moved = {k: float(np.abs(after["layers"][name][k]
                                 - before["layers"][name][k]).max())
                 for k in after["layers"][name]}
        assert all(v > 0 for k, v in moved.items() if k != "router_bias"), (
            name, moved)
        assert moved.get("router_bias", 0.0) == 0.0


def test_state_round_trip_through_the_elastic_workers_checkpoint(tmp_path):
    """Save by one `ElasticWorker`, restore by the next: every leaf of the
    heterogeneous state comes back and the step count goes on."""
    model = hybrid.make_model(seq_len=S, pattern="ME*")
    source = SyntheticShardSource(model, batch_size=8, batches_per_shard=1)
    seen = []

    def worker(shards):  # each a job of its own: the directory is what joins
        coord = InProcessCoordinator(task_lease_sec=60.0,
                                     heartbeat_ttl_sec=60.0)
        coord.client("admin").add_tasks(shards)
        return ElasticWorker(
            model, coord.client("trainer-0"), source,
            ElasticConfig(checkpoint_dir=str(tmp_path / "ck"),
                          checkpoint_interval=10**9,
                          trainer=TrainerConfig(optimizer="adam"),
                          step_callback=lambda step, state: seen.append(
                              (step, jax.device_get(state)))),
            device_planner=lambda world: jax.devices()[:1])

    worker(shard_names("first", 2)).run()
    assert [step for step, _ in seen] == [1, 2]
    saved = seen[-1][1]
    second = worker(shard_names("second", 1))
    second.run()
    assert seen[-1][0] == 3  # restored at 2, one more step
    fresh = Trainer(model, build_mesh(MeshSpec({"data": 1}),
                                      jax.devices()[:1]),
                    TrainerConfig(optimizer="adam"))
    restored = second.ckpt.restore(
        jax.eval_shape(fresh.init_state), fresh.mesh,
        jax.tree_util.tree_map(lambda _: None, saved), step=2)
    for a, b in zip(jax.tree_util.tree_leaves(saved),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_configuration_file_counts_its_parameters(mesh):
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    kwargs = {ours: config[theirs]
              for theirs, ours in config["maps_to"].items()}
    model = resolve(config["model"], dict(kwargs, seq_len=8192))
    shapes = jax.eval_shape(lambda key: model.init(key, mesh),
                            jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert count == config["parameters"] == 666_963_456
    cfg = model.config
    assert cfg.pattern == "MEMEM*EME" and cfg.experts_held == (0, 8)
    assert (cfg.n_experts, cfg.top_k, cfg.vocab_size) == (128, 6, 16384)
    assert cfg.mamba_inner == 4096 and cfg.conv_dim == 6144
    per_kind = {kind: sum(int(np.prod(s)) for s in hybrid._layer_shapes(
        cfg, kind).values()) for kind in "M*E"}
    assert per_kind == {"M": 38_744_896, "*": 23_399_040, "E": 100_125_440}


def test_serving_and_export_refuse_the_model(tmp_path, tiny):
    from edl_tpu.runtime.export import (load_inference_model,
                                        save_inference_model)
    from edl_tpu.serving.lm import require_lm_servable

    model, params = tiny
    with pytest.raises(NotImplementedError, match="state beside K/V"):
        save_inference_model(str(tmp_path / "art"), "hybrid", params,
                             config={"seq_len": S})
    assert not os.path.exists(tmp_path / "art")
    with pytest.raises(NotImplementedError, match="state beside K/V"):
        require_lm_servable(model)
    # an artifact that names the module is refused before any weight is read
    os.makedirs(tmp_path / "forged")
    with open(tmp_path / "forged" / "manifest.json", "w") as f:
        json.dump({"format": 1, "model": "hybrid",
                   "weights": "none.npz", "leaves": []}, f)
    with pytest.raises(NotImplementedError, match="state beside K/V"):
        load_inference_model(str(tmp_path / "forged"))


def test_a_bad_pattern_or_share_is_refused():
    with pytest.raises(ValueError, match="layer kinds"):
        hybrid.make_model(pattern="MX")
    with pytest.raises(ValueError, match="experts_held"):
        hybrid.make_model(experts_first=6, experts_count=4)
