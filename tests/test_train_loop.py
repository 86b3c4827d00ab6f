"""Train-loop tests: every model family steps and learns on the 8-dev mesh."""

import jax
import numpy as np
import pytest

from edl_tpu.models import ctr, fit_a_line, mnist, word2vec
from edl_tpu.parallel import MeshSpec, build_mesh, local_mesh
from edl_tpu.runtime import Trainer, TrainerConfig


def batches(model, rng, batch_size, n):
    for _ in range(n):
        yield model.synthetic_batch(rng, batch_size)


def test_fit_a_line_converges():
    mesh = local_mesh()
    trainer = Trainer(fit_a_line.MODEL, mesh, TrainerConfig(optimizer="sgd", learning_rate=0.1))
    state = trainer.init_state()
    rng = np.random.default_rng(0)
    state, metrics = trainer.run(state, batches(fit_a_line.MODEL, rng, 64, 200))
    assert metrics["final_loss"] < 0.05, metrics
    # learned weights approach the generating ones
    w = np.asarray(state.params["w"]).ravel()
    np.testing.assert_allclose(w, fit_a_line._TRUE_W, atol=0.1)


def test_ctr_deep_wide_steps_and_descends():
    mesh = local_mesh()
    trainer = Trainer(ctr.MODEL, mesh, TrainerConfig(optimizer="adagrad", learning_rate=0.05))
    state = trainer.init_state()
    rng = np.random.default_rng(1)
    state, metrics = trainer.run(state, batches(ctr.MODEL, rng, 32, 8))
    assert np.isfinite(metrics["final_loss"])
    assert metrics["final_loss"] < metrics["mean_loss"] + 0.1  # not diverging
    # sparse tables sharded: 8 shards of the padded vocab
    table = state.params["deep_table"]
    assert table.shape[0] % 8 == 0
    assert int(state.step) == 8


def test_ctr_on_multiaxis_mesh():
    """CTR with a dedicated expert axis: table sharded 4-way, batch 2-way."""
    mesh = build_mesh(MeshSpec({"data": 2, "expert": 4}))
    model = ctr.make_model(shard_axis="expert", batch_axis="data", sparse_dim=10007)
    trainer = Trainer(model, mesh, TrainerConfig())
    state = trainer.init_state()
    rng = np.random.default_rng(2)
    state, metrics = trainer.run(state, batches(model, rng, 16, 2))
    assert np.isfinite(metrics["final_loss"])
    assert state.params["deep_table"].shape[0] == 10240  # rescale-stable padding


def test_word2vec_steps():
    mesh = local_mesh()
    trainer = Trainer(word2vec.MODEL, mesh, TrainerConfig(learning_rate=1e-2))
    state = trainer.init_state()
    rng = np.random.default_rng(3)
    state, metrics = trainer.run(state, batches(word2vec.MODEL, rng, 64, 10))
    assert np.isfinite(metrics["final_loss"])
    assert metrics["final_loss"] < np.log(word2vec.VOCAB) + 1.0


def test_mnist_learns_synthetic_digits():
    mesh = local_mesh()
    trainer = Trainer(mnist.MODEL, mesh, TrainerConfig(learning_rate=1e-3))
    state = trainer.init_state()
    rng = np.random.default_rng(4)
    first_loss = None

    def on_step(i, loss):
        nonlocal first_loss
        if i == 1:
            first_loss = loss

    state, metrics = trainer.run(
        state, batches(mnist.MODEL, rng, 64, 30), on_step=on_step
    )
    assert metrics["final_loss"] < first_loss * 0.7, (first_loss, metrics)
    test_batch = mnist.MODEL.synthetic_batch(rng, 256)
    acc = float(
        jax.jit(mnist.accuracy)(state.params, trainer.place_batch(test_batch))
    )
    assert acc > 0.5, acc  # far above the 0.1 random baseline


def test_zero1_sharded_opt_state_matches_replicated():
    """ZeRO-1 (shard_opt_state): moments shard over the data axis — each
    chip holds 1/N — while the training trajectory stays identical to the
    replicated-optimizer run, and the sharding survives the jitted update
    (donated buffers keep the layout step over step)."""
    from jax.sharding import NamedSharding

    from edl_tpu.models import transformer
    from edl_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec({"data": 8}))
    model = transformer.make_model(
        vocab_size=64, d_model=32, n_layers=2, n_heads=8, d_ff=64, seq_len=16
    )
    rng = np.random.default_rng(0)
    batches = [model.synthetic_batch(rng, 8) for _ in range(3)]

    losses = {}
    final_states = {}
    for tag, zero1 in (("rep", False), ("zero1", True)):
        trainer = Trainer(
            model, mesh,
            TrainerConfig(optimizer="adam", learning_rate=1e-3,
                          shard_opt_state=zero1),
        )
        state = trainer.init_state()
        ls = []
        for b in batches:
            state, loss = trainer.train_step(state, trainer.place_batch(b))
            ls.append(float(loss))
        losses[tag] = ls
        final_states[tag] = state

    # identical math
    assert losses["rep"] == pytest.approx(losses["zero1"], rel=1e-6)

    def shardable(leaf):
        return (
            getattr(leaf, "ndim", 0) > 0
            and any(s > 0 and s % 8 == 0 for s in leaf.shape)
        )

    def sharded_flags(state):
        """is-sharded flag for every moment tensor that COULD shard."""
        out = []
        for leaf in jax.tree_util.tree_leaves(state.opt_state):
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding) and shardable(leaf):
                out.append(any(s is not None for s in sh.spec))
        return out

    # replicated run: every moment fully replicated; zero1 run: EVERY moment
    # with a divisible dim is sharded — a partial fallback to replication
    # would silently forfeit the HBM savings.
    assert not any(sharded_flags(final_states["rep"]))
    z = sharded_flags(final_states["zero1"])
    assert z and all(z), f"moments fell back to replicated: {z}"
    # ...the layout survived 3 donated jitted updates (not just init), and
    # params themselves stay replicated (ZeRO-1, not ZeRO-3)
    for p in jax.tree_util.tree_leaves(final_states["zero1"].params):
        sh = getattr(p, "sharding", None)
        if isinstance(sh, NamedSharding):
            assert all(s is None for s in sh.spec)


def _tiny_hybrid():
    """Sparse-attention and expert layers of `models.hybrid`, the family of
    most benchmark cells, at toy widths."""
    from edl_tpu.models import hybrid

    return hybrid.make_model(
        pattern="SE", seq_len=64, vocab_size=64, d_model=32, n_heads=4,
        n_kv_heads=2, head_dim=8, indexer_heads=2, indexer_head_dim=8,
        indexer_topk=16, router_score="softmax", expert_act="silu",
        shared_width=0, n_experts=4, experts_count=2, experts_first=1,
        top_k=2, expert_width=16, remat=True,
    )


@pytest.mark.parametrize(
    "make_model, devices, config",
    [
        (lambda: fit_a_line.MODEL, 1, TrainerConfig(optimizer="adam")),
        (lambda: fit_a_line.MODEL, 1,
         TrainerConfig(optimizer="sgd", momentum=0.9)),
        (lambda: mnist.MODEL, 2,
         TrainerConfig(optimizer="adam", shard_opt_state=True)),
        (lambda: ctr.make_model(sparse_dim=1001), 2,
         TrainerConfig(optimizer="adagrad")),
        (_tiny_hybrid, 1, TrainerConfig(optimizer="adam")),
    ],
    ids=["adam-1dev", "sgd-1dev", "adam-2dev-zero1", "adagrad-2dev-rowsharded",
         "adam-1dev-hybrid"],
)
def test_a_fresh_state_is_where_the_step_returns_it(make_model, devices, config):
    """Every leaf of `init_state()` — params, moments, counts, the step
    counter — is committed to the sharding the donating step returns for
    it, so three steps compile one program and no second one at step 2."""
    model = make_model()
    mesh = build_mesh(MeshSpec({"data": devices}), jax.devices()[:devices])
    trainer = Trainer(model, mesh, config)
    state = trainer.init_state()

    def shardings(tree):
        return jax.tree_util.tree_map(lambda x: x.sharding, tree)

    fresh = shardings(state)
    assert all(x._committed for x in jax.tree_util.tree_leaves(state))
    rng = np.random.default_rng(0)
    for n in range(3):
        state, _ = trainer.train_step(
            state, trainer.place_batch(model.synthetic_batch(rng, 8))
        )
        if n == 0:
            assert shardings(state) == fresh
    assert trainer._jit_cache_size() == 1
    if devices > 1:  # the case places something across the mesh
        assert any(
            any(a is not None for a in s.spec)
            for s in jax.tree_util.tree_leaves(fresh.opt_state)
        )
