"""Pipeline schedule vs sequential-stage oracle on the virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.parallel import MeshSpec, build_mesh
from edl_tpu.parallel.pipeline import pipeline_apply


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _stack_params(rng, n_stages, d):
    return {
        "w": jnp.asarray(rng.standard_normal((n_stages, d, d)) * 0.5, jnp.float32),
        "b": jnp.asarray(rng.standard_normal((n_stages, d)) * 0.1, jnp.float32),
    }


def _sequential(params, x, n_stages):
    for s in range(n_stages):
        x = _stage_fn({"w": params["w"][s], "b": params["b"][s]}, x)
    return x


@pytest.mark.parametrize("axes,microbatches", [
    ({"pipe": 4, "data": 2}, None),
    ({"pipe": 8}, 8),
    ({"pipe": 2, "data": 4}, 4),
])
def test_matches_sequential(axes, microbatches):
    rng = np.random.default_rng(0)
    n = axes["pipe"]
    params = _stack_params(rng, n, 8)
    x = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    mesh = build_mesh(MeshSpec(axes))
    got = pipeline_apply(
        _stage_fn, params, x, mesh, microbatches=microbatches
    )
    want = _sequential(params, x, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_gradients_match_sequential():
    rng = np.random.default_rng(1)
    mesh = build_mesh(MeshSpec({"pipe": 4, "data": 2}))
    params = _stack_params(rng, 4, 4)
    x = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)

    g_pipe = jax.grad(
        lambda p: jnp.sum(pipeline_apply(_stage_fn, p, x, mesh) ** 2)
    )(params)
    g_seq = jax.grad(lambda p: jnp.sum(_sequential(p, x, 4) ** 2))(params)
    for k in ("w", "b"):
        np.testing.assert_allclose(
            np.asarray(g_pipe[k]), np.asarray(g_seq[k]), atol=1e-4, rtol=1e-4
        )


def test_no_pipe_axis_falls_back():
    rng = np.random.default_rng(2)
    mesh = build_mesh(MeshSpec({"data": 8}))
    params = _stack_params(rng, 1, 4)
    x = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
    got = pipeline_apply(_stage_fn, params, x, mesh)
    want = _sequential(params, x, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_no_pipe_axis_runs_all_stages():
    """A mesh without a pipe axis (e.g. post-rescale) must still apply every
    stage sequentially, not silently run only stage 0."""
    rng = np.random.default_rng(2)
    params = _stack_params(rng, 4, 8)
    x = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    mesh = build_mesh(MeshSpec({"data": 8}))
    got = pipeline_apply(_stage_fn, params, x, mesh)
    want = _sequential(params, x, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_bubble_fraction_accounting():
    from edl_tpu.parallel.pipeline import bubble_fraction

    assert bubble_fraction("gpipe", 1, 4) == 0.0
    assert bubble_fraction("gpipe", 4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction("1f1b", 4, 4) == pytest.approx(6 / 10)
    # 1f1b's bubble shrinks with M while its memory stays O(n) — the regime
    # the schedule exists for
    assert bubble_fraction("1f1b", 4, 32) < bubble_fraction("1f1b", 4, 8)
    with pytest.raises(ValueError):
        bubble_fraction("interleaved", 4, 4)


def test_bubble_fraction_interleaved():
    from edl_tpu.parallel.pipeline import bubble_fraction

    # v=1 degenerates to plain 1f1b exactly
    for n, m in [(2, 4), (4, 8), (4, 32), (8, 16)]:
        assert bubble_fraction("1f1b-interleaved", n, m, 1) == pytest.approx(
            bubble_fraction("1f1b", n, m)
        )
    # closed form: (n*v + n - 2) / (m*v + n*v + n - 2)
    assert bubble_fraction("1f1b-interleaved", 4, 8, 2) == pytest.approx(
        10 / 26
    )
    # interleaving strictly shrinks the bubble at fixed M for n >= 3...
    for n, m in [(4, 4), (4, 8), (8, 16)]:
        assert bubble_fraction("1f1b-interleaved", n, m, 2) < bubble_fraction(
            "1f1b", n, m
        )
        assert bubble_fraction("1f1b-interleaved", n, m, 4) < bubble_fraction(
            "1f1b-interleaved", n, m, 2
        )
    # ...but at n=2 the lockstep schedule exactly ties plain 1f1b
    assert bubble_fraction("1f1b-interleaved", 2, 8, 2) == pytest.approx(
        bubble_fraction("1f1b", 2, 8)
    )
    with pytest.raises(ValueError):
        bubble_fraction("1f1b-interleaved", 4, 8, 0)
    with pytest.raises(ValueError):
        bubble_fraction("gpipe", 4, 8, 2)


def test_stash_slots_accounting():
    from edl_tpu.parallel.pipeline import stash_slots

    assert stash_slots("gpipe", 1, 8) == 0
    # gpipe's stash grows with M; 1f1b's saturates at 2n-1
    assert stash_slots("gpipe", 4, 32) == 35
    assert stash_slots("1f1b", 4, 32) == 7
    assert stash_slots("1f1b", 4, 4) == 4  # min(M, 2n-1)
    # interleaved: v rings of min(M, 3n) — O(n*v), still M-independent
    assert stash_slots("1f1b-interleaved", 4, 32, 2) == 24
    assert stash_slots("1f1b-interleaved", 4, 8, 2) == 16
    # the M-independent schedules stay below gpipe at large M
    assert stash_slots("1f1b-interleaved", 4, 64, 4) < stash_slots(
        "gpipe", 4, 64
    )


def test_interleaved_layout():
    from edl_tpu.parallel.pipeline import interleaved_layout

    # identity at v=1
    np.testing.assert_array_equal(
        interleaved_layout(8, 4, 1), np.arange(8)
    )
    # n=2, v=2, Lc=2: rank 0 holds stages 0,2 (layers 0,1,4,5), rank 1
    # holds stages 1,3 (layers 2,3,6,7), chunk-major
    np.testing.assert_array_equal(
        interleaved_layout(8, 2, 2), [0, 1, 4, 5, 2, 3, 6, 7]
    )
    perm = interleaved_layout(16, 4, 2)
    assert sorted(perm.tolist()) == list(range(16))  # a permutation
    with pytest.raises(ValueError):
        interleaved_layout(6, 4, 2)  # 6 % 8 != 0


@pytest.mark.parametrize(
    "axes,microbatches",
    [({"pipe": 2, "data": 4}, 4), ({"pipe": 4, "data": 2}, 8),
     # the risky composition: the combined scan's per-tick jax.vjp runs
     # THROUGH ring attention's seq-axis ppermutes and the tensor-parallel
     # psums inside the stage function
     ({"pipe": 2, "seq": 2, "model": 2}, 4)],
    ids=["pp2-M4", "pp4-M8", "pp2-sp2-tp2"],
)
def test_1f1b_matches_gpipe_in_model(axes, microbatches):
    """Schedule choice must change memory/wall profile, not math: loss AND
    every gradient (stage, tail, embedding via dx) equal to reassociation
    tolerance between gpipe and the combined-scan 1f1b."""
    import dataclasses

    from edl_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=8, d_ff=64,
        seq_len=16, microbatches=microbatches,
    )
    mesh = build_mesh(MeshSpec(axes))
    gpipe = transformer.make_model(cfg)
    onef1b = transformer.make_model(
        dataclasses.replace(cfg, pipeline_schedule="1f1b")
    )
    params = gpipe.init(jax.random.PRNGKey(0), mesh)
    batch = gpipe.synthetic_batch(np.random.default_rng(0), 16)
    placed = {
        k: jax.device_put(
            jnp.asarray(v),
            jax.sharding.NamedSharding(mesh, gpipe.batch_spec(mesh)[k]),
        )
        for k, v in batch.items()
    }

    def run(model):
        fn = jax.jit(jax.value_and_grad(
            lambda p, b: model.loss_fn(p, b, mesh)
        ))
        loss, grads = fn(params, placed)
        return float(loss), grads

    l_g, g_g = run(gpipe)
    l_1, g_1 = run(onef1b)
    assert l_g == pytest.approx(l_1, rel=1e-5)
    flat_g, _ = jax.tree_util.tree_flatten_with_path(g_g)
    flat_1 = jax.tree_util.tree_leaves(g_1)
    for (path, a), b in zip(flat_g, flat_1):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=2e-5,
            err_msg=str(path),
        )


def test_1f1b_matches_single_device_oracle():
    """1f1b on a pipe mesh vs the same model on one device: the schedule
    must be invisible to the optimizer."""
    import dataclasses

    from edl_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=8, d_ff=64,
        seq_len=16,
    )
    batch = transformer.synthetic_batch(cfg, np.random.default_rng(0), 8)

    def loss_on(axes, schedule):
        n_dev = 1
        for v in axes.values():
            n_dev *= v
        mesh = build_mesh(MeshSpec(axes), jax.devices()[:n_dev])
        model = transformer.make_model(
            dataclasses.replace(cfg, pipeline_schedule=schedule)
        )
        params = model.init(jax.random.PRNGKey(0), mesh)
        placed = {
            k: jax.device_put(
                jnp.asarray(v),
                jax.sharding.NamedSharding(mesh, model.batch_spec(mesh)[k]),
            )
            for k, v in batch.items()
        }
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, b: model.loss_fn(p, b, mesh)
        ))(params, placed)
        return float(loss), grads

    l_ref, g_ref = loss_on({"data": 1}, "gpipe")
    l_pp, g_pp = loss_on({"pipe": 4, "data": 2}, "1f1b")
    assert l_pp == pytest.approx(l_ref, rel=2e-2)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_pp)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=8e-2, atol=3e-4,
        )


def _run_model_loss_grads(cfg, axes, batch):
    """Init + value_and_grad of a transformer on a sub-mesh of ``axes``."""
    from edl_tpu.models import transformer

    n_dev = 1
    for s in axes.values():
        n_dev *= s
    mesh = build_mesh(MeshSpec(axes), jax.devices()[:n_dev])
    model = transformer.make_model(cfg)
    params = model.init(jax.random.PRNGKey(0), mesh)
    placed = {
        k: jax.device_put(
            jnp.asarray(v),
            jax.sharding.NamedSharding(mesh, model.batch_spec(mesh)[k]),
        )
        for k, v in batch.items()
    }
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: model.loss_fn(p, b, mesh)
    ))(params, placed)
    return float(loss), grads


def test_interleaved_matches_single_device_oracle():
    """Interleaved 1f1b (pp=4, v=2, M=8) vs the same logical model on one
    device. Both inits use the same key, so the logical layers are
    identical; the interleaved model stores blocks chunk-major, so its
    block grads map back to logical layer order through the inverse of
    interleaved_layout before comparison."""
    import dataclasses

    from edl_tpu.models import transformer
    from edl_tpu.parallel.pipeline import interleaved_layout

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=8, n_heads=8, d_ff=64,
        seq_len=16,
    )
    batch = transformer.synthetic_batch(cfg, np.random.default_rng(0), 16)

    l_ref, g_ref = _run_model_loss_grads(cfg, {"data": 1}, batch)
    l_il, g_il = _run_model_loss_grads(
        dataclasses.replace(
            cfg, pipeline_schedule="1f1b-interleaved", virtual_stages=2,
            microbatches=8,
        ),
        {"pipe": 4, "data": 2}, batch,
    )
    assert l_il == pytest.approx(l_ref, rel=2e-2)
    inv = np.argsort(interleaved_layout(8, 4, 2))
    for k, a in g_ref["blocks"].items():
        np.testing.assert_allclose(
            np.asarray(g_il["blocks"][k])[inv], np.asarray(a, np.float32),
            rtol=8e-2, atol=3e-4, err_msg=f"blocks[{k}]",
        )
    for k in ("embed", "pos", "lnf", "head"):
        np.testing.assert_allclose(
            np.asarray(g_il[k]), np.asarray(g_ref[k], np.float32),
            rtol=8e-2, atol=3e-4, err_msg=k,
        )


def test_interleaved_matches_gpipe_in_model():
    """gpipe and interleaved 1f1b on the same pp=4 mesh: schedule choice
    changes the timetable, not the math. Tighter tolerance than the oracle
    test since both sides run the same per-stage shard_map arithmetic."""
    import dataclasses

    from edl_tpu.models import transformer
    from edl_tpu.parallel.pipeline import interleaved_layout

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=8, n_heads=8, d_ff=64,
        seq_len=16, microbatches=8,
    )
    batch = transformer.synthetic_batch(cfg, np.random.default_rng(1), 16)
    axes = {"pipe": 4, "data": 2}

    l_g, g_g = _run_model_loss_grads(cfg, axes, batch)
    l_il, g_il = _run_model_loss_grads(
        dataclasses.replace(
            cfg, pipeline_schedule="1f1b-interleaved", virtual_stages=2,
        ),
        axes, batch,
    )
    assert l_il == pytest.approx(l_g, rel=1e-5)
    inv = np.argsort(interleaved_layout(8, 4, 2))

    def close(got, want, name):
        # The two schedules sum the same microbatch gradients in different
        # orders, and every term went through bf16 activations and
        # cotangents. An element that cancels to near zero therefore
        # carries an ABSOLUTE error set by the terms, not by itself: about
        # one bf16 ulp (2^-8) of the tensor's largest entries. A fixed
        # atol=2e-5 was never sound for such elements (jax 0.9.0: one of
        # 512 elements of `pos`, itself 3e-4 in a tensor reaching 5e-2,
        # differs by 6.9e-5 = 0.35 ulp of that scale); the bound follows
        # the tensor's magnitude instead.
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=5e-2,
            atol=float(np.abs(want).max()) * 2.0 ** -8, err_msg=name,
        )

    for k, a in g_g["blocks"].items():
        close(np.asarray(g_il["blocks"][k])[inv], a, f"blocks[{k}]")
    for k in ("embed", "pos", "lnf", "head"):
        close(g_il[k], g_g[k], k)


def test_interleaved_config_validation():
    from edl_tpu.models import transformer

    mesh = build_mesh(MeshSpec({"pipe": 4, "data": 2}))
    # v > 1 demands the interleaved schedule
    with pytest.raises(ValueError, match="virtual_stages"):
        transformer.make_model(
            vocab_size=64, d_model=32, n_layers=8, n_heads=8, d_ff=64,
            seq_len=16, virtual_stages=2,
        ).init(jax.random.PRNGKey(0), mesh)
    # layers must split evenly into pp*v chunks
    with pytest.raises(ValueError, match="n_layers"):
        transformer.make_model(
            vocab_size=64, d_model=32, n_layers=4, n_heads=8, d_ff=64,
            seq_len=16, pipeline_schedule="1f1b-interleaved",
            virtual_stages=2, microbatches=8,
        ).init(jax.random.PRNGKey(0), mesh)
    # microbatches inject in groups of pp under interleaving
    with pytest.raises(ValueError, match="microbatches"):
        transformer.make_model(
            vocab_size=64, d_model=32, n_layers=8, n_heads=8, d_ff=64,
            seq_len=16, pipeline_schedule="1f1b-interleaved",
            virtual_stages=2, microbatches=6,
        ).init(jax.random.PRNGKey(0), mesh)
