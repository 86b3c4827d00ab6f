"""Explicit data-plane tests: ZeRO shard placement, gradient buckets, the
closed-form bytes-on-wire model, and reduce-scatter/psum numerics parity.

The parity tests are the tentpole's contract: the explicit plane
(``grad_sync="reduce_scatter"`` — reduce-scatter → sharded update →
all-gather) must produce the SAME params and moments as the implicit psum
step, because the only float-level difference is reduction reassociation.
The byte tests pin `collective_bytes` to the ring closed forms and the
acceptance invariant (explicit strictly below implicit at equal config)
that BENCH_COLLECTIVE.json commits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from edl_tpu.models import transformer
from edl_tpu.parallel import MeshSpec, build_hierarchical_mesh, build_mesh
from edl_tpu.parallel.collective import (
    assign_buckets,
    collective_bytes,
    ring_bytes,
    split_microbatches,
    zero1_step_bytes,
    zero_shard_dim,
    zero_shard_spec,
)
from edl_tpu.runtime import Trainer, TrainerConfig


def small_model(**kw):
    base = dict(
        vocab_size=64, d_model=32, n_layers=2, n_heads=8, d_ff=64, seq_len=16
    )
    base.update(kw)
    return transformer.make_model(**base)


def _mesh(axes):
    spec = MeshSpec(dict(axes))
    if axes.get("dcn", 1) > 1:
        return build_hierarchical_mesh(spec)
    return build_mesh(spec)


def _leaves_allclose(a, b, **tol):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **tol)


# -- ZeRO shard-dim choice -----------------------------------------------------


def test_zero_shard_dim_prefers_largest_divisible():
    # first-divisible (the seed behavior) would split (8, 4096) into 1-row
    # slivers; largest-divisible keeps shards contiguous runs of dim 1
    assert zero_shard_dim((8, 4096), 8) == 1
    assert zero_shard_dim((4096, 8), 8) == 0
    assert zero_shard_dim((16, 16), 8) == 0  # tie -> lowest index
    assert zero_shard_dim((6, 10), 8) is None  # nothing divides
    assert zero_shard_dim((64,), 1) is None  # nothing to split


def test_zero_shard_spec_flat_and_hierarchical():
    mesh = _mesh({"data": 8})
    assert zero_shard_spec((8, 4096), mesh, "data") == P(None, "data")
    assert zero_shard_spec((3, 5), mesh, "data") is None
    # absent hierarchy axes drop out to the bare present axis
    assert zero_shard_spec((64,), mesh, ("dcn", "data")) == P("data")
    hier = _mesh({"dcn": 2, "data": 4})
    assert zero_shard_spec((64, 32), hier, ("dcn", "data")) == P(
        ("dcn", "data"), None
    )


def test_zero_shard_spec_across_non_dividing_world_change():
    """The 6 -> 4 rescale: neither world divides the other, so every leaf's
    shard dim is re-derived per mesh — some leaves change layout (divisible
    by 6 only), some pick a different dim, some go replicated. The spec
    must be consistent per (shape, mesh), which is all the checkpoint
    plane's reassemble-then-reshard recovery relies on."""
    import jax as _jax

    mesh6 = build_mesh(MeshSpec({"data": 6}), _jax.devices()[:6])
    mesh4 = build_mesh(MeshSpec({"data": 4}), _jax.devices()[:4])
    # divides both worlds, but on a different dim (24 % 6 == 24 % 4 == 0)
    assert zero_shard_spec((24, 4), mesh6, "data") == P("data", None)
    assert zero_shard_spec((24, 4), mesh4, "data") == P("data", None)
    # divides 6 only -> replicated at world 4 (the blob/plane restore path
    # must therefore never assume the shard dim survives a rescale)
    assert zero_shard_spec((18, 5), mesh6, "data") == P("data", None)
    assert zero_shard_spec((18, 5), mesh4, "data") is None
    # divides 4 only -> sharded only after the shrink
    assert zero_shard_spec((8, 3), mesh6, "data") is None
    assert zero_shard_spec((8, 3), mesh4, "data") == P("data", None)
    # largest-divisible dim FLIPS across the change: 12 wins at world 6
    # (16 % 6 != 0), 16 wins at world 4
    assert zero_shard_spec((12, 16), mesh6, "data") == P("data", None)
    assert zero_shard_spec((12, 16), mesh4, "data") == P(None, "data")


def test_shard_opt_state_shards_largest_dim():
    """`Trainer._shard_opt_state` places every moment on its
    `zero_shard_spec` layout — the LARGEST divisible dim, not the first.
    The position embedding moment (seq 16, d 32) is the discriminating
    case: both dims divide 8, first-divisible would pick dim 0."""
    mesh = _mesh({"data": 8})
    trainer = Trainer(
        small_model(), mesh,
        TrainerConfig(optimizer="adam", shard_opt_state=True),
    )
    state = trainer.init_state()
    assert zero_shard_spec((16, 32), mesh, "data") == P(None, "data")
    checked = 0
    for leaf in jax.tree_util.tree_leaves(state.opt_state):
        sh = getattr(leaf, "sharding", None)
        if not isinstance(sh, NamedSharding) or getattr(leaf, "ndim", 0) == 0:
            continue
        expect = zero_shard_spec(leaf.shape, mesh, "data")
        if expect is None:
            assert all(s is None for s in sh.spec), (leaf.shape, sh.spec)
        else:
            assert tuple(sh.spec) == tuple(expect), (leaf.shape, sh.spec)
            checked += 1
    assert checked > 0  # the layout assertions actually ran


# -- gradient buckets ----------------------------------------------------------


def test_assign_buckets_reverse_greedy():
    sizes = [100, 200, 300, 1000, 50]
    buckets = assign_buckets(sizes, 400)
    # reverse traversal order (backward finishes last params first); the
    # oversize leaf gets its own bucket, never split
    assert [b.indices for b in buckets] == [(4,), (3,), (2,), (1, 0)]
    assert [b.nbytes for b in buckets] == [50, 1000, 300, 300]
    covered = sorted(i for b in buckets for i in b.indices)
    assert covered == list(range(len(sizes)))  # every leaf exactly once


def test_assign_buckets_rejects_nonpositive_target():
    with pytest.raises(ValueError, match="bucket_bytes"):
        assign_buckets([1, 2], 0)


# -- closed-form bytes on wire -------------------------------------------------


def test_ring_bytes_closed_forms():
    nbytes = 1024.0
    assert ring_bytes(nbytes, 8, "reduce_scatter") == nbytes * 7 / 8
    assert ring_bytes(nbytes, 8, "all_gather") == nbytes * 7 / 8
    assert ring_bytes(nbytes, 8, "all_reduce") == 2 * nbytes * 7 / 8
    assert ring_bytes(nbytes, 1, "all_reduce") == 0.0
    with pytest.raises(ValueError, match="broadcast"):
        ring_bytes(nbytes, 8, "broadcast")


def test_collective_bytes_flat_matches_ring():
    for op in ("reduce_scatter", "all_gather", "all_reduce"):
        acct = collective_bytes(4096, [("data", 8)], op)
        assert acct["data"] == acct["total"] == ring_bytes(4096, 8, op)


def test_collective_bytes_hierarchical_all_reduce():
    # the lowering XLA emits for a psum over ("dcn", "data"): intra-slice
    # reduce-scatter at full size, inter-slice all-reduce on the 1/4
    # shard (the DCN hop at shard size), intra-slice all-gather
    nbytes = 4096.0
    acct = collective_bytes(nbytes, [("dcn", 2), ("data", 4)], "all_reduce")
    assert acct["data"] == 2 * nbytes * 3 / 4  # inner RS + inner AG
    assert acct["dcn"] == 2 * (nbytes / 4) * (1 / 2)  # AR on the shard
    assert acct["total"] == acct["data"] + acct["dcn"]


def test_collective_bytes_ar_decomposes_into_rs_plus_ag():
    # all-reduce = reduce-scatter + all-gather, tier by tier — the
    # identity the explicit plane exploits by keeping the gather half
    # for params only
    tiers = [("dcn", 2), ("data", 4)]
    ar = collective_bytes(999.0, tiers, "all_reduce")
    rs = collective_bytes(999.0, tiers, "reduce_scatter")
    ag = collective_bytes(999.0, tiers, "all_gather")
    for key in ("dcn", "data", "total"):
        assert ar[key] == pytest.approx(rs[key] + ag[key])


def test_zero1_step_bytes_rs_strictly_below_psum():
    for tiers in ([("data", 8)], [("dcn", 2), ("data", 4)]):
        ps = zero1_step_bytes(1e6, 0.0, tiers, "psum")
        rs = zero1_step_bytes(1e6, 0.0, tiers, "reduce_scatter")
        assert rs["total"] < ps["total"], tiers
        for name, _ in tiers:  # every tier moves fewer bytes, DCN included
            assert rs[name] < ps[name], (tiers, name)
    # flat, all-sharded: AR(2 units) + AG(1) vs RS(1) + AG(1) -> exactly 2/3
    flat_ps = zero1_step_bytes(1e6, 0.0, [("data", 8)], "psum")
    flat_rs = zero1_step_bytes(1e6, 0.0, [("data", 8)], "reduce_scatter")
    assert flat_rs["total"] == pytest.approx(flat_ps["total"] * 2 / 3)
    # leaves with no divisible dim all-reduce either way: modes tie
    rep_ps = zero1_step_bytes(0.0, 1e6, [("data", 8)], "psum")
    rep_rs = zero1_step_bytes(0.0, 1e6, [("data", 8)], "reduce_scatter")
    assert rep_ps["total"] == rep_rs["total"]


# -- Trainer integration: resolution, accounting -------------------------------


def test_grad_sync_resolution_and_validation():
    mesh = _mesh({"data": 8})
    model = small_model()
    assert Trainer(
        model, mesh, TrainerConfig(shard_opt_state=True)
    ).grad_sync == "reduce_scatter"  # auto + ZeRO layout -> explicit
    assert Trainer(model, mesh, TrainerConfig()).grad_sync == "psum"
    assert Trainer(
        model, mesh, TrainerConfig(shard_opt_state=True, grad_sync="psum")
    ).grad_sync == "psum"  # explicit opt-out honored
    with pytest.raises(ValueError, match="ZeRO-1 layout"):
        Trainer(model, mesh, TrainerConfig(grad_sync="reduce_scatter"))
    with pytest.raises(ValueError, match="grad_sync"):
        Trainer(model, mesh, TrainerConfig(grad_sync="ring"))
    with pytest.raises(ValueError, match="grad_accum_microbatches"):
        Trainer(model, mesh, TrainerConfig(grad_accum_microbatches=0))


def test_data_plane_accounting_invariant():
    """The committed acceptance invariant, asserted at the Trainer level:
    the explicit plane's analytic bytes-on-wire is strictly below the
    implicit psum plane's at equal config, by exactly the reduce-scatter
    cost of the sharded fraction (AR = 2xRS; one RS unit is never paid)."""
    mesh = _mesh({"data": 8})
    model = small_model()
    planes = {}
    for mode in ("psum", "reduce_scatter"):
        trainer = Trainer(
            model, mesh,
            TrainerConfig(
                optimizer="adam", shard_opt_state=True, grad_sync=mode,
                grad_bucket_mb=0.01,
            ),
        )
        state = trainer.init_state()
        planes[mode] = trainer.data_plane(state.params)
    rs, ps = planes["reduce_scatter"], planes["psum"]
    assert rs["bytes_per_step"] < ps["bytes_per_step"]
    assert rs["param_bytes_per_step"] == ps["param_bytes_per_step"]
    saved = collective_bytes(
        rs["sharded_bytes"], [("data", 8)], "reduce_scatter"
    )["total"]
    assert ps["grad_bytes_per_step"] - rs["grad_bytes_per_step"] == (
        pytest.approx(saved)
    )
    # bucket accounting covers every gradient byte exactly once
    total = sum(
        int(np.prod(jnp.shape(x))) * np.dtype(jnp.result_type(x)).itemsize
        for x in jax.tree_util.tree_leaves(
            Trainer(model, mesh, TrainerConfig()).init_state().params
        )
    )
    assert sum(rs["bucket_nbytes"]) == total
    assert rs["n_buckets"] > 1  # 0.01 MiB target actually fragments


# -- numerics parity: explicit reduce-scatter vs implicit-psum oracle ----------


@pytest.mark.parametrize(
    "axes,opt,clip",
    [
        ({"data": 8}, "adam", 0.0),
        ({"data": 8}, "adam", 1.0),
        ({"data": 8}, "adagrad", 0.0),
        ({"data": 8}, "adagrad", 1.0),
        ({"dcn": 2, "data": 4}, "adam", 1.0),
        ({"dcn": 2, "data": 4}, "adagrad", 0.0),
    ],
    ids=["flat-adam", "flat-adam-clip", "flat-adagrad", "flat-adagrad-clip",
         "dcn-adam-clip", "dcn-adagrad"],
)
def test_explicit_rs_matches_psum_oracle(axes, opt, clip):
    """Identical params AND moments after K steps: the explicit plane is a
    lowering change (where the reduction happens), not a math change."""
    mesh = _mesh(axes)
    batch_axis = ("dcn", "data") if "dcn" in axes else "data"
    model = small_model()
    rng = np.random.default_rng(0)
    batches = [model.synthetic_batch(rng, 16) for _ in range(3)]

    def run(grad_sync):
        trainer = Trainer(
            model, mesh,
            TrainerConfig(
                optimizer=opt, grad_clip_norm=clip, batch_axis=batch_axis,
                shard_opt_state=True, grad_sync=grad_sync,
            ),
        )
        assert trainer.grad_sync == grad_sync
        state = trainer.init_state()
        losses = []
        for b in batches:
            state, loss = trainer.train_step(state, trainer.place_batch(b))
            losses.append(float(loss))
        return state, losses

    st_ps, l_ps = run("psum")
    st_rs, l_rs = run("reduce_scatter")
    assert l_ps == pytest.approx(l_rs, rel=1e-6, abs=1e-7)
    _leaves_allclose(st_ps.params, st_rs.params, rtol=1e-6, atol=1e-7)
    _leaves_allclose(st_ps.opt_state, st_rs.opt_state, rtol=1e-6, atol=1e-7)


def test_grad_accum_matches_single_step_sgd():
    """Scan-based accumulation == whole-batch step for a linear-in-grads
    optimizer (sgd): the microbatch partition only reassociates the mean
    (equal-sized chunks -> mean of means IS the batch mean). ONE step, so
    the param delta is lr x the gradient difference — pure reassociation
    noise, with no step-over-step amplification through the loss surface
    (multi-step trajectory equivalence of the explicit plane itself is
    test_explicit_rs_matches_psum_oracle's job). flash=False: the flash
    kernel blocks over the batch dim, so a different microbatch size
    changes its accumulation order — dense attention keeps per-sample
    math bit-identical across the split."""
    mesh = _mesh({"data": 8})
    model = small_model(flash=False)
    rng = np.random.default_rng(0)
    batch = model.synthetic_batch(rng, 32)

    def run(accum):
        trainer = Trainer(
            model, mesh,
            TrainerConfig(
                optimizer="sgd", learning_rate=0.1, shard_opt_state=True,
                grad_accum_microbatches=accum,
            ),
        )
        state = trainer.init_state()
        state, loss = trainer.train_step(state, trainer.place_batch(batch))
        return state, float(loss)

    st1, l1 = run(1)
    st4, l4 = run(4)
    assert l4 == pytest.approx(l1, rel=1e-5)
    # atol scale: the cross-sample mean cancels (batch-mean grads ~1e-4
    # from per-sample grads ~1e-1), so the error rides the TERM magnitude,
    # and the terms pass through bf16: one sample's gradient term carries a
    # rounding error of about term x 2^-8 (one bf16 ulp), the mean over the
    # 32 samples divides that by sqrt(32), and the parameter moves by lr
    # times it: 0.1 x 1e-1 x 2^-8 / sqrt(32) = 6.9e-6 for one sigma. The
    # earlier 5e-6 was four times a maximum observed on another XLA (1.1e-6),
    # not a derived bound; jax 0.9.0's CPU compiler rounds the microbatched
    # dots at different points and reads 5.6e-6 on 2 of 192 elements of
    # wqkv, inside one sigma. Bound at four sigma.
    lr, term, batch_size = 0.1, 1e-1, 32
    atol = 4 * lr * term * 2.0 ** -8 / np.sqrt(batch_size)
    _leaves_allclose(st1.params, st4.params, rtol=1e-5, atol=atol)


def test_split_microbatches_shapes_and_divisibility():
    mesh = _mesh({"data": 8})
    batch = {"x": jnp.zeros((32, 5))}
    out = jax.jit(lambda b: split_microbatches(b, 4, mesh, "data"))(batch)
    assert out["x"].shape == (4, 8, 5)
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(lambda b: split_microbatches(b, 5, mesh, "data"))(batch)
