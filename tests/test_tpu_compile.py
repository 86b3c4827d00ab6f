"""Compile-only guards for the TPU: the main path's programs, at real widths,
compiled by the TPU's own compiler for a DESCRIBED v5e:2x2 topology.

No chip is attached and nothing runs — these say nothing about results or
times. They catch what interpret mode cannot: a block shape Mosaic refuses,
a kernel that does not lower, a program that silently takes the Pallas
interpreter or the dense path. The platform is chosen by the shardings of
the abstract arguments (devices of the described topology), which is what
`flash_attention`'s platform dispatch keys on; the process's default backend
stays the CPU.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU's library, so nothing here may touch it
at import or at collection time (see the on-chip-measurement guide, §2).
"""

import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from edl_tpu.models import transformer
from edl_tpu.ops import flash_attention
from edl_tpu.parallel import MeshSpec, build_mesh
from edl_tpu.parallel.collective import zero_shard_spec
from edl_tpu.runtime.train_loop import Trainer, TrainerConfig, TrainState

#: the module; `edl_tpu.ops` exports the function under the same name
fa = importlib.import_module("edl_tpu.ops.flash_attention")

#: GPT-2-medium widths (chip_smoke.py's); depth is cut to keep compiles short
WIDTHS = dict(vocab_size=50257, d_model=1024, n_heads=16, d_ff=4096,
              seq_len=1024)
N_LAYERS = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to JAX's persistent cache
    # but cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _qkv(sharding, seq, head_dim=64, batch=2, heads=16, kv_heads=None):
    of = lambda heads: jax.ShapeDtypeStruct(
        (batch, seq, heads, head_dim), jnp.bfloat16, sharding=sharding)
    return of(heads), of(kv_heads or heads), of(kv_heads or heads)


def _compile_flash(fn, args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    assert "flash_attention_interpreted" not in text


#: (S, D) the tile was swept at (onchip_flash_sweep.py): the dense cell's, a
#: ring hop's, a longer one, a wider head, the hybrid cell's, the longest one
#: span holds; and one past `_MAX_SPAN`, which takes two spans a sequence
RULE_SHAPES = [(1024, 64), (2048, 64), (4096, 64), (1024, 128), (8192, 64),
               (16384, 64), (32768, 64)]

#: (batch, seq, heads, head_dim) of the benchmark's first two cells as they
#: called the kernels before PR 33: two heads of 64 to a 128-lane block and 8
#: blocks a row; one head of 128 to a block, 32 blocks a row, one span a head
CELL_SHAPES = [(32, 1024, 16, 64), (2, 8192, 32, 128)]


#: (batch, seq, heads, head_dim, K/V heads) with groups (PR 33): the hybrid
#: cell's 32 on 2 and the sparse cell's 32 on 4 as their models call the
#: kernels (the sparse cell's with its selection: further down), groups of
#: two on K/V blocks of two heads of 64, sixteen of 64 on one K/V head
GROUPED_SHAPES = [(2, 8192, 32, 128, 2), (1, 16384, 32, 128, 4),
                  (2, 2048, 16, 64, 8), (2, 1024, 16, 64, 1)]

#: every rule shape at batch 2 and 16 heads, the two cells, and the groups
FLASH_SHAPES = [pytest.param(*shape, id="x".join(map(str, shape)))
                for shape in [(2, seq, 16, d, 16) for seq, d in RULE_SHAPES]
                + [shape + shape[2:3] for shape in CELL_SHAPES]
                + GROUPED_SHAPES]


@pytest.mark.parametrize("batch,seq,heads,head_dim,kv_heads", FLASH_SHAPES)
def test_flash_forward_and_backward_at_the_rules_tiles(
        one_chip, batch, seq, heads, head_dim, kv_heads):
    """The tile, spans, lanes and heads a step the kernels take unasked, as
    Mosaic sees them: a tile it refuses, a slice it cannot align or a kernel
    over its VMEM limit fails here."""

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    _compile_flash(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                   _qkv(one_chip, seq, head_dim, batch, heads, kv_heads))


@pytest.mark.parametrize("batch,seq,heads,head_dim,kv_heads", FLASH_SHAPES)
def test_flash_with_lse_as_the_ring_calls_it(one_chip, batch, seq, heads,
                                             head_dim, kv_heads):
    """`_ring_flash_local`'s hop engine: global offsets, f32 partial output
    and a differentiable logsumexp."""

    def hop(q, k, v):
        out, lse = flash_attention(q, k, v, causal=True, return_lse=True,
                                   q_offset=seq, k_offset=0)
        return out.sum() + lse.sum()

    _compile_flash(jax.value_and_grad(hop, argnums=(0, 1, 2)),
                   _qkv(one_chip, seq, head_dim, batch, heads, kv_heads))


@pytest.mark.parametrize("heads,head_dim", [(16, 64), (32, 128)])
@pytest.mark.parametrize("seq", [200, 12])
def test_flash_short_sequence_pads_to_the_block(one_chip, seq, heads,
                                                head_dim):
    """Sequence lengths that are no multiple of the 128-row tile: the
    padding branch, forward and backward, at both cells' heads."""

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    _compile_flash(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                   _qkv(one_chip, seq, head_dim, heads=heads))


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("shape,kv_heads,selected,grids,moved", [
    # B, blocks of lanes, blocks of 512 rows, spans of up to 8,192
    ((32, 1024, 16, 64), 16, False, [(32, 8, 2, 1)] * 3, None),
    # the hybrid cell: 32 query heads on 2 K/V heads of 128, a group of 16 a
    # step (four in `flash_bwd_dkv`, which walks the group in four steps a
    # span), one span
    ((2, 8192, 32, 128), 2, False,
     [(2, 2, 16, 1), (2, 2, 16, 1), (2, 2, 16, 4)], None),
    # the sparse cell: 32 on 4 with the selection, a group of 8 a step (two
    # in `flash_bwd_dkv`), one span of 16,384: K and V 32 MiB and the
    # selection's blocks 1 GiB a call where a head a step on repeated K/V in
    # two spans moved 8 GiB each
    ((1, 16384, 32, 128), 4, True,
     [(1, 4, 32, 1), (1, 4, 32, 1), (1, 4, 32, 4)],
     {"flash_fwd": {"k+v": 32.0, "selection": 1024.0, "q+o": 256.0},
      "flash_bwd_dq": {"k+v": 32.0, "selection": 1024.0, "q+do+dq": 384.0},
      "flash_bwd_dkv": {"q+do": 8192.0, "selection": 1024.0,
                        "k+v+dk+dv": 64.0}}),
    # and as it was called before PR 33, K and V repeated to 32 heads
    ((1, 16384, 32, 128), 32, True, [(1, 32, 32, 1)] * 3,
     {"flash_fwd": {"k+v": 256.0, "selection": 8192.0, "q+o": 256.0},
      "flash_bwd_dq": {"k+v": 256.0, "selection": 8192.0, "q+do+dq": 384.0},
      "flash_bwd_dkv": {"q+do": 256.0, "selection": 8192.0,
                        "k+v+dk+dv": 512.0}}),
])
def test_flash_grid_at_the_benchmark_shapes(shape, kv_heads, selected, grids,
                                            moved, caplog):
    """A grid step costs about 0.35 us before it computes: at 128 x 128
    tiles, one a step, the dense cell's (32, 1024, 16, 64) call was 32,768
    steps and 16 ms (PERF.md, PR 26). A fall back to that, or to a head a
    step under a group, must not pass unseen: every kernel of the call,
    forward and backward, takes ``B x blocks of lanes x blocks x spans``
    steps: 512 at the dense cell (two heads a block; 1,024 when a block was
    one head), 64 and 256 at the hybrid cell and 128 and 512 at the sparse
    cell (PR 33: a K/V head's group a step, and K and V of ``Hkv x D`` lanes
    as the model projects them; 1,024 and 2,048 a head a step at PR 32). The
    trace-time line `flash_attention` logs says the same and what a call
    moves by operand."""
    B, S, H, D = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, kv_heads, D), jnp.bfloat16)
    picked = jax.ShapeDtypeStruct((B, S, S), jnp.int8) if selected else None

    def loss(q, k, v, picked):
        return flash_attention(q, k, v, causal=True, selection=picked
                               ).astype(jnp.float32).sum()

    with caplog.at_level("DEBUG", logger="edl_tpu.ops.flash_attention"):
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            q, kv, kv, picked)
    calls = {eqn.params["name"]: eqn for eqn in _pallas_calls(jaxpr.jaxpr)}
    names = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
    assert {name: tuple(calls[name].params["grid_mapping"].grid)
            for name in names} == dict(zip(names, grids))
    for name in names:  # K and V go in as they are: Hkv x D lanes, unrepeated
        k_in, v_in = calls[name].invars[3:5]
        assert k_in.aval.shape == v_in.aval.shape == (B, S, kv_heads * D)
    dk, dv = calls["flash_bwd_dkv"].outvars
    assert dk.aval.shape == dv.aval.shape == (B, S, kv_heads * D)
    line = caplog.records[0].getMessage()
    tiling = fa._tiling(B, S, S, H, kv_heads, D, 2, 512, 512, selected)
    assert str(tiling) in line
    assert [tiling[name]["grid"] for name in names] == grids
    if moved is not None:
        assert {name: tiling[name]["MiB"] for name in names} == moved


def _param_avals(model, mesh):
    """The model's parameters as shapes placed by its own `param_spec` (no
    array can be put on a described device)."""
    shapes = jax.eval_shape(lambda key: model.init(key, mesh),
                            jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
        shapes, model.param_spec(mesh),
    )


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_serving_step_at_one_bucket(topo, phase):
    """`LMServingReplica._compile_all`'s programs at batch bucket 4, seq
    bucket 1024."""
    mesh = build_mesh(MeshSpec({"data": 1}), list(topo.devices)[:1])
    model = transformer.make_model(**WIDTHS, n_layers=N_LAYERS)
    cfg = model.config
    params = _param_avals(model, mesh)
    rep = NamedSharding(mesh, P())
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
    if phase == "prefill":
        step, args = transformer.make_prefill_step(cfg), (
            params, i32(4, 1024), i32(4))
    else:
        cache = jax.ShapeDtypeStruct(
            (N_LAYERS, 4, 1024, cfg.n_heads, cfg.head_dim), jnp.bfloat16,
            sharding=rep)
        step, args = transformer.make_decode_step(cfg), (
            params, cache, cache, i32(4), i32(4))
    compiled = jax.jit(step).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


@pytest.mark.parametrize("chips", [1, 4])
def test_train_step(topo, chips):
    """One whole `Trainer.train_step` program: flash attention under
    `shard_map`, per-block remat, Adam; on four chips with ZeRO-1, whose
    collectives must show in the program."""
    mesh = build_mesh(MeshSpec({"data": chips}), list(topo.devices)[:chips])
    model = transformer.make_model(**WIDTHS, n_layers=N_LAYERS, remat=True)
    zero1 = chips > 1
    trainer = Trainer(model, mesh, TrainerConfig(optimizer="adam",
                                                 shard_opt_state=zero1))
    params = _param_avals(model, mesh)

    def moment(x):
        spec = (zero_shard_spec(x.shape, mesh, "data")
                if zero1 and x.ndim else None)
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec or P()))

    state = TrainState(
        jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
        params,
        jax.tree_util.tree_map(moment,
                               jax.eval_shape(trainer.opt.init, params)),
    )
    tokens = jax.ShapeDtypeStruct((8, 1024), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    compiled = trainer._jit_step.lower(
        state, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the train step took the dense path"
    assert "flash_attention_interpreted" not in text
    if zero1:
        assert "all-reduce" in text and "all-gather" in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 15 * 2**30


def _compiled_cell_step(topo, model, batch, seq):
    """One chip's whole Adam train step of ``model`` at ``batch`` x ``seq``
    tokens, as the benchmark's runners build it, compiled for the described
    v5e."""
    mesh = build_mesh(MeshSpec({"data": 1}), list(topo.devices)[:1])
    trainer = Trainer(model, mesh, TrainerConfig(optimizer="adam"))
    params = _param_avals(model, mesh)
    rep = NamedSharding(mesh, P())
    state = TrainState(
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep), params,
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
            jax.eval_shape(trainer.opt.init, params)))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    return trainer._jit_step.lower(
        state, {"tokens": tokens, "targets": tokens}).compile()


def test_dense_train_step_at_the_cell(topo):
    """`train_gpt2m_1chip`'s step: GPT-2-medium at full depth, batch 32 x
    1,024, per-block remat, Adam, as `benchmarks/runners/train.py` builds
    it. The flash kernels take the block's arrays as they lie, so the step
    holds no transposed copy of q, k, v or o beyond what XLA's own layouts
    ask for: its temporaries stay within the 9.77 GiB they were when the
    kernels took (B*H, S, D) (PERF.md, Sizing), and the step fits the
    chip's 15.75 GiB."""
    compiled = _compiled_cell_step(
        topo, transformer.make_model(**WIDTHS, n_layers=24, remat=True),
        32, 1024)
    text = compiled.as_text()
    assert "flash_fwd" in text and "tpu_custom_call" in text
    assert "flash_attention_interpreted" not in text
    mem = compiled.memory_analysis()
    print(f"dense step for the described v5e: temp "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.3f} GiB")
    assert mem.temp_size_in_bytes <= 9.77 * 2**30
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) \
        < 15.75 * 2**30


def _kv_broadcasts(text, elements):
    """The instructions of a compiled program under the scope `attn_core`
    that broadcast an ARRAY to ``elements`` elements or more: what a
    `jnp.repeat` of K or V to the query heads compiles to (a
    `broadcast_in_dim` to (B, S, Hkv, group, D), on its own or in a
    fusion)."""
    found = []
    for line in text.splitlines():
        made = re.search(r"= \w+\[([\d,]+)\]\S* broadcast\(", line)
        if (made and "attn_core" in line and "broadcast_in_dim" in line
                and math.prod(map(int, made.group(1).split(","))) >= elements):
            found.append(line.strip()[:200])
    return found


def test_a_repeat_of_kv_is_seen_in_the_compiled_text(one_chip):
    """`_kv_broadcasts` reads what the cells' rehearsals hold it to: the
    repeat the models made before PR 33, and nothing in the call with
    groups."""
    q, k, v = _qkv(one_chip, 1024, 128, 2, 16, 2)

    def compiled(repeat):
        def loss(q, k, v):
            with jax.named_scope("attn_core"):
                if repeat:
                    k, v = (jnp.repeat(a, 8, axis=2) for a in (k, v))
                return flash_attention(q, k, v).astype(jnp.float32).sum()
        return jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            q, k, v).compile().as_text()

    assert len(_kv_broadcasts(compiled(True), 2 * 1024 * 16 * 128)) == 2
    assert not _kv_broadcasts(compiled(False), 2 * 1024 * 16 * 128)


def test_hybrid_train_step_at_the_cell(topo):
    """`train_nemotron3nano_1chip`'s step: the configuration file's widths
    (nine layers `MEMEM*EME`, 8 of 128 experts held, 16,384 rows of the
    vocabulary) at the cell's 2 x 8,192 tokens, per-layer remat, Adam. It
    compiles for the described v5e (the flash kernels at (64, 8192, 128) and
    the grouped product through Mosaic, and since PR 28 the SSD scan's two
    kernels `ssd_fwd` and `ssd_bwd` at chunks of 128 with 8 heads of 64 a
    group) and its temporaries and arguments fit the chip's 15.75 GiB, the
    temporaries in no more than the 3.858 GiB they took before the flash
    kernels addressed (B, S, H*D) (PR 28's reading; the cell file's
    ``sizing`` holds PR 27's; PERF.md, Sizing, the newest)."""
    import json
    import os

    from edl_tpu.models import resolve

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "fixed_b2_s8192.json")) as f:
        traffic = json.load(f)
    sizes = {ours: config[theirs]
             for theirs, ours in config["maps_to"].items()}
    model = resolve(config["model"], dict(sizes, seq_len=traffic["seq_len"],
                                          remat=True))
    compiled = _compiled_cell_step(topo, model, traffic["batch"],
                                   traffic["seq_len"])
    text = compiled.as_text()
    assert "flash_fwd" in text and "tpu_custom_call" in text
    assert "ssd_fwd" in text and "ssd_bwd" in text, \
        "the SSD scan's kernels are not in the step"
    # `_pallas_call` names its interpreted branch so for every kernel
    assert "flash_attention_interpreted" not in text
    # K and V go to the kernels as projected (PR 33): 2 heads, not 32
    assert not _kv_broadcasts(text, traffic["batch"] * traffic["seq_len"]
                              * sizes["n_heads"] * sizes["head_dim"])
    mem = compiled.memory_analysis()
    print(f"hybrid step for the described v5e: temp "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.3f} GiB")
    assert mem.argument_size_in_bytes > 7.4 * 2**30  # the 8.00 GB of state
    assert mem.temp_size_in_bytes <= 3.8585 * 2**30
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) \
        < 15.75 * 2**30


def test_sparse_train_step_at_the_cell(topo):
    """`train_keyevl2_1chip`'s step: the configuration file's widths (six
    published layers as `SESESESESESE`, 16 of 128 gated experts held, 18,992
    rows of the vocabulary) at the cell's 1 x 16,384 tokens, per-layer
    remat, Adam. It compiles for the described v5e: the flash kernels at (1,
    16384, 32, 128) WITH their int8 selection operand through Mosaic (a
    block of 512 x 8,192 bytes beside K and V), the selection's two kernels
    `indexer_scores` and `top_k_select` (`ops/sparse_select.py`), the
    grouped product over one pass of 32,768 rows a layer; temporaries and
    arguments fit the chip's 15.75 GiB (PR 32's reading: temp 5.017 GiB +
    arguments 7.367 GiB; 6.480 with the selection in plain XLA; since PR 33,
    with K and V handed to the kernels as projected, temp 4.742 GiB)."""
    import json
    import os

    from edl_tpu.models import resolve

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "fixed_b1_s16384.json")) as f:
        traffic = json.load(f)
    sizes = {ours: config[theirs]
             for theirs, ours in config["maps_to"].items()}
    model = resolve(config["model"], dict(sizes, seq_len=traffic["seq_len"],
                                          remat=True))
    compiled = _compiled_cell_step(topo, model, traffic["batch"],
                                   traffic["seq_len"])
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                   "indexer_scores", "top_k_select"):
        assert kernel in text
    assert "tpu_custom_call" in text
    assert "flash_attention_interpreted" not in text
    # K and V go to the kernels as projected (PR 33): 4 heads, not 32
    assert not _kv_broadcasts(text, traffic["batch"] * traffic["seq_len"]
                              * sizes["n_heads"] * sizes["head_dim"])
    mem = compiled.memory_analysis()
    print(f"sparse step for the described v5e: temp "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.3f} GiB")
    assert mem.argument_size_in_bytes > 7.3 * 2**30  # the 7.91 GB of state
    assert mem.temp_size_in_bytes <= 4.8 * 2**30
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) \
        < 15.75 * 2**30


@pytest.mark.parametrize("batch,seq,heads,kv_heads,head_dim", [
    (1, 16384, 32, 32, 128), (2, 1024, 16, 16, 64), (1, 200, 16, 16, 64),
    (1, 16384, 32, 4, 128), (2, 1024, 16, 4, 64), (1, 200, 16, 1, 64)])
def test_flash_with_a_selection_compiles(one_chip, batch, seq, heads,
                                         kv_heads, head_dim):
    """The three kernels with the selection operand (a byte a pair, the same
    for every head) through Mosaic: at the sparse cell's shape, with two
    heads of 64 to a block, and padded to one tile; each also with K and V
    of fewer heads, a group a step (the sparse cell's 32 on 4 as its model
    calls it since PR 33; groups of four on K/V blocks of two heads of 64;
    sixteen of 64 on one)."""
    q, k, v = _qkv(one_chip, seq, head_dim, batch, heads, kv_heads)
    picked = jax.ShapeDtypeStruct((batch, seq, seq), jnp.int8,
                                  sharding=one_chip)

    def loss(q, k, v, picked):
        return jnp.sum(flash_attention(q, k, v, selection=picked)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        q, k, v, picked).compile().as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text
    assert "flash_attention_interpreted" not in text


@pytest.mark.parametrize("seq, heads", [(16384, 16), (128, 4)])
def test_selection_kernels_compile(one_chip, seq, heads):
    """`indexer_scores` and `top_k_select` through Mosaic at the sparse
    cell's shape (16 heads of 64 on one key head, 16,384 positions, all the
    keys of 128 queries in one VMEM block) and at one lane row."""
    from edl_tpu.ops.sparse_select import indexer_scores, top_k_select

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def select(qI, kI, w):
        return top_k_select(indexer_scores(qI, kI, w), 2048)

    text = jax.jit(select).lower(
        of((1, seq, heads, 64), jnp.bfloat16), of((1, seq, 64), jnp.bfloat16),
        of((1, seq, heads), jnp.float32)).compile().as_text()
    assert "indexer_scores" in text and "top_k_select" in text
    assert "flash_attention_interpreted" not in text


@pytest.mark.parametrize("batch,seq,heads,kv_heads,head_dim,window", [
    (1, 16384, 28, 4, 128, 4096), (2, 1024, 16, 8, 64, 300),
    (1, 200, 7, 1, 64, 77)])
def test_flash_with_a_window_compiles(one_chip, batch, seq, heads, kv_heads,
                                      head_dim, window):
    """The three kernels under a sliding window (loops that start at the
    window's first tile and, in `flash_bwd_dkv`, stop at its last) through
    Mosaic: at the window cell's shape, a group of 7 query heads of 128 to a
    grid step (one a step in `flash_bwd_dkv`); with two heads of 64 to a
    block and a window that is no multiple of the tile; padded to one
    tile."""
    q, k, v = _qkv(one_chip, seq, head_dim, batch, heads, kv_heads)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, window=window)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        q, k, v).compile().as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text
    assert "flash_attention_interpreted" not in text


def test_window_train_step_at_the_cell(topo):
    """`train_smallthinker21b_1chip`'s step: the configuration file's widths
    (four published layers as `*EWEWEWE`, 16 of 64 gated relu experts held,
    37,984 rows of the vocabulary) at the cell's 1 x 16,384 tokens,
    per-layer remat, Adam. It compiles for the described v5e: the flash
    kernels at (1, 16384, 28 on 4, 128) through Mosaic, with and without
    the window of 4,096; the routing made outside the expert layers'
    checkpoints; the grouped product over one pass of 32,768 rows a layer;
    temporaries and arguments stay under 15.0 GiB (PR 35's reading: temp
    3.907 GiB + arguments 7.337 GiB)."""
    import json
    import os

    from edl_tpu.models import resolve

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs",
                           "smallthinker-21b-a3b-instruct.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic",
                           "fixed_b1_s16384_ids37984.json")) as f:
        traffic = json.load(f)
    sizes = {ours: config[theirs]
             for theirs, ours in config["maps_to"].items()}
    model = resolve(config["model"], dict(sizes, seq_len=traffic["seq_len"],
                                          remat=True))
    compiled = _compiled_cell_step(topo, model, traffic["batch"],
                                   traffic["seq_len"])
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text
    assert "tpu_custom_call" in text
    assert "flash_attention_interpreted" not in text
    # K and V go to the kernels as projected: 4 heads, not 28
    assert not _kv_broadcasts(text, traffic["batch"] * traffic["seq_len"]
                              * sizes["n_heads"] * sizes["head_dim"])
    mem = compiled.memory_analysis()
    print(f"window step for the described v5e: temp "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.3f} GiB")
    assert mem.argument_size_in_bytes > 7.3 * 2**30  # the 7.88 GB of state
    assert mem.temp_size_in_bytes <= 4.0 * 2**30
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) \
        < 15.0 * 2**30
