"""Persistent AOT compile cache: round-trip, eviction, and the Trainer
integration contract (a hit dispatches AOT and never touches the jit
dispatch cache).
"""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from edl_tpu.models import fit_a_line
from edl_tpu.parallel import local_mesh
from edl_tpu.runtime import Trainer, TrainerConfig
from edl_tpu.runtime.compile_cache import CompileCache, code_fingerprint


def _hits(cache):
    return cache.hits.value(tier="memory") + cache.hits.value(tier="disk")


def _misses(cache):
    return sum(cache.misses.value(reason=r)
               for r in ("absent", "stale", "corrupt"))


def _trainer(cache):
    return Trainer(fit_a_line.MODEL, local_mesh(),
                   TrainerConfig(optimizer="sgd", learning_rate=0.1),
                   compile_cache=cache)


def _avals(model, n=64):
    batch = model.synthetic_batch(np.random.default_rng(0), n)
    return batch, {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for k, v in batch.items()}


def test_round_trip_serves_identical_executable(tmp_path):
    mesh = local_mesh()

    def f(x):
        return x * 2.0 + 1.0

    # Compiled FOR the mesh the key names: an entry is loaded back onto
    # exactly that mesh's devices (a program compiled for one default
    # device under an 8-device mesh's key was never a coherent entry).
    aval = jax.ShapeDtypeStruct(
        (8,), np.float32, sharding=NamedSharding(mesh, PartitionSpec("data")))
    compiled = jax.jit(f).lower(aval).compile()
    cache = CompileCache(str(tmp_path))
    key = cache.key(mesh, "test-config", repr(aval), "no-state")
    assert cache.load(key, mesh) is None  # absent
    assert cache.store(key, compiled)
    assert cache.entries() == 1

    # Memory tier: the very object back.
    assert cache.load(key, mesh) is compiled

    # Disk tier: drop the memory map, deserialize, execute, compare.
    cache.clear_memory()
    loaded = cache.load(key, mesh)
    assert loaded is not None and loaded is not compiled
    x = np.arange(8, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(loaded(x)),
                               np.asarray(compiled(x)))


def test_key_separates_layout_config_and_avals(tmp_path):
    mesh = local_mesh()
    cache = CompileCache(str(tmp_path))
    base = cache.key(mesh, "cfg", "batch-sig", "state-sig")
    assert cache.key(mesh, "cfg2", "batch-sig", "state-sig") != base
    assert cache.key(mesh, "cfg", "batch-sig-64", "state-sig") != base
    assert cache.key(mesh, "cfg", "batch-sig", "state-sig-2") != base
    from edl_tpu.parallel import MeshSpec, build_mesh
    half = build_mesh(MeshSpec({"data": 4}), jax.devices()[:4])
    assert cache.key(half, "cfg", "batch-sig", "state-sig") != base
    assert cache.key(mesh, "cfg", "batch-sig", "state-sig") == base


def test_corrupted_entry_evicts_and_recompiles(tmp_path):
    mesh = local_mesh()
    compiled = jax.jit(lambda x: x + 1).lower(
        jax.ShapeDtypeStruct((4,), np.float32)).compile()
    cache = CompileCache(str(tmp_path))
    key = cache.key(mesh, "cfg", "b", "s")
    cache.store(key, compiled)
    cache.clear_memory()

    path = cache._path(key)
    with open(path, "r+b") as f:
        header = f.readline()
        f.write(b"\x00garbage\x00")  # tear the payload, keep the header
    before = cache.misses.value(reason="corrupt")
    assert cache.load(key, mesh) is None
    assert cache.misses.value(reason="corrupt") == before + 1
    import os
    assert not os.path.exists(path), "corrupt entry must be evicted"
    # and the slot is clean for a fresh store
    assert cache.store(key, compiled)
    cache.clear_memory()
    assert cache.load(key, mesh) is not None


def test_stale_fingerprint_evicts(tmp_path):
    mesh = local_mesh()
    compiled = jax.jit(lambda x: x - 1).lower(
        jax.ShapeDtypeStruct((4,), np.float32)).compile()
    writer = CompileCache(str(tmp_path), fingerprint="aaaa000011112222")
    key = writer.key(mesh, "cfg", "b", "s")
    writer.store(key, compiled)

    # Same directory, different code fingerprint — e.g. the package was
    # edited between the store and this process. Note the key itself also
    # embeds the fingerprint, so this models a *collision-free* stale read:
    # the reader probes the writer's key (warm-restart handoff file, say)
    # and must refuse the bytes.
    reader = CompileCache(str(tmp_path), fingerprint="bbbb333344445555")
    before = reader.misses.value(reason="stale")
    assert reader.load(key, mesh) is None
    assert reader.misses.value(reason="stale") == before + 1
    assert reader.entries() == 0


def test_default_fingerprint_is_code_fingerprint(tmp_path):
    cache = CompileCache(str(tmp_path))
    assert cache.fingerprint == code_fingerprint()
    assert len(cache.fingerprint) == 16


def test_trainer_warm_compile_miss_then_hit(tmp_path):
    cache = CompileCache(str(tmp_path))
    model = fit_a_line.MODEL
    batch, avals = _avals(model)

    t1 = _trainer(cache)
    s1 = t1.init_state()
    miss_seconds = t1.warm_compile(s1, avals)
    assert t1.last_compile_cache == "miss"
    assert cache.entries() == 1

    # A fresh Trainer (same config, same mesh, fresh init_state) keys
    # identically and is served without compiling.
    t2 = _trainer(cache)
    s2 = t2.init_state()
    hits_before = _hits(cache)
    hit_seconds = t2.warm_compile(s2, avals)
    assert t2.last_compile_cache == "hit"
    assert _hits(cache) == hits_before + 1
    assert hit_seconds < miss_seconds

    # The hit dispatches through the warm AOT path: jit cache unpolluted,
    # and the step matches a plain-jit trainer bit-for-bit.
    placed = t2.place_batch(batch)
    s2, loss = t2.train_step(s2, placed)
    size = t2._jit_cache_size()
    if size is not None:
        assert size == 0
    ref = Trainer(model, local_mesh(),
                  TrainerConfig(optimizer="sgd", learning_rate=0.1))
    _, ref_loss = ref.train_step(ref.init_state(), ref.place_batch(batch))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    assert int(s2.step) == 1


def test_trainer_disk_hit_across_cache_instances(tmp_path):
    """The warm-restart shape: a new CompileCache over the same directory
    (new process, same code) serves the executable from disk."""
    model = fit_a_line.MODEL
    _, avals = _avals(model)

    first = CompileCache(str(tmp_path))
    t1 = _trainer(first)
    t1.warm_compile(t1.init_state(), avals)
    assert t1.last_compile_cache == "miss"

    second = CompileCache(str(tmp_path))
    disk_before = second.hits.value(tier="disk")
    t2 = _trainer(second)
    t2.warm_compile(t2.init_state(), avals)
    assert t2.last_compile_cache == "hit"
    assert second.hits.value(tier="disk") == disk_before + 1


def test_trainer_without_cache_reports_off(tmp_path):
    model = fit_a_line.MODEL
    _, avals = _avals(model)
    t = Trainer(model, local_mesh(),
                TrainerConfig(optimizer="sgd", learning_rate=0.1))
    assert t.last_compile_cache == "off"
    t.warm_compile(t.init_state(), avals)
    assert t.last_compile_cache == "off"
