"""Model zoo: TPU-native re-implementations of the reference workloads.

Reference examples (SURVEY C14-C18) and their equivalents here:

- `example/fit_a_line/train_local.py` / `train_ft.py` (linear regression)
  -> ``fit_a_line``
- `example/fit_a_line/train_ft.py:41-99` (5-gram word embedding)
  -> ``word2vec`` (N-gram neural LM with a mesh-sharded embedding table)
- `example/fit_a_line/fluid/recognize_digits.py:20-52` (softmax/MLP/conv MNIST)
  -> ``mnist``
- `example/ctr/ctr/train.py` (deep-wide CTR, 1e6+1 sparse features)
  -> ``ctr`` — the flagship; its sparse tables are row-sharded over the mesh
  (`edl_tpu.parallel.ShardedEmbedding`) instead of living on C++ pservers
- ResNet-50 (BASELINE.json config list) -> ``resnet``
- no reference analog: ``transformer`` (dense decoder LM, every mesh axis) and
  ``hybrid`` (a decoder whose stack is a pattern of Mamba-2, grouped-query
  attention and sparse-expert layers; training only)

Every model follows the same functional convention (``models.base.Model``):
pure ``init``/``loss_fn`` plus sharding specs, so the elastic runtime can
build a jit-compiled SPMD train step for any of them on any mesh.

All models generate deterministic synthetic data shaped like the reference
datasets (UCI housing, PTB-style ids, MNIST, Criteo-style CTR) — this image
has zero egress, and the elasticity/throughput story does not depend on real
data values.
"""

from edl_tpu.models.base import Model
from edl_tpu.models import (fit_a_line, mnist, word2vec, ctr, resnet,
                            transformer, hybrid)


_MODULES = {
    "fit_a_line": fit_a_line,
    "mnist": mnist,
    "word2vec": word2vec,
    "ctr": ctr,
    "resnet": resnet,
    "transformer": transformer,
    "hybrid": hybrid,
}

#: default instances, keyed by each model's own name (module name and
#: model name differ where one module serves a family: resnet -> resnet50)
_REGISTRY = {mod.MODEL.name: mod.MODEL for mod in _MODULES.values()}


def get(name: str) -> Model:
    """Look up a zoo model's default instance by name."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def resolve(ref: str, config=None) -> Model:
    """Rebuild a zoo model from (module ref, make_model kwargs) — the model
    half of an inference artifact (`runtime.export`). ``ref`` names a zoo
    module; with no config, registry names (e.g. ``resnet50``) work too."""
    if not config:
        if ref in _MODULES:
            return _MODULES[ref].MODEL
        return get(ref)
    if ref not in _MODULES:
        raise KeyError(f"unknown model module {ref!r}; have {sorted(_MODULES)}")
    mod = _MODULES[ref]
    if not hasattr(mod, "make_model"):
        raise TypeError(f"model {ref!r} is not configurable (no make_model)")
    return mod.make_model(**config)


def serving_refusal(ref: str):
    """Why the zoo module named ``ref``, or the one whose model is named so,
    cannot be exported or served (its ``NOT_SERVABLE``), or None where it
    can."""
    mod = _MODULES.get(ref) or next(
        (m for m in _MODULES.values() if m.MODEL.name == ref), None)
    return getattr(mod, "NOT_SERVABLE", None)


__all__ = ["Model", "ctr", "fit_a_line", "get", "hybrid", "mnist", "resnet",
           "resolve", "serving_refusal", "transformer", "word2vec"]
