"""The model convention the elastic runtime trains against.

A ``Model`` is a bundle of pure functions — no hidden state, no framework
classes — so the runtime can jit/shard/checkpoint it uniformly:

- ``init(key, mesh)`` -> params pytree (created sharded on the mesh).
- ``loss_fn(params, batch, mesh)`` -> scalar loss (jit-traceable; the runtime
  differentiates it and applies the optimizer under one jit).
- ``param_spec(mesh)`` -> PartitionSpec pytree matching params (replicated by
  default; big tables row-sharded).
- ``synthetic_batch(rng, batch_size)`` -> host-side numpy batch for tests and
  benchmarks.

This replaces the reference's Paddle program construction + transpiler
contract (`example/ctr/ctr/train.py:119-151`): there, distribution rewrites
the graph; here, the same loss function runs on any mesh and only the specs
change.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

Params = Any
Batch = Dict[str, np.ndarray]


@dataclass(frozen=True)
class Model:
    name: str
    init: Callable  # (key, mesh) -> params
    loss_fn: Callable  # (params, batch, mesh) -> scalar
    param_spec: Callable  # (mesh) -> PartitionSpec pytree
    synthetic_batch: Callable  # (np.random.Generator, batch_size) -> Batch
    #: optional (mesh) -> {batch key: PartitionSpec}. Default None = every
    #: array sharded on dim 0 over the trainer's batch axis; models with
    #: sequence-sharded inputs (transformer: tokens (B, S) over data x seq)
    #: override this so `Trainer.place_batch` places dims on the right axes.
    batch_spec: Optional[Callable] = None
    #: batch keys holding the training objective (labels/targets/weights).
    #: Wire transport never applies lossy encodings to these — a float
    #: regression target consumed by a float32 loss must cross exactly
    #: (integer labels keep their exact u8/u24 encodings).
    label_keys: Tuple[str, ...] = ()
    #: optional inference entrypoint (params, batch, mesh) -> outputs, the
    #: serving twin of loss_fn (jit-traceable; batch omits label keys).
    #: Drives `runtime.export.load_inference_model(...).predict` — the
    #: reference's save_inference_model program (`ctr/train.py:169-180`).
    predict: Optional[Callable] = None
    #: optional structured config the model was built from (e.g. a
    #: ResNetConfig/TransformerConfig) for forward helpers and export.
    config: Optional[Any] = None
    #: optional analytic (batch_size) -> train-step model FLOPs. Convention:
    #: matmul/conv FLOPs only (2*M*N*K per matmul), causal attention halved,
    #: backward = 2x forward (so train = 3x forward), rematerialization
    #: recompute EXCLUDED — i.e. the numerator of "model FLOPs utilization"
    #: in the standard (PaLM-appendix) sense, so bench MFU numbers are
    #: comparable to published ones. `edl_tpu.tools.mfu` falls back to XLA
    #: cost analysis when absent.
    flops_per_step: Optional[Callable] = None
    #: optional (params, batch) -> {layer: {made, held, per_expert, dropped}}
    #: for models with routed experts: the router's decisions on one batch as
    #: host numbers (and into the metrics registry). A forward pass of its
    #: own, for a caller outside the timed step (`models/hybrid.py`).
    routing_stats: Optional[Callable] = None
    #: optional (params, batch, whole=False) -> {layer: {...}} for models
    #: with learned sparse attention: which keys sampled queries attend to,
    #: the layer's input, and counters (selected, visible, future,
    #: miscounted), as host numbers and into the metrics registry; with
    #: ``whole`` each layer's whole selection too, on the device. A forward
    #: pass of its own, outside the timed step (`models/hybrid.py`).
    selection_stats: Optional[Callable] = None
    #: optional (params, batch) -> {layer: {visible, causal}} for models with
    #: sliding-window attention layers: the (query, key) pairs each attention
    #: layer's core sees over the batch's queries, under its window and under
    #: causality alone, counted by the core itself (`models/hybrid.py`).
    window_stats: Optional[Callable] = None
