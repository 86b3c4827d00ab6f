"""SPMD train-step construction: one jit, any model, any mesh.

Replaces the reference's per-strategy program construction — local SGD
(`example/fit_a_line/train_local.py`), transpiled pserver programs
(`example/ctr/ctr/train.py:204-231`), ParallelExecutor replica execution
(`train.py:146-151`) — with a single code path: the model's pure ``loss_fn``
is differentiated and the optimizer applied inside one ``jax.jit`` whose
inputs live sharded on the mesh. XLA's SPMD partitioner inserts the gradient
all-reduce over the ``data`` axis (what the pserver round-trip did) and the
embedding collectives (what the sparse ports did); donated buffers keep
optimizer state update in-place in HBM.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from edl_tpu.models.base import Model
from edl_tpu.obs.metrics import get_registry
from edl_tpu.parallel.sharding import (
    batch_shardings,
    canonical_sharding,
    shard_batch,
    with_canonical_sharding,
)

log = logging.getLogger("edl_tpu.runtime.train_loop")

#: retraces inside the steady loop are a performance bug wherever they
#: happen — one process-wide counter, shared by every Trainer instance.
_M_RETRACES = get_registry().counter(
    "edl_trainer_retraces_total",
    "steady-state jit recompilations (shape/dtype churn in the hot loop)",
)


def _aval_signature(tree: Any) -> Tuple:
    """Hashable (structure, per-leaf shape/dtype/sharding) key for a pytree
    of arrays or ShapeDtypeStructs — what an AOT-compiled executable is
    specialized to. Leaves without a sharding (host numpy) key as None."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (
        treedef,
        tuple(
            (tuple(x.shape), str(np.dtype(x.dtype)), getattr(x, "sharding", None))
            for x in leaves
        ),
    )


#: public name — the serving tier keys its params-swap compatibility check
#: on the same signature its AOT bucket executables were specialized to.
aval_signature = _aval_signature


class _WarmStep(NamedTuple):
    """An AOT-compiled step executable and the avals it is specialized to."""

    fn: Any  # jax.stages.Compiled
    batch_signature: Tuple
    seconds: float  # compile wall time (reported by the rescale bench)


class TrainState(NamedTuple):
    step: jax.Array  # scalar int32
    params: Any
    opt_state: Any


@dataclass
class TrainerConfig:
    learning_rate: float = 1e-3
    optimizer: str = "adam"  # "adam" | "sgd" | "adagrad" (ref CTR uses adagrad-ish SGD)
    momentum: float = 0.0
    grad_clip_norm: float = 0.0
    #: one mesh axis or a hierarchy tuple (("dcn", "data") for multi-slice
    #: data parallelism; see parallel.mesh.build_hierarchical_mesh)
    batch_axis: Any = "data"
    seed: int = 0
    #: compact host->device batch transport (bf16 floats, u8/u24 ints; see
    #: edl_tpu.runtime.wire). Decode happens inside the jitted step.
    wire_transport: bool = False
    #: extra batch keys (besides the model's label_keys) that must never get
    #: a lossy wire encoding — e.g. per-sample weights fed to the loss.
    wire_raw_keys: Tuple[str, ...] = ()
    #: ZeRO-1: shard REPLICATED optimizer-state tensors (adam/adagrad
    #: moments) over the batch axis. Each chip then holds 1/N of the moments
    #: instead of a full copy; XLA SPMD partitions the elementwise optimizer
    #: update along the moment sharding and all-gathers the param update —
    #: HBM for one cheap data-axis collective per step. Param and gradient
    #: layouts are untouched, so the math is identical. Already-sharded
    #: moments (e.g. row-sharded embedding tables') keep their sharding.
    shard_opt_state: bool = False
    #: gradient synchronization over the batch axis:
    #: - "psum" — implicit: XLA all-reduces the FULL gradient (2·P bytes/chip
    #:   on a ring) and, under shard_opt_state, all-gathers the updated
    #:   params behind it (3·P·(N−1)/N total).
    #: - "reduce_scatter" — explicit ZeRO-1 data plane: gradients are pinned
    #:   to their ZeRO shard layout BEFORE the optimizer update, so the
    #:   cross-batch-axis reduction lowers as reduce-scatter, each chip
    #:   updates its 1/N moment+gradient shard, and only the updated params
    #:   all-gather (2·P·(N−1)/N total — the all-reduce's gather half is
    #:   never paid). Requires shard_opt_state and a model param_spec; on a
    #:   ("dcn", "data") hierarchy the DCN hop stays at shard size. Exact
    #:   same math (elementwise update on shards; reduction reassociation
    #:   is the only float-level difference). See parallel.collective for
    #:   the closed-form byte accounting and BENCH_COLLECTIVE.json for the
    #:   measured arms.
    #: - "auto" — "reduce_scatter" whenever the ZeRO layout exists
    #:   (shard_opt_state and param_spec), else "psum".
    grad_sync: str = "auto"
    #: microbatch gradient accumulation: > 1 runs the step as a lax.scan
    #: over that many microbatches of the placed batch. Under the explicit
    #: data plane each microbatch's gradient buckets are pinned to their
    #: shard layout INSIDE the scan body — the reduction of microbatch k is
    #: issued with no data dependence on microbatch k+1's backward, the
    #: lowering async collective schedulers overlap (and the scan carry
    #: accumulates 1/N-sized shards, not full gradients). Batch dim must
    #: divide by this count.
    grad_accum_microbatches: int = 1
    #: target size (MiB) of one gradient-reduction bucket in the
    #: accumulation mode: leaves are greedily packed (reverse traversal
    #: order — backward finishes the LAST layers' grads first) into
    #: buckets of at most this size, bounding each issued reduction so
    #: early buckets can reduce while later grads are still computing.
    #: Accounting per bucket lives in `Trainer.data_plane`.
    grad_bucket_mb: float = 4.0
    #: device-side input pipelining for ``Trainer.run``: 0 places each batch
    #: synchronously on the dispatch thread; N >= 1 runs ``place_batch``
    #: (wire encode + H2D shard placement) on a background pump thread,
    #: staying up to N placed batches ahead of step dispatch
    #: (`edl_tpu.runtime.pipeline.DevicePrefetcher`).
    pipeline_depth: int = 0


def _make_optimizer(cfg: TrainerConfig) -> optax.GradientTransformation:
    if cfg.optimizer == "adam":
        opt = optax.adam(cfg.learning_rate)
    elif cfg.optimizer == "sgd":
        opt = optax.sgd(cfg.learning_rate, momentum=cfg.momentum or None)
    elif cfg.optimizer == "adagrad":
        opt = optax.adagrad(cfg.learning_rate)
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    if cfg.grad_clip_norm > 0:
        opt = optax.chain(optax.clip_by_global_norm(cfg.grad_clip_norm), opt)
    return opt


def loss_value(loss: jax.Array) -> float:
    """A step's loss on the host: wait on the buffer's ready event, THEN
    copy. `float()` on a loss still being computed waits on the copy
    instead, and on the chip that wait outlasted a finished step by 0.8 to
    4.7 s in a third of the runs (PERF.md, PR 25). The one way this
    package reads a loss it has just dispatched."""
    return float(jax.block_until_ready(loss))


class Trainer:
    """Builds and owns the jitted train step for (model, mesh, config).

    The mesh is bound at construction; elastic rescale constructs a new
    Trainer on the new mesh and restores state via checkpoint
    (`edl_tpu.runtime.elastic`).
    """

    def __init__(
        self,
        model: Model,
        mesh: Mesh,
        config: Optional[TrainerConfig] = None,
        codec_channel: Optional[Any] = None,
        compile_cache: Optional[Any] = None,
    ):
        self.model = model
        self.mesh = mesh
        self.config = config or TrainerConfig()
        cfg = self.config
        self.opt = _make_optimizer(cfg)
        #: persistent AOT compile cache (runtime.compile_cache.CompileCache)
        #: consulted by warm_compile: revisiting a previously-seen (layout,
        #: avals) pair loads the serialized executable instead of re-paying
        #: XLA. None (default) keeps warm_compile always compiling.
        self.compile_cache = compile_cache
        #: how the last warm_compile was satisfied: "hit" | "miss" | "off"
        #: (rescale-span attribution; benches read it after join).
        self.last_compile_cache = "off"
        #: multi-process codec agreement (edl_tpu.runtime.wire.KVCodecChannel).
        #: Required for wire_transport in multi-process jobs: every process
        #: must jit the identical decode program, so the codec is negotiated
        #: through the coordinator KV instead of inferred per-process.
        self.codec_channel = codec_channel

        if cfg.grad_sync not in ("auto", "psum", "reduce_scatter"):
            raise ValueError(
                f"unknown grad_sync {cfg.grad_sync!r}; expected 'auto', "
                "'psum' or 'reduce_scatter'"
            )
        if cfg.grad_accum_microbatches < 1:
            raise ValueError(
                f"grad_accum_microbatches must be >= 1, got "
                f"{cfg.grad_accum_microbatches}"
            )
        zero_layout = cfg.shard_opt_state and model.param_spec is not None
        if cfg.grad_sync == "reduce_scatter" and not zero_layout:
            raise ValueError(
                "grad_sync='reduce_scatter' needs the ZeRO-1 layout: set "
                "shard_opt_state=True on a model with a param_spec (the "
                "explicit data plane updates 1/N moment+gradient shards)"
            )
        #: the mode the step actually lowers with ("psum"|"reduce_scatter"):
        #: "auto" resolves to the explicit plane whenever the ZeRO layout
        #: exists — it moves strictly fewer bytes at identical math.
        self.grad_sync = (
            "reduce_scatter"
            if zero_layout and cfg.grad_sync != "psum"
            else "psum"
        )
        self._data_plane: Optional[Dict[str, Any]] = None

        def _grads_and_loss(params, batch):
            """One (micro)batch's loss and gradient, the gradient pinned to
            its ZeRO shard layout under the explicit plane — the pin is
            what makes the partitioner lower the cross-batch-axis
            reduction as reduce-scatter instead of all-reduce."""
            loss, grads = jax.value_and_grad(model.loss_fn)(params, batch, mesh)
            if self.grad_sync == "reduce_scatter":
                from edl_tpu.parallel.collective import constrain_to_specs

                grads = constrain_to_specs(
                    grads, self._zero_specs(grads), mesh
                )
            return grads, loss

        def _accumulate(params, batch):
            """Scan-based gradient accumulation: microbatch k's (bucketed)
            reductions are issued inside the scan body with no data
            dependence on microbatch k+1's backward, so an async-collective
            scheduler can overlap them; under the explicit plane the carry
            holds 1/N gradient shards, not full gradients."""
            from edl_tpu.parallel.collective import (
                constrain_to_specs, split_microbatches,
            )

            n_micro = cfg.grad_accum_microbatches
            specs = (
                model.batch_spec(mesh) if model.batch_spec is not None else None
            )
            micro = split_microbatches(
                batch, n_micro, mesh, cfg.batch_axis, specs=specs
            )
            zero_specs = self._zero_specs(params)

            def body(acc, mb):
                grads, loss = _grads_and_loss(params, mb)
                acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                if self.grad_sync == "reduce_scatter":
                    # keep the carry on the shard layout step over step
                    acc = constrain_to_specs(acc, zero_specs, mesh)
                return acc, loss

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.result_type(p)), params
            )
            if self.grad_sync == "reduce_scatter":
                zeros = constrain_to_specs(zeros, zero_specs, mesh)
            grads, losses = jax.lax.scan(body, zeros, micro)
            grads = jax.tree_util.tree_map(
                lambda g: g / np.float32(n_micro), grads
            )
            # equal-sized microbatches: mean of per-microbatch means IS the
            # whole-batch mean, for the loss exactly as for the gradient
            return grads, jnp.mean(losses)

        def _step(state: TrainState, batch: Dict[str, jax.Array]) -> Tuple[TrainState, jax.Array]:
            # The two scopes name the step's phases in every operation's
            # `op_name`, so a device trace splits the step (metadata only).
            with jax.named_scope("fwd_bwd"):
                if cfg.grad_accum_microbatches > 1:
                    grads, loss = _accumulate(state.params, batch)
                else:
                    grads, loss = _grads_and_loss(state.params, batch)
            with jax.named_scope("optimizer"):
                params, opt_state = _update(state, grads)
            return TrainState(state.step + 1, params, opt_state), loss

        def _update(state: TrainState, grads):
            updates, opt_state = self.opt.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            if self.config.shard_opt_state and model.param_spec is not None:
                # ZeRO-1 boundary: without this pin, XLA's sharding
                # propagation would push the moments' data-axis sharding
                # onto the updated params too (drifting toward an implicit
                # ZeRO-3). Params keep their canonical layout; only the
                # optimizer state stays sharded. Under the explicit plane
                # this pin IS the all-gather that completes the
                # reduce-scatter → sharded-update → all-gather pipeline.
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                params = jax.tree_util.tree_map(
                    lambda p, s: jax.lax.with_sharding_constraint(
                        p, NamedSharding(mesh, s)
                    ),
                    params,
                    model.param_spec(mesh),
                    is_leaf=lambda x: isinstance(x, P),
                )
            return params, opt_state

        # Input shardings flow from the state/batch placements; XLA SPMD
        # inserts the data-axis psum for grads. Donation reuses HBM buffers.
        self._step_fn = _step
        self._jit_step = jax.jit(_step, donate_argnums=(0,))
        self._codec = None  # negotiated on first place_batch when wire_transport
        self._jit_step_wire = None
        #: retracing canary (the runtime complement of the EDL002 static
        #: check): cumulative count of step-function recompiles after the
        #: expected first-step compile. Nonzero means shape/dtype churn in
        #: the input pipeline is silently burning compile time every step.
        self.retraces = 0
        self._compiles_seen: Optional[int] = None
        self._warmed = False  # set once the jit cache holds steady one step
        #: memoized "this JAX version has no private _cache_size API" — set
        #: after the first None so the per-step canary probe stops
        #: re-reflecting over both jits for the rest of the run.
        self._cache_probe_broken = False
        #: AOT warm-compiled step executable (rescale warm-compile path).
        self._warm: Optional[_WarmStep] = None

    # -- state -----------------------------------------------------------------

    def init_state(self, key: Optional[jax.Array] = None) -> TrainState:
        key = key if key is not None else jax.random.PRNGKey(self.config.seed)
        # Every leaf is created committed where the step returns it, in the
        # spelling the step returns (`canonical_sharding`): each moment at
        # its parameter's sharding (a row-sharded table's moments are
        # row-sharded too), the step counter and the optimizer's counts
        # replicated on the mesh. A leaf that is uncommitted, or spelled
        # otherwise, comes back from the donating step changed, and step 2
        # then compiles a second program for the new input shardings.
        params = jax.tree_util.tree_map(
            with_canonical_sharding, self.model.init(key, self.mesh)
        )
        replicated = NamedSharding(self.mesh, P())
        opt_shardings = optax.tree_map_params(
            self.opt,
            # a traced parameter (under `jax.eval_shape`) is placed nowhere,
            # and jit places its moments as it likes
            lambda _, p: None if isinstance(p, jax.core.Tracer) else p.sharding,
            jax.eval_shape(self.opt.init, params),
            params,
            transform_non_params=lambda _: replicated,
        )
        opt_state = jax.jit(self.opt.init, out_shardings=opt_shardings)(params)
        # Gate on param_spec exactly like the step-boundary pin: sharding the
        # moments WITHOUT being able to pin params would let XLA propagation
        # push the data-axis layout onto the params (implicit ZeRO-3 drift).
        if self.config.shard_opt_state and self.model.param_spec is not None:
            opt_state = self._shard_opt_state(opt_state)
        step = jnp.zeros((), jnp.int32, device=replicated)
        return TrainState(step, params, opt_state)

    def _zero_specs(self, tree: Any) -> Any:
        """Per-leaf ZeRO-1 shard specs for a params-shaped pytree (grads or
        params): leaves whose param spec is fully replicated get their
        `zero_shard_spec` over the batch axis; model-sharded leaves and
        leaves with no divisible dim get None (left to the partitioner).
        Must agree leaf-for-leaf with `_shard_opt_state`'s moment placement
        — both route through `zero_shard_spec`, so the gradient shard the
        reduce-scatter lands IS the shard the local moments cover."""
        from jax.sharding import PartitionSpec as P

        from edl_tpu.parallel.collective import zero_shard_spec

        def leaf_spec(x, s):
            if any(e is not None for e in s):
                return None  # model-sharded param: grads keep its layout
            shape = jnp.shape(x)
            if len(shape) == 0:
                return None
            return zero_shard_spec(shape, self.mesh, self.config.batch_axis)

        return jax.tree_util.tree_map(
            leaf_spec,
            tree,
            self.model.param_spec(self.mesh),
            is_leaf=lambda x: isinstance(x, P),
        )

    def _shard_opt_state(self, opt_state: Any) -> Any:
        """ZeRO-1 placement: re-shard replicated moment tensors over the
        batch axis (largest divisible dim — `zero_shard_spec`). Leaves that
        already carry a real sharding (moments of sharded params) and
        scalars are untouched."""
        from edl_tpu.parallel.collective import zero_shard_spec
        from edl_tpu.parallel.sharding import present_axes

        axis = present_axes(self.mesh, self.config.batch_axis)
        if not axis:
            return opt_state

        def target_sharding(x):
            """New sharding for leaves that should reshard; None otherwise.
            Unchanged leaves (the counts, moments that stay replicated) keep
            the mesh sharding `init_state` committed them to, the one the
            step returns; only the resharded moments are copied."""
            if x.ndim == 0 or any(s is not None for s in x.sharding.spec):
                return None  # a count, or already sharded (table moments)
            spec = zero_shard_spec(x.shape, self.mesh, self.config.batch_axis)
            if spec is None:
                return None  # no divisible dim: stays replicated
            return canonical_sharding(NamedSharding(self.mesh, spec))

        # One batched device_put over just the resharded leaves (the
        # codebase's placement convention — see parallel/sharding.py).
        flat, treedef = jax.tree_util.tree_flatten(opt_state)
        targets = [target_sharding(x) for x in flat]
        to_move = [x for x, t in zip(flat, targets) if t is not None]
        if not to_move:
            return opt_state
        moved = iter(jax.device_put(to_move, [t for t in targets if t is not None]))
        out = [next(moved) if t is not None else x for x, t in zip(flat, targets)]
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- data-plane accounting -------------------------------------------------

    def data_plane(self, params: Any) -> Dict[str, Any]:
        """Analytic per-step data-plane accounting for this trainer's
        resolved ``grad_sync`` mode: bytes-on-wire per tier from the
        `parallel.collective` closed forms, a bandwidth-model seconds
        estimate (the profiler's ``collective_ms`` series), and the
        gradient-bucket assignment the accumulation mode issues. Pure
        shape/byte arithmetic on host — cached after the first call (the
        layout is frozen with the mesh; a rescale builds a new Trainer).

        Gradient reductions are priced once per microbatch: the transformer
        loss psums inside `shard_map`, so its backward reduces per
        microbatch in BOTH modes — no whole-batch deferral is assumed. The
        param all-gather is paid once per step in either mode.
        """
        if self._data_plane is not None:
            return self._data_plane
        from edl_tpu.parallel.collective import (
            assign_buckets,
            collective_bytes,
            estimate_collective_seconds,
            zero1_step_bytes,
        )
        from edl_tpu.parallel.sharding import present_axes

        axes = present_axes(self.mesh, self.config.batch_axis)
        tiers = [(a, int(self.mesh.shape[a])) for a in axes]
        leaves = jax.tree_util.tree_leaves(params)
        leaf_nbytes = [
            int(np.prod(jnp.shape(x), dtype=np.int64))
            * np.dtype(jnp.result_type(x)).itemsize
            for x in leaves
        ]
        zero_layout = (
            self.config.shard_opt_state and self.model.param_spec is not None
        )
        if zero_layout:
            from jax.sharding import PartitionSpec as P

            flat_specs = jax.tree_util.tree_leaves(
                self._zero_specs(params),
                is_leaf=lambda x: x is None or isinstance(x, P),
            )
        else:
            flat_specs = [None] * len(leaves)
        sharded = float(
            sum(nb for nb, s in zip(leaf_nbytes, flat_specs) if s is not None)
        )
        replicated = float(
            sum(nb for nb, s in zip(leaf_nbytes, flat_specs) if s is None)
        )
        n_micro = max(1, self.config.grad_accum_microbatches)
        step_acct = zero1_step_bytes(sharded, replicated, tiers, self.grad_sync)
        param_acct = collective_bytes(sharded, tiers, "all_gather")
        # per-tier totals: (grad-only share) × microbatches + one param AG
        per_tier = {
            name: (step_acct[name] - param_acct[name]) * n_micro
            + param_acct[name]
            for name, _ in tiers
        }
        grad_bytes = step_acct["grad_bytes"] * n_micro
        bucket_bytes = max(1, int(self.config.grad_bucket_mb * 2**20))
        buckets = assign_buckets(leaf_nbytes, bucket_bytes)
        self._data_plane = {
            "grad_sync": self.grad_sync,
            "tiers": tiers,
            "grad_accum_microbatches": n_micro,
            "sharded_bytes": sharded,
            "replicated_bytes": replicated,
            "grad_bytes_per_step": grad_bytes,
            "param_bytes_per_step": step_acct["param_bytes"],
            "bytes_per_step": grad_bytes + step_acct["param_bytes"],
            "per_tier_bytes": per_tier,
            "collective_seconds": estimate_collective_seconds(per_tier),
            "bucket_target_bytes": bucket_bytes,
            "n_buckets": len(buckets),
            "bucket_nbytes": [int(b.nbytes) for b in buckets],
        }
        return self._data_plane

    # -- stepping --------------------------------------------------------------

    def _rebuild_wire_jit(self) -> None:
        codec = self._codec
        self._jit_step_wire = jax.jit(
            lambda state, wired: self._step_fn(state, codec.decode(wired)),
            donate_argnums=(0,),
        )

    def place_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        multiproc = jax.process_count() > 1
        if self.config.wire_transport and multiproc and self.codec_channel is None:
            # Per-process codec inference from local batches would diverge the
            # jitted programs and mis-pair collectives; without a negotiation
            # channel the only safe transport is raw.
            if not getattr(self, "_warned_wire_multiproc", False):
                self._warned_wire_multiproc = True
                log.warning(
                    "wire_transport disabled: multi-process jobs need a "
                    "codec_channel (KVCodecChannel) for a globally agreed codec"
                )
        elif self.config.wire_transport:
            from edl_tpu.runtime.wire import (
                WireCodec, WireOverflowError, WireRestartRequired,
            )

            if self._codec is None:
                if not multiproc:
                    self._codec = WireCodec.infer(
                        batch,
                        no_lossy_keys=(*self.model.label_keys,
                                       *self.config.wire_raw_keys),
                    )
                    if self.codec_channel is not None:
                        # Single-process jobs still honor the persistent widen
                        # floor so a restart cannot re-learn old overflows.
                        self._codec = self._codec.apply_floor(
                            self.codec_channel.floor()
                        )
                elif jax.process_index() == 0:
                    inferred = WireCodec.infer(
                        batch,
                        no_lossy_keys=(*self.model.label_keys,
                                       *self.config.wire_raw_keys),
                    )
                    self._codec = self.codec_channel.publish(inferred)
                else:
                    self._codec = self.codec_channel.fetch()
                self._rebuild_wire_jit()
            while True:
                try:
                    batch = self._codec.encode(batch)
                    break
                except WireOverflowError as e:
                    if multiproc:
                        # In-place widening would desync the gang (peers keep
                        # the old decode-jit). Publish the widened floor and
                        # demand a warm restart; renegotiation starts from the
                        # floor, so this overflow cannot recur.
                        self.codec_channel.raise_floor(
                            e.key, self._codec.widen(e.key).keys[e.key].encoding
                        )
                        raise WireRestartRequired(e.key) from e
                    # Single process: widen that key's encoding and re-jit
                    # (bounded — at most two widenings per key, then raw).
                    self._codec = self._codec.widen(e.key)
                    if self.codec_channel is not None:
                        self.codec_channel.raise_floor(
                            e.key, self._codec.keys[e.key].encoding
                        )
                    self._rebuild_wire_jit()
        specs = (
            self.model.batch_spec(self.mesh)
            if self.model.batch_spec is not None
            else None
        )
        return shard_batch(batch, self.mesh, self.config.batch_axis, specs=specs)

    def _step_callable(self, batch: Dict[str, Any]) -> Callable:
        """The program that will step ``batch``: the wire-decode jit for
        encoded batches, the AOT warm-compiled executable when one matches
        the batch avals, else the plain jit."""
        if self._codec is not None and self._codec.is_encoded(batch):
            return self._jit_step_wire
        if (
            self._warm is not None
            and _aval_signature(batch) == self._warm.batch_signature
        ):
            return self._warm_step
        return self._jit_step

    def _warm_step(self, state: TrainState, batch: Dict[str, Any]) -> Tuple[TrainState, jax.Array]:
        """Dispatch to the AOT warm-compiled executable; retire it and fall
        back to the jit on any aval/sharding mismatch it rejects (the batch
        signature can't see everything — e.g. state layout drift)."""
        warm = self._warm
        try:
            return warm.fn(state, batch)
        except (TypeError, ValueError) as e:
            log.warning(
                "warm-compiled step rejected its inputs (%s); retiring it "
                "and falling back to jit", e,
            )
            self._warm = None
            return self._jit_step(state, batch)

    def place_bound(self, batch: Dict[str, np.ndarray]) -> Tuple[Dict[str, Any], Callable]:
        """Place a batch AND snapshot the program that must step it.

        The pipelined hot loop (`DevicePrefetcher`) runs placement ahead of
        stepping, and a wire-codec widening during placement rebuilds
        ``_jit_step_wire`` — binding at placement time keeps each in-flight
        batch paired with the codec generation that encoded it.
        """
        placed = self.place_batch(batch)
        return placed, self._step_callable(placed)

    def train_step(self, state: TrainState, batch: Dict[str, Any]) -> Tuple[TrainState, jax.Array]:
        return self._step_callable(batch)(state, batch)

    # -- rescale warm-compile --------------------------------------------------

    def warm_compile(
        self,
        state: TrainState,
        host_batch_avals: Dict[str, jax.ShapeDtypeStruct],
    ) -> float:
        """AOT-compile the step for this mesh from abstract inputs; returns
        compile wall seconds (0.0 when skipped).

        Run on a background thread during the rescale checkpoint/drain
        window (`runtime/elastic.py`) so restore lands on a ready
        executable and the first post-rescale step pays dispatch, not XLA.
        ``host_batch_avals`` describes the HOST batch (shape/dtype only);
        placed-batch shardings are derived exactly like ``place_batch``
        derives them, so the executable matches what the hot loop feeds it.

        Wire transport is warm-compiled only once this trainer holds a
        negotiated codec; before first placement there is nothing to
        specialize against (guessing an encoding would compile a program
        the hot loop never runs), so we skip and report 0.0 — the elastic
        rescale path, which builds a FRESH trainer per mesh, therefore
        warm-compiles the raw-transport step only.
        """
        t0 = time.perf_counter()
        if self.config.wire_transport and self._codec is None:
            log.debug("warm_compile skipped: wire codec not negotiated yet")
            return 0.0
        specs = (
            self.model.batch_spec(self.mesh)
            if self.model.batch_spec is not None
            else None
        )
        if self.config.wire_transport:
            # Encoded-batch avals via a zeros round-trip: zeros fit every
            # int encoding's range, so this cannot overflow-widen the codec.
            zeros = {
                k: np.zeros(v.shape, v.dtype) for k, v in host_batch_avals.items()
            }
            host_batch_avals = {
                k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in self._codec.encode(zeros).items()
            }
        shardings = batch_shardings(self.mesh, self.config.batch_axis, specs)
        if isinstance(shardings, jax.sharding.Sharding):
            abstract_batch = {
                k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shardings)
                for k, v in host_batch_avals.items()
            }
        else:
            abstract_batch = jax.tree_util.tree_map(
                lambda v, s: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=s),
                dict(host_batch_avals),
                shardings,
            )
        def state_aval(x):
            # Only committed arrays pin their sharding into the lowering.
            # Every leaf of a state from `init_state` or a restore is
            # committed to the sharding the step returns, so this pins what
            # the lazy path's jit sees; an uncommitted leaf (a state built
            # by hand) is left for jit to place, as the lazy path does.
            sharding = (
                x.sharding if getattr(x, "_committed", False) else None
            )
            return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype, sharding=sharding)

        abstract_state = jax.tree_util.tree_map(state_aval, state)
        target = (
            self._jit_step_wire if self.config.wire_transport else self._jit_step
        )
        # Persistent AOT cache: a layout seen before (same mesh + devices,
        # same program config, same avals, same code) loads its serialized
        # executable instead of re-paying XLA. Wire-transport steps are not
        # cached — their program embeds a negotiated codec generation the
        # key cannot see.
        cache = self.compile_cache
        cache_key = None
        self.last_compile_cache = "off"
        if cache is not None and not self.config.wire_transport:
            cache_key = cache.key(
                self.mesh,
                self._compile_cache_repr(),
                _aval_signature(abstract_batch),
                _aval_signature(abstract_state),
            )
            hit = cache.load(cache_key, self.mesh)
            if hit is not None:
                seconds = time.perf_counter() - t0
                self._warm = _WarmStep(
                    hit, _aval_signature(abstract_batch), seconds
                )
                self.last_compile_cache = "hit"
                log.info(
                    "warm step for mesh %s served from compile cache in "
                    "%.3fs (zero compiles)", dict(self.mesh.shape), seconds,
                )
                return seconds
            self.last_compile_cache = "miss"
        compiled = target.lower(abstract_state, abstract_batch).compile()
        if cache_key is not None:
            cache.store(cache_key, compiled)
        seconds = time.perf_counter() - t0
        # AOT lower().compile() does NOT populate the jit dispatch cache
        # (verified: _cache_size stays 0 and the first normal call
        # recompiles), so the executable is kept and dispatched directly
        # via _step_callable's signature match.
        self._warm = _WarmStep(compiled, _aval_signature(abstract_batch), seconds)
        log.info(
            "warm-compiled step for mesh %s in %.2fs", dict(self.mesh.shape), seconds
        )
        return seconds

    def _compile_cache_repr(self) -> str:
        """The program-identity component of the compile-cache key: the
        trainer config (a dataclass: stable repr) plus the model's identity
        and structured config. Two trainers with equal reprs lower the
        identical step program for identical avals."""
        return repr((
            self.config,
            getattr(self.model, "name", ""),
            getattr(self.model, "config", None),
            self.grad_sync,
        ))

    # -- retracing canary ------------------------------------------------------

    def _jit_cache_size(self) -> Optional[int]:
        """Total compiled-program count across the step jits (None when the
        private ``_cache_size`` API is unavailable on this JAX version).
        Unavailability is memoized after the first None so the per-step
        canary probe stops re-reflecting over both jits for the whole run."""
        if self._cache_probe_broken:
            return None
        total = 0
        for fn in (self._jit_step, self._jit_step_wire):
            if fn is None:
                continue
            cache_size = getattr(fn, "_cache_size", None)
            if cache_size is None:
                self._cache_probe_broken = True
                return None
            try:
                total += int(cache_size())
            except Exception:  # edl: noqa[EDL005] observability probe on a private API; a broken probe must not fail the step
                self._cache_probe_broken = True
                return None
        return total

    def check_retrace(self, step: int) -> bool:
        """Record whether the step function recompiled since the last call.

        Warmup self-detects: cache growth is absorbed silently until the
        cache holds steady across one step (the step-1 compile; a state
        from `init_state` or a checkpoint restore already carries the
        shardings the step returns, so step 2 adds none). After that first
        stable step, any growth is a retrace — logged loudly, counted in
        ``self.retraces``, and surfaced in ``run()`` metrics. A wire-codec
        widening rebuilds ``_jit_step_wire`` and legitimately shrinks the
        cache; the baseline just resets (and re-warms).
        """
        total = self._jit_cache_size()
        if total is None:
            return False
        if self._compiles_seen is None or total < self._compiles_seen:
            self._compiles_seen = total
            self._warmed = False
            return False
        if total == self._compiles_seen:
            self._warmed = True
            return False
        grew = total - self._compiles_seen
        self._compiles_seen = total
        if self._warmed and step > 1:
            self.retraces += grew
            _M_RETRACES.inc(grew)
            log.warning(
                "train step RECOMPILED at step %d (%d new program(s), "
                "jit cache now %d) — shape/dtype churn in the input "
                "pipeline is spending compile time inside the hot loop",
                step, grew, total,
            )
            return True
        return False

    def _dispatch_iter(
        self, batches: Iterator[Dict[str, np.ndarray]], depth: int
    ) -> Iterator[Tuple[Dict[str, Any], Callable, int, float]]:
        """Yield ``(placed, step_fn, samples, place_seconds)`` per batch.

        depth == 0: place synchronously on the dispatch thread (timed
        inline). depth >= 1: run ``place_bound`` on a DevicePrefetcher pump
        thread so encode + H2D placement of batch N+1 overlaps step N; the
        step callable is snapshotted at placement time (codec widening
        in-flight must not re-route already-encoded batches).
        """
        if depth <= 0:
            for batch in batches:
                first = next(iter(batch.values()))
                t0 = time.perf_counter()
                placed, step_fn = self.place_bound(batch)
                yield placed, step_fn, len(first), time.perf_counter() - t0
            return
        from edl_tpu.runtime.pipeline import DevicePrefetcher

        with DevicePrefetcher(batches, self.place_bound, depth=depth) as pf:
            for item in pf:
                placed, step_fn = item.payload
                yield placed, step_fn, item.samples, item.place_seconds

    def run(
        self,
        state: TrainState,
        batches: Iterator[Dict[str, np.ndarray]],
        max_steps: Optional[int] = None,
        on_step: Optional[Callable[[int, float], None]] = None,
        profiler: Optional[Any] = None,
        pipeline_depth: Optional[int] = None,
    ) -> Tuple[TrainState, Dict[str, float]]:
        """Drive the hot loop host-side: place batch, step, account throughput.

        ``pipeline_depth`` (default ``config.pipeline_depth``) > 0 moves
        placement onto a background pump thread (`DevicePrefetcher`) so
        wire encode + H2D transfer overlap device compute; exceptions from
        the batch source or placement re-raise here exactly as in the
        synchronous loop.

        Losses stay on-device until the loop ends so JAX async dispatch can
        pipeline steps; passing ``on_step`` forces a per-step sync (use it for
        debugging, not benchmarking). ``profiler`` (a
        ``edl_tpu.tools.profiler.StepProfiler``) records per-step wall times
        without forcing syncs — its step times reflect dispatch cadence, so
        its aggregate throughput can over-report slightly on short runs
        (in-flight tail steps are not awaited); the returned ``metrics``
        dict's ``samples_per_sec`` is computed after the final sync.
        """
        depth = (
            self.config.pipeline_depth if pipeline_depth is None else pipeline_depth
        )
        losses = []
        n = 0
        t0 = time.perf_counter()
        samples = 0
        place_seconds = 0.0
        plane = self.data_plane(state.params)
        if profiler is not None:
            # Let the profiler's summary account FLOPs/MFU without the
            # caller having to thread the model/mesh through twice.
            if getattr(profiler, "model", None) is None:
                profiler.model = self.model
            if getattr(profiler, "n_chips", -1) is None:
                profiler.n_chips = max(1, self.mesh.devices.size)
            if getattr(profiler, "data_plane", None) is None:
                profiler.data_plane = plane
            profiler.start()
        for placed, step_fn, batch_samples, place_dt in self._dispatch_iter(
            batches, depth
        ):
            samples += batch_samples
            place_seconds += place_dt
            state, loss = step_fn(state, placed)
            n += 1
            self.check_retrace(n)
            if on_step is not None:
                on_step(n, loss_value(loss))
            if profiler is not None:
                profiler.step(
                    batch_samples,
                    place_seconds=place_dt,
                    collective_seconds=plane["collective_seconds"],
                )
            losses.append(loss)
            if max_steps is not None and n >= max_steps:
                break
        losses = [float(l) for l in jax.device_get(losses)] if losses else []
        elapsed = max(time.perf_counter() - t0, 1e-9)
        metrics = {
            "steps": float(n),
            "final_loss": losses[-1] if losses else float("nan"),
            "mean_loss": float(np.mean(losses)) if losses else float("nan"),
            "samples_per_sec": samples / elapsed,
            "seconds": elapsed,
            "retraces": float(self.retraces),
            "place_seconds": place_seconds,
            # analytic data-plane accounting (see Trainer.data_plane):
            # bytes are exact for the resolved grad_sync mode, seconds are
            # a bandwidth-model estimate, not a measurement.
            "grad_bytes_per_step": plane["grad_bytes_per_step"],
            "collective_seconds_est": plane["collective_seconds"] * n,
        }
        return state, metrics
