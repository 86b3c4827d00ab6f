"""Sharding helpers: NamedSharding construction and host->mesh data placement."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: a batch axis is one mesh axis or a hierarchy of them (e.g.
#: ("dcn", "data") for multi-slice data parallelism — see parallel.mesh)
BatchAxis = Union[str, Sequence[str]]


def present_axes(mesh: Mesh, axis: BatchAxis) -> Tuple[str, ...]:
    """The subset of ``axis`` (str or sequence) present on ``mesh``."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    return tuple(a for a in names if a in mesh.axis_names)


def axis_size(mesh: Mesh, axis: BatchAxis) -> int:
    """Product of the present axes' sizes (1 when none present)."""
    n = 1
    for a in present_axes(mesh, axis):
        n *= mesh.shape[a]
    return n


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicate(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def canonical_sharding(sharding: Any) -> Any:
    """``sharding`` spelled as a jit output carries it: JAX reads an
    output's XLA sharding back into a spec without trailing unsharded dims,
    so ``P(None, "data", None)`` comes back as ``P(None, "data")``. Both
    place a leaf identically but compare unequal, and jit keys its cache on
    the comparison: a donated state spelled the long way compiles a second
    program when the step's own outputs come back. Shardings other than
    NamedSharding pass through."""
    if not isinstance(sharding, NamedSharding):
        return sharding
    spec = tuple(sharding.spec)
    while spec and spec[-1] is None:
        spec = spec[:-1]
    if len(spec) == len(sharding.spec):
        return sharding
    return NamedSharding(sharding.mesh, P(*spec),
                         memory_kind=sharding.memory_kind)


def with_canonical_sharding(x: jax.Array) -> jax.Array:
    """``x`` relabelled with `canonical_sharding`, over the same buffers:
    the placement is unchanged, so nothing is copied and no program runs
    (a ``device_put`` across processes would run one). A traced ``x``
    (under ``jax.eval_shape``) is placed nowhere and comes back as is."""
    if isinstance(x, jax.core.Tracer):
        return x
    sharding = canonical_sharding(x.sharding)
    if sharding is x.sharding:
        return x
    return jax.make_array_from_single_device_arrays(
        x.shape, sharding, [shard.data for shard in x.addressable_shards]
    )


def batch_sharding(mesh: Mesh, axis: BatchAxis = "data") -> NamedSharding:
    """Leading-dim batch sharding over the data axis or axis hierarchy
    (absent axes drop out; none present: replicate)."""
    have = present_axes(mesh, axis)
    if have:
        # a single axis stays a bare name: P(("data",)) and P("data") shard
        # identically but compare unequal, and the normalized form is what
        # every other spec in the codebase (and tests) uses
        return NamedSharding(mesh, P(have if len(have) > 1 else have[0]))
    return replicate(mesh)


def batch_shardings(
    mesh: Mesh,
    axis: BatchAxis = "data",
    specs: Optional[Any] = None,
):
    """Sharding(s) for placing a host batch on ``mesh``.

    With ``specs`` (a PartitionSpec pytree from `Model.batch_spec`): a
    NamedSharding pytree matching the batch structure. Without: ONE
    leading-dim batch sharding shared by every leaf. Hoisted out of the
    placement tree_maps so NamedSharding construction happens once per
    batch, not once per leaf — and reused by the AOT warm-compile path
    (`Trainer.warm_compile`) to derive placed-batch avals without placing
    anything.
    """
    if specs is not None:
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )
    return batch_sharding(mesh, axis)


def shard_batch(
    batch: Any,
    mesh: Mesh,
    axis: BatchAxis = "data",
    specs: Optional[Any] = None,
):
    """Place a host-side batch pytree onto the mesh.

    Default: every array sharded on dim 0 over ``axis``. With ``specs`` (a
    PartitionSpec pytree matching ``batch``, from `Model.batch_spec`), each
    array gets its own layout — e.g. transformer tokens (B, S) over data x seq.

    The per-trainer data path: each trainer produces its local slice of the
    global batch (from its leased data shards); `jax.device_put` with a
    NamedSharding makes the global array. Replaces the reference's
    per-trainer file-shard reader (`example/fit_a_line/fluid/common.py:24-40`,
    `idx % trainers == trainer_id`).

    Multi-process (`jax.distributed` initialized): each process passes its
    LOCAL slice and `jax.make_array_from_process_local_data` assembles the
    global array — no host ever holds the full batch.
    """
    shardings = batch_shardings(mesh, axis, specs)
    per_leaf = not isinstance(shardings, jax.sharding.Sharding)
    if jax.process_count() > 1:
        # Shardings are built once above and leaves convert to numpy in one
        # pass here — mirroring the single-process batched dispatch below
        # instead of rebuilding a NamedSharding and re-converting inside the
        # assembly tree_map for every leaf of every step's batch.
        host_batch = jax.tree_util.tree_map(lambda a: np.asarray(a), batch)
        if per_leaf:
            return jax.tree_util.tree_map(
                lambda a, s: jax.make_array_from_process_local_data(s, a),
                host_batch,
                shardings,
            )
        return jax.tree_util.tree_map(
            lambda a: jax.make_array_from_process_local_data(shardings, a),
            host_batch,
        )

    # Single process: ONE device_put over the whole tree — a single batched
    # dispatch instead of one call per key. Device arrays pass through
    # (device_put reshards them); everything else becomes host numpy so the
    # transfer goes STRAIGHT to the target sharding — jnp.asarray here would
    # bounce through the default device first (an extra hop on the
    # PCIe-bound input path).
    host_batch = jax.tree_util.tree_map(
        lambda a: a if isinstance(a, jax.Array) else np.asarray(a), batch
    )
    return jax.device_put(host_batch, shardings)


def global_batch_size(local_batch: int, mesh: Mesh, axis: BatchAxis = "data") -> int:
    return local_batch * axis_size(mesh, axis)
