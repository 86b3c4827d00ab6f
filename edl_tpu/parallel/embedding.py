"""Row-sharded embedding tables: the TPU-native sparse parameter server.

The reference serves large sparse embeddings (CTR's 1e6+1-row table,
`example/ctr/ctr/train.py:60-64`) from dedicated C++ pserver processes over
per-pserver sparse ports (`pkg/jobparser.go:232-247`, `docker/paddle_k8s:7-9`).
Here the table is one jax array row-sharded across the mesh — each device's
HBM holds ``vocab/N`` rows, the moral equivalent of one pserver shard — and a
lookup is a `shard_map` collective instead of an RPC:

- ids sharded on the same axis as the table (pure-DP meshes): all-gather the
  ids, gather local rows with an ownership mask, then ``psum_scatter`` so each
  device keeps exactly its batch slice — the classic embedding all-to-all,
  riding ICI.
- ids sharded on a different axis (dedicated ``expert`` axis): each row-shard
  sees its full local batch; masked local gather + ``psum`` over the row axis.

Both paths are differentiable under jit: the backward of gather/psum_scatter
is scatter-add/all-gather, which XLA lowers to the mirror-image collective —
this is what replaces the reference's sparse gradient push.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# -- dedup'd gather: sparse-gradient aggregation (opt-in) ----------------------
#
# The backward of a plain ``table[ids]`` is a scatter-add with duplicate
# indices. This gather's custom vjp pre-combines duplicate ids (sort +
# sorted segment-sum) so the final scatter sees each row at most once and
# can assert ``unique_indices`` — the moral equivalent of the reference
# pserver's aggregated sparse-row update (`docker/paddle_k8s:7-9`).
#
# Measured on v5e with CTR shapes (8192x26 zipf ids into a 1e6x10 table),
# XLA's native scatter-add beat this path (11.6 ms vs 18.6 ms: the 213k-key
# sort dominates), so the lookup paths below use the plain gather; this
# stays available for workloads with far heavier id duplication (it wins
# when duplicates per step >> unique rows, e.g. tiny vocabularies).


@jax.custom_vjp
def dedup_gather(table: jax.Array, flat_ids: jax.Array) -> jax.Array:
    """``table[flat_ids]`` whose backward aggregates duplicate ids before
    scattering. ``flat_ids``: 1-D non-negative int array."""
    return table[flat_ids]


def _dedup_gather_fwd(table, flat_ids):
    return table[flat_ids], (table, flat_ids)


def _dedup_gather_bwd(res, g):
    table, flat_ids = res
    # Canonicalize: the sentinel logic below needs a signed dtype wide enough
    # for table.shape[0] + n (segment_max's identity for unsigned ints is 0,
    # which would collide with real row 0).
    flat_ids = flat_ids.astype(jnp.int32)
    n = flat_ids.shape[0]
    if n == 0:
        return jnp.zeros_like(table), None
    sorted_ids, perm = jax.lax.sort_key_val(
        flat_ids, jnp.arange(n, dtype=jnp.int32)
    )
    g_sorted = jnp.take(g, perm, axis=0)
    starts = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_ids[1:] != sorted_ids[:-1]]
    )
    seg = jnp.cumsum(starts.astype(jnp.int32)) - 1
    uniq_grad = jax.ops.segment_sum(
        g_sorted, seg, num_segments=n, indices_are_sorted=True
    )
    uniq_ids = jax.ops.segment_max(
        sorted_ids, seg, num_segments=n, indices_are_sorted=True
    )
    # Empty segments hold segment_max's identity (int32 min); remap each to a
    # distinct out-of-range slot so `unique_indices` stays honest and `drop`
    # discards them.
    sentinel = table.shape[0] + jnp.arange(n, dtype=uniq_ids.dtype)
    uniq_ids = jnp.where(uniq_ids < 0, sentinel, uniq_ids)
    dtable = jnp.zeros_like(table).at[uniq_ids].add(
        uniq_grad.astype(table.dtype), mode="drop", unique_indices=True
    )
    return dtable, None


dedup_gather.defvjp(_dedup_gather_fwd, _dedup_gather_bwd)


@dataclass(frozen=True)
class ShardedEmbedding:
    """Config + functional init/apply for one row-sharded table.

    vocab is padded up so every shard holds the same row count (XLA needs
    static equal shards). ``shard_axis`` is the mesh axis rows live on;
    ``batch_axis`` the axis ids/batches are sharded on (may be the same).
    """

    vocab_size: int
    features: int
    shard_axis: str = "data"
    #: one mesh axis or a hierarchy tuple (e.g. ("dcn", "data")) the
    #: ids/batches are sharded over
    batch_axis: Any = "data"
    dtype: jnp.dtype = jnp.float32

    #: vocab is padded to a multiple of this REGARDLESS of mesh size, so the
    #: table shape is stable across elastic rescale (a checkpoint written on a
    #: 4-shard mesh restores onto 8 shards by resharding, not reshaping).
    #: 256 divides evenly for every power-of-two shard count up to 256.
    PAD_MULTIPLE = 256

    def padded_vocab(self, mesh: Mesh) -> int:
        n = mesh.shape[self.shard_axis] if self.shard_axis in mesh.axis_names else 1
        if self.PAD_MULTIPLE % n == 0:
            return _round_up(self.vocab_size, self.PAD_MULTIPLE)
        # Exotic shard counts (e.g. 3, 12) fall back to the LCM so rows still
        # split evenly — at the cost of rescale-compatible shapes.
        return _round_up(self.vocab_size, n * self.PAD_MULTIPLE)

    def table_spec(self) -> P:
        return P(self.shard_axis, None)

    def init(self, key: jax.Array, mesh: Mesh, scale: float = 0.01) -> jax.Array:
        """Initialize the sharded table directly on the mesh (no host copy of
        the full table — rows materialize shard-local, as pserver shards did)."""
        vocab = self.padded_vocab(mesh)
        sharding = NamedSharding(mesh, self.table_spec())

        @partial(jax.jit, out_shardings=sharding)
        def _init():
            return (
                jax.random.normal(key, (vocab, self.features), dtype=self.dtype)
                * scale
            )

        return _init()

    def apply(self, mesh: Mesh, table: jax.Array, ids: jax.Array) -> jax.Array:
        """Lookup: ids (...,) int32 -> embeddings (..., features).

        Out-of-range ids (e.g. the reference's hashed features modulo vocab)
        must be pre-clipped by the caller; padded rows return real (trainable,
        never-updated) values, matching pserver semantics for unused buckets.
        """
        if self.shard_axis not in mesh.axis_names or mesh.shape[self.shard_axis] == 1:
            return table[ids]

        flat = ids.reshape(-1)
        if self.shard_axis == self.batch_axis:
            out = self._lookup_same_axis(mesh, table, flat)
        else:
            out = self._lookup_cross_axis(mesh, table, flat)
        return out.reshape(ids.shape + (self.features,))

    # -- shard_map kernels -----------------------------------------------------

    def _lookup_same_axis(self, mesh: Mesh, table: jax.Array, flat_ids: jax.Array):
        axis = self.shard_axis
        n = mesh.shape[axis]

        def kernel(table_local: jax.Array, ids_local: jax.Array):
            # (B/n,) -> (B,): everyone needs to answer everyone's queries.
            ids_all = jax.lax.all_gather(ids_local, axis, tiled=True)
            local_rows = table_local.shape[0]
            offset = jax.lax.axis_index(axis) * local_rows
            local_ids = ids_all - offset
            hit = (local_ids >= 0) & (local_ids < local_rows)
            safe = jnp.clip(local_ids, 0, local_rows - 1)
            contrib = jnp.where(hit[:, None], table_local[safe], 0)
            # Return each participant its own batch slice, summed over owners.
            return jax.lax.psum_scatter(contrib, axis, scatter_dimension=0, tiled=True)

        return jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(self.table_spec(), P(axis)),
            out_specs=P(axis, None),
        )(table, flat_ids)

    def _lookup_cross_axis(self, mesh: Mesh, table: jax.Array, flat_ids: jax.Array):
        from edl_tpu.parallel.sharding import present_axes

        shard_ax = self.shard_axis
        have = present_axes(mesh, self.batch_axis)
        batch_ax = have or None  # P accepts the axis tuple directly
        batch_spec = P(batch_ax) if have else P()

        def kernel(table_local: jax.Array, ids_local: jax.Array):
            local_rows = table_local.shape[0]
            offset = jax.lax.axis_index(shard_ax) * local_rows
            local_ids = ids_local - offset
            hit = (local_ids >= 0) & (local_ids < local_rows)
            safe = jnp.clip(local_ids, 0, local_rows - 1)
            contrib = jnp.where(hit[:, None], table_local[safe], 0)
            return jax.lax.psum(contrib, shard_ax)

        out_spec = P(batch_ax, None) if have else P(None, None)
        return jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(self.table_spec(), batch_spec),
            out_specs=out_spec,
        )(table, flat_ids)
