"""Pipeline parallelism: microbatch schedules over a ``pipe`` mesh axis.

The reference has no pipeline parallelism (SURVEY §2.3) — its distribution is
data-parallel PS only — but a TPU framework schedules models too big for one
chip's HBM, so stages are first-class here. Design:

- Stage parameters are a pytree whose LEADING dim is the stage index, sharded
  over the ``pipe`` mesh axis: each device holds one stage's weights (for a
  transformer, its contiguous chunk of layers).
- Activations hop one ICI neighbor per tick via `jax.lax.ppermute`;
  warmup/drain bubble ticks compute on garbage that is masked out, so the
  schedules stay jit-compilable with static shapes.
- Stage outputs must have the stage-input shape (the standard homogeneous-
  stage restriction; residual-stream models satisfy it by construction).

Three schedules:

- **GPipe** (`_pipeline_local`): the classic (M + n - 1)-tick forward loop,
  differentiated by autodiff — backward replays the reversed schedule. The
  activation stash grows O(M) per stage (every microbatch's stage input is
  saved for the backward scan).
- **1F1B** (`pipeline_train_1f1b`, ``virtual_stages=1``): forward AND
  backward interleave in ONE scan — each tick runs stage ``r``'s forward of
  microbatch ``t - r`` and its backward of microbatch ``t - 2(n-1) + r``,
  with a cotangent hop riding `ppermute` in the reverse direction. Because
  backward consumes activations while forward produces them, the stash is a
  ring buffer of at most ``min(M, 2n - 1)`` microbatch inputs — O(n),
  independent of M. That is the 1F1B memory property, and it is only
  reachable as a combined schedule: autodiff of any forward-only scan must
  first finish all M forwards (activations O(M)) before its reverse pass, so
  the construct computes loss and all gradients in its forward rule
  (`jax.custom_vjp`; the vjp just scales the stashed grads by the upstream
  cotangent).
- **Interleaved 1F1B** (``virtual_stages=v > 1``): each pipe rank owns ``v``
  NONCONTIGUOUS virtual stage chunks — rank ``r`` holds global virtual
  stages ``r + k*n`` for ``k < v`` (`interleaved_layout` gives the matching
  chunk-major storage packing) — and the combined scan advances in
  chunk-ticks of 1/v the per-rank work. Activations traverse all
  ``V = n*v`` virtual stages on a forward ring (wraparound ``n-1 -> 0``
  carries chunk ``k`` to chunk ``k+1``); cotangents ride the reverse ring.
  Microbatches are injected in groups of ``n`` (M must divide by n), giving
  the conflict-free timetable: forward of virtual stage ``s`` for microbatch
  ``m = q*n + j`` at chunk-tick ``q*n*v + s + j``, backward mirrored at
  ``q*n*v + j + 2*(V-1) - s``. Total span is ``M*v + n*v + n - 2``
  chunk-ticks — at v=1 exactly the plain schedule's ``M + 2(n-1)`` — so the
  warmup/drain bubble shrinks by ~v at fixed M (strictly, for n >= 3; at
  n=2 the lockstep span ties plain 1F1B). The stash grows to
  ``v * min(M, 3n)`` microbatch inputs — still O(n*v), independent of M.

Schedule economics on TPU (honest accounting, `bubble_fraction`): XLA's
static schedule executes masked bubble ticks at full cost, so at EQUAL M the
plain combined 1F1B scan (``M + 2(n-1)`` ticks of fwd+bwd) loses wall-clock
to GPipe's effective ``M + n - 1`` — plain 1F1B's win is HBM headroom (O(n)
stash admits a much larger M where GPipe OOMs). Interleaving closes that
gap at the schedule level: bubble ``(nv + n - 2)/v`` full-tick equivalents
vs plain's ``2(n-1)``. The committed sweep (`bench_pipeline.py` ->
`BENCH_PIPELINE.json`) quantifies all
three on the same mesh: per-step wall time and stash bytes across M and v.
Pick the schedule from those numbers — GPipe while the O(M) stash fits,
1F1B when activation memory binds, interleaved 1F1B (v >= 2, n >= 3) to buy
back most of 1F1B's bubble at a ~v-fold stash premium over plain 1F1B
(still M-independent).

`_pipeline_local` is the inside-a-shard_map form (composable with tensor and
sequence parallelism — the transformer calls it with ring attention inside the
stage function); `pipeline_apply` wraps it for standalone use. Stage
functions may carry a per-stage auxiliary value (MoE load-balance loss)
through any schedule: with ``stage_aux``/``aux_weight`` the stage function
returns ``(y, aux)`` — aux shape (1,), not rank-0: jax 0.4's shard_map
transpose gives residuals a leading-dim sharding that a scalar cannot
carry — the schedules accumulate aux only over real (stage, microbatch)
executions, psum it over the pipe axis, and fold
``aux_weight * mean_over_microbatches`` into the loss — gradients included
(the 1F1B runners seed the aux cotangent with ``aux_weight`` in each
per-tick vjp).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(
    schedule: str,
    n_stages: int,
    microbatches: int,
    virtual_stages: int = 1,
) -> float:
    """Fraction of stage executions that are masked warmup/drain garbage
    (XLA executes them at full cost — this is wasted wall-clock, not just
    idle time). GPipe: (n-1)/(M+n-1) in each of the forward and backward
    scans. 1F1B combined scan: 2(n-1)/(M+2(n-1)) of its fwd+bwd ticks.
    Interleaved 1F1B advances in chunk-ticks of 1/v the per-rank work over
    a span of M*v + n*v + n - 2, of which M*v are useful:
    (n*v + n - 2)/(M*v + n*v + n - 2) — equal to plain 1F1B at v=1, and
    strictly below it for v >= 2 whenever n >= 3 (at n=2 the lockstep
    schedule ties)."""
    n, m, v = n_stages, microbatches, virtual_stages
    if v < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {v}")
    if v != 1 and schedule != "1f1b-interleaved":
        raise ValueError(
            f"virtual_stages={v} only applies to '1f1b-interleaved', "
            f"not {schedule!r}"
        )
    if n <= 1:
        return 0.0
    if schedule == "gpipe":
        return (n - 1) / (m + n - 1)
    if schedule == "1f1b":
        return 2 * (n - 1) / (m + 2 * (n - 1))
    if schedule == "1f1b-interleaved":
        return (n * v + n - 2) / (m * v + n * v + n - 2)
    raise ValueError(f"unknown schedule {schedule!r}")


def stash_slots(
    schedule: str,
    n_stages: int,
    microbatches: int,
    virtual_stages: int = 1,
) -> int:
    """Per-device activation-stash entries, in units of one microbatch
    stage-input (the boundary activation; per-block internals are the remat
    story, orthogonal to the schedule). GPipe's forward scan saves its
    stage input every tick — M + n - 1 entries, O(M). Plain 1F1B holds a
    ring of min(M, 2n-1). Interleaved 1F1B holds v rings of min(M, 3n)
    (chunk k's input lives up to 2(V-1-s)+1 chunk-ticks; microbatches in
    flight per chunk span < 3n indices) — O(n*v), still M-independent."""
    n, m, v = n_stages, microbatches, virtual_stages
    if n <= 1:
        return 0
    if schedule == "gpipe":
        return m + n - 1
    if schedule == "1f1b":
        return min(m, 2 * n - 1)
    if schedule == "1f1b-interleaved":
        return v * min(m, 3 * n)
    raise ValueError(f"unknown schedule {schedule!r}")


def interleaved_layout(
    n_layers: int, n_stages: int, virtual_stages: int
) -> np.ndarray:
    """Layer permutation for chunk-major interleaved storage: entry ``p`` is
    the LOGICAL layer held at stacked-storage row ``p``. Rank ``r``'s
    contiguous shard (rows ``[r*L/n, (r+1)*L/n)`` under a ``P(pipe)``
    leading-dim sharding) then holds its virtual stages ``r + k*n`` back to
    back, chunk-major — rows ``k*Lc + j`` of the shard are logical layer
    ``(r + k*n)*Lc + j`` (``Lc = L/(n*v)``). Apply as ``stacked[perm]`` at
    init; invert with ``np.argsort(perm)`` to map gradients or checkpoints
    back to logical layer order. Identity at v=1."""
    n, v = n_stages, virtual_stages
    if n_layers % (n * v):
        raise ValueError(
            f"n_layers={n_layers} must divide by n_stages*virtual_stages="
            f"{n * v}"
        )
    lc = n_layers // (n * v)
    rows = [
        layer
        for r in range(n)
        for k in range(v)
        for layer in range((r + k * n) * lc, (r + k * n + 1) * lc)
    ]
    return np.asarray(rows, dtype=np.int64)


def _pipeline_local(
    stage_fn: Callable[[Any, jax.Array], Any],
    stage_params: Any,
    x: jax.Array,
    *,
    pipe_axis: str,
    n_stages: int,
    microbatches: int,
    stage_aux: bool = False,
) -> Any:
    """Run the GPipe schedule on local shards — call inside a shard_map
    whose manual axes include ``pipe_axis``.

    ``stage_params`` is THIS device's stage slice (leading stage dim already
    consumed by the enclosing in_spec). ``x``: (B_local, ...) activations; the
    full batch enters at stage 0 and the result is psum-broadcast to all
    stages so downstream (loss) code stays SPMD-uniform.

    With ``stage_aux=True`` the stage function returns ``(y, aux)`` (aux
    shape (1,) — a rank-0 aux in the differentiated scan carry trips jax
    0.4's shard_map scalar-residual transpose bug) and the return value is
    ``(outs, aux)`` where ``aux`` is the pipe-psum'd shape-(1,) per-stage
    value, accumulated only over real (stage, microbatch) executions and
    averaged over microbatches — differentiable, so GPipe's autodiff
    carries the aux gradient for free.
    """
    if n_stages == 1:
        return stage_fn(stage_params, x)
    M = microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"local batch {B} must be divisible by microbatches {M}")
    mb = x.reshape((M, B // M) + x.shape[1:])
    idx = jax.lax.axis_index(pipe_axis)
    fwd = [(i, i + 1) for i in range(n_stages - 1)]  # stage r -> r+1, no wrap

    def tick(carry, t):
        state, outs, aux_acc = carry
        # Stage 0 feeds microbatch t (clipped re-feeds during drain are
        # masked garbage); later stages consume the hop received last tick.
        inp = jnp.where(idx == 0, mb[jnp.clip(t, 0, M - 1)], state)
        out = stage_fn(stage_params, inp)
        y, aux_val = out if stage_aux else (out, None)
        if stage_aux:
            fm = t - idx  # this stage's microbatch this tick
            valid = (fm >= 0) & (fm < M)
            aux_acc = aux_acc + jnp.where(valid, aux_val, 0.0)
        opos = jnp.clip(t - (n_stages - 1), 0, M - 1)
        write = (idx == n_stages - 1) & (t >= n_stages - 1)
        prev = jax.lax.dynamic_index_in_dim(outs, opos, 0, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(write, y, prev), opos, 0
        )
        state = jax.lax.ppermute(y, pipe_axis, fwd)
        return (state, outs, aux_acc), None

    state0 = jnp.zeros_like(mb[0])
    outs0 = jnp.zeros_like(mb)
    (_, outs, aux_acc), _ = jax.lax.scan(
        tick, (state0, outs0, jnp.zeros((1,), jnp.float32)),
        jnp.arange(M + n_stages - 1)
    )
    # Only the last stage wrote real outputs (zeros elsewhere): broadcast.
    outs = jax.lax.psum(jnp.where(idx == n_stages - 1, outs, 0), pipe_axis)
    outs = outs.reshape((B,) + x.shape[1:])
    if stage_aux:
        return outs, jax.lax.psum(aux_acc, pipe_axis) / M
    return outs


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    pipe_axis: str = "pipe",
    batch_axis: str = "data",
    microbatches: Optional[int] = None,
) -> jax.Array:
    """Standalone pipeline over ``mesh``. ``stage_params`` leaves have a
    leading stage dim == pipe axis size; ``x`` (B, ...) is batch-sharded over
    ``batch_axis``. ``microbatches`` defaults to the stage count (bubble
    fraction (n-1)/(M+n-1); raise it to shrink the bubble)."""
    if pipe_axis not in mesh.axis_names or mesh.shape[pipe_axis] == 1:
        # No pipe axis on this mesh (e.g. after an elastic rescale dropped
        # it): run every stage sequentially on each device.
        n_stages = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
        out = x
        for i in range(n_stages):
            one = jax.tree_util.tree_map(lambda a, i=i: a[i], stage_params)
            out = stage_fn(one, out)
        return out
    n = mesh.shape[pipe_axis]
    M = microbatches or n

    param_specs = jax.tree_util.tree_map(lambda _: P(pipe_axis), stage_params)
    x_spec = P(batch_axis if batch_axis in mesh.axis_names else None)

    def kernel(params_local, x_local):
        one = jax.tree_util.tree_map(lambda a: a[0], params_local)
        return _pipeline_local(
            stage_fn, one, x_local, pipe_axis=pipe_axis, n_stages=n,
            microbatches=M,
        )

    return jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x)


# -- 1F1B: combined forward/backward schedule ----------------------------------


def _tree_where(cond, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(cond, x, y), a, b
    )


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _tree_zeros(t):
    return jax.tree_util.tree_map(jnp.zeros_like, t)


def _tree_scale(t, s):
    return jax.tree_util.tree_map(lambda x: (x * s).astype(x.dtype), t)


def _run_1f1b(stage_fn, tail_fn, pipe_axis, n_stages, microbatches,
              aux_weight, stage_params, tail_params, x, aux):
    """The plain combined schedule (see module docstring). Local to a
    shard_map.

    Returns ``(loss, (d_stage, d_tail, dx))`` where loss/d_tail/dx are
    pipe-replicated (psum-assembled) and d_stage is this rank's stage
    gradient. All gradients already carry the 1/M mean weighting. With
    ``aux_weight != 0`` the stage function returns ``(y, aux_scalar)`` and
    ``aux_weight * mean_over_microbatches(sum_over_stages(aux))`` is folded
    into the loss, its gradient seeded through each per-tick vjp.
    """
    n, M = n_stages, microbatches
    aux_mode = bool(aux_weight)
    B = x.shape[0]
    if B % M:
        raise ValueError(f"local batch {B} must be divisible by microbatches {M}")
    mb = x.reshape((M, B // M) + x.shape[1:])
    aux_mb = jax.tree_util.tree_map(
        lambda a: a.reshape((M, B // M) + a.shape[1:]), aux
    )
    r = jax.lax.axis_index(pipe_axis)
    fwd_pairs = [(i, i + 1) for i in range(n - 1)]
    bwd_pairs = [(i + 1, i) for i in range(n - 1)]
    n_slots = min(M, 2 * n - 1)  # max in-flight microbatches per stage

    def stage_vjp(a, g):
        """Recompute-forward vjp of one stage application (remat-style:
        only the stage INPUT is stashed). In aux mode the stage output is
        (y, aux[(1,)]) and the aux cotangent is the static aux weight."""
        _, vjp = jax.vjp(lambda p, a_: stage_fn(p, a_), stage_params, a)
        if aux_mode:
            return vjp((g, jnp.full((1,), aux_weight, jnp.float32)))
        return vjp(g)  # (d_params, d_input)

    def tail_grad(y, av):
        """Per-microbatch loss + seed cotangent at the last stage."""
        loss, vjp = jax.vjp(
            lambda tp, y_: tail_fn(tp, y_, av), tail_params, y
        )
        d_tail, g = vjp(jnp.ones_like(loss))
        return loss, d_tail, g

    def tick(carry, t):
        (fwd_hop, bwd_hop, act_buf, d_stage, d_tail, dx_grid, loss_acc,
         aux_acc) = carry

        # ---- F-phase: stage r runs forward of microbatch t - r ----
        fm = t - r
        valid_f = (fm >= 0) & (fm < M)
        fmc = jnp.clip(fm, 0, M - 1)
        inp = jnp.where(r == 0, mb[fmc], fwd_hop)
        out = stage_fn(stage_params, inp)
        y, aux_val = out if aux_mode else (out, None)
        if aux_mode:
            aux_acc = aux_acc + jnp.where(valid_f, aux_val, 0.0)
        # stash the stage input for this microbatch's backward
        slot_f = fmc % n_slots
        prev = jax.lax.dynamic_index_in_dim(act_buf, slot_f, 0, keepdims=False)
        act_buf = jax.lax.dynamic_update_index_in_dim(
            act_buf, jnp.where(valid_f, inp, prev), slot_f, 0
        )

        # ---- B-phase: stage r runs backward of microbatch t - 2(n-1) + r.
        # At the last stage that is exactly this tick's forward microbatch,
        # so its tail cotangent seeds from the y just computed. The tail
        # vjp only carries information on the last stage's valid ticks —
        # everywhere else both branches' outputs are masked downstream, so
        # a real branch skips the (full LM-head-sized) tail work.
        bm = t - 2 * (n - 1) + r
        valid_b = (bm >= 0) & (bm < M)
        bmc = jnp.clip(bm, 0, M - 1)
        av = jax.tree_util.tree_map(lambda a: a[bmc], aux_mb)
        last_valid = valid_b & (r == n - 1)
        loss_mb, d_tail_mb, g_tail = jax.lax.cond(
            last_valid,
            lambda _: tail_grad(y, av),
            lambda _: (jnp.zeros((), jnp.float32), _tree_zeros(tail_params),
                       jnp.zeros_like(y)),
            None,
        )
        g = jnp.where(r == n - 1, g_tail, bwd_hop).astype(y.dtype)
        a_saved = jax.lax.dynamic_index_in_dim(
            act_buf, bmc % n_slots, 0, keepdims=False
        )
        d_p, d_a = stage_vjp(a_saved, g)
        d_stage = _tree_add(d_stage, _tree_where(valid_b, d_p, _tree_zeros(d_p)))
        d_tail = _tree_add(
            d_tail, _tree_where(last_valid, d_tail_mb, _tree_zeros(d_tail_mb))
        )
        loss_acc = loss_acc + jnp.where(last_valid, loss_mb, 0.0)
        prev_dx = jax.lax.dynamic_index_in_dim(dx_grid, bmc, 0, keepdims=False)
        dx_grid = jax.lax.dynamic_update_index_in_dim(
            dx_grid, jnp.where(valid_b & (r == 0), d_a, prev_dx), bmc, 0
        )

        # ---- hops: activations to r+1, cotangents to r-1 ----
        fwd_hop = jax.lax.ppermute(y, pipe_axis, fwd_pairs)
        bwd_hop = jax.lax.ppermute(d_a, pipe_axis, bwd_pairs)
        return (fwd_hop, bwd_hop, act_buf, d_stage, d_tail, dx_grid,
                loss_acc, aux_acc), None

    carry0 = (
        jnp.zeros_like(mb[0]),                       # fwd activation hop
        jnp.zeros_like(mb[0]),                       # bwd cotangent hop
        jnp.zeros((n_slots,) + mb.shape[1:], mb.dtype),  # input ring buffer
        _tree_zeros(stage_params),
        _tree_zeros(tail_params),
        jnp.zeros_like(mb),                          # dx per microbatch
        jnp.zeros((), jnp.float32),
        jnp.zeros((1,), jnp.float32),                # aux accumulator
    )
    (_, _, _, d_stage, d_tail, dx_grid, loss_acc, aux_acc), _ = jax.lax.scan(
        tick, carry0, jnp.arange(M + 2 * (n - 1))
    )

    inv_m = 1.0 / M
    is_last = (r == n - 1).astype(jnp.float32)
    # loss and tail grads live only on the last stage; dx only on stage 0:
    # psum re-replicates them across the pipe axis (zeros elsewhere). Each
    # rank's aux accumulator covers its own stage, so the psum is the sum
    # over stages.
    total = loss_acc * is_last
    if aux_mode:
        total = total + jnp.asarray(aux_weight, jnp.float32) * aux_acc[0]
    loss = jax.lax.psum(total, pipe_axis) * inv_m
    d_tail = jax.tree_util.tree_map(
        lambda v: jax.lax.psum(
            (v * is_last.astype(v.dtype)).astype(v.dtype), pipe_axis
        ) * jnp.asarray(inv_m, v.dtype),
        d_tail,
    )
    # dx stays NONZERO ONLY ON STAGE 0 — the same per-device cotangent
    # pattern autodiff of the GPipe local program produces (x is consumed
    # through `where(r == 0, ...)` there too). The enclosing shard_map
    # transpose reconciles replicated-input cotangents from that pattern;
    # replicating dx across the pipe axis here would double-count.
    dx = (jnp.where(r == 0, dx_grid, 0) * jnp.asarray(inv_m, dx_grid.dtype))
    dx = dx.astype(x.dtype).reshape((B,) + x.shape[1:])
    d_stage = _tree_scale(d_stage, inv_m)
    return loss, (d_stage, d_tail, dx)


def _run_1f1b_interleaved(stage_fn, tail_fn, pipe_axis, n_stages, microbatches,
                          virtual_stages, aux_weight, stage_params,
                          tail_params, x, aux):
    """Interleaved combined schedule: rank ``r`` owns ``v`` chunks (global
    virtual stage ``r + k*n`` at chunk-major slice ``k`` of the leading
    param dim — `interleaved_layout` packing). The scan advances in
    chunk-ticks: each tick this rank runs ONE chunk's forward and ONE
    chunk's backward, the active chunk/microbatch decoded from the tick
    index by the conflict-free timetable derived in the module docstring.
    Same return convention and pipe-replication contract as `_run_1f1b`;
    ``d_stage`` comes back in the rank's stacked (chunk-major) layout.
    """
    n, M, v = n_stages, microbatches, virtual_stages
    V = n * v
    nv = n * v
    aux_mode = bool(aux_weight)
    if M % n:
        raise ValueError(
            f"interleaved 1F1B needs microbatches divisible by the pipe "
            f"size: M={M}, n={n}"
        )
    B = x.shape[0]
    if B % M:
        raise ValueError(f"local batch {B} must be divisible by microbatches {M}")
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] % v:
            raise ValueError(
                f"stage param leading dim {leaf.shape[0]} must divide by "
                f"virtual_stages={v}"
            )
    mb = x.reshape((M, B // M) + x.shape[1:])
    aux_mb = jax.tree_util.tree_map(
        lambda a: a.reshape((M, B // M) + a.shape[1:]), aux
    )
    r = jax.lax.axis_index(pipe_axis)
    # Full rings: the wraparound edge carries chunk k's boundary (stage
    # k*n - 1 -> k*n) forward and its cotangent backward.
    ring_fwd = [(i, (i + 1) % n) for i in range(n)]
    ring_bwd = [(i, (i - 1) % n) for i in range(n)]
    n_slots = min(M, 3 * n)  # in-flight microbatches per chunk (see stash_slots)

    # Chunk-major view of this rank's params: leading dim v, chunk k =
    # global virtual stage r + k*n.
    chunked = jax.tree_util.tree_map(
        lambda a: a.reshape((v, a.shape[0] // v) + a.shape[1:]), stage_params
    )

    def apply_chunk(k, a_in):
        cp = jax.tree_util.tree_map(
            lambda arr: jax.lax.dynamic_index_in_dim(arr, k, 0, keepdims=False),
            chunked,
        )
        return stage_fn(cp, a_in)

    def chunk_vjp(k, a_in, g):
        """vjp of chunk k's application w.r.t. the FULL chunked params —
        the dynamic-index transpose scatters the chunk gradient into an
        otherwise-zero (v, ...) tree, which accumulates directly."""
        def f(ch, a_):
            cp = jax.tree_util.tree_map(
                lambda arr: jax.lax.dynamic_index_in_dim(
                    arr, k, 0, keepdims=False
                ),
                ch,
            )
            return stage_fn(cp, a_)

        _, vjp = jax.vjp(f, chunked, a_in)
        if aux_mode:
            return vjp((g, jnp.full((1,), aux_weight, jnp.float32)))
        return vjp(g)

    def tail_grad(y, av):
        loss, vjp = jax.vjp(
            lambda tp, y_: tail_fn(tp, y_, av), tail_params, y
        )
        d_tail, g = vjp(jnp.ones_like(loss))
        return loss, d_tail, g

    def tick(carry, t):
        (fwd_hop, bwd_hop, act_buf, d_stage, d_tail, dx_grid, loss_acc,
         aux_acc) = carry

        # ---- F-phase: decode (chunk, microbatch) from u = t - r via the
        # mixed-radix timetable u = q*n*v + k*n + j  (j < n, k < v).
        u = t - r
        rem = jnp.mod(u, nv)
        k_f = rem // n
        j_f = rem % n
        fm = jnp.floor_divide(u, nv) * n + j_f
        valid_f = (u >= 0) & (fm < M)
        fmc = jnp.clip(fm, 0, M - 1)
        # Fresh microbatches enter only at virtual stage 0 = rank 0 chunk 0;
        # every other (rank, chunk) consumes the ring hop, which the
        # timetable guarantees is the previous virtual stage's output.
        inp = jnp.where((r == 0) & (k_f == 0), mb[fmc], fwd_hop)
        out = apply_chunk(k_f, inp)
        y, aux_val = out if aux_mode else (out, None)
        if aux_mode:
            aux_acc = aux_acc + jnp.where(valid_f, aux_val, 0.0)
        slot_f = k_f * n_slots + fmc % n_slots
        prev = jax.lax.dynamic_index_in_dim(act_buf, slot_f, 0, keepdims=False)
        act_buf = jax.lax.dynamic_update_index_in_dim(
            act_buf, jnp.where(valid_f, inp, prev), slot_f, 0
        )

        # ---- B-phase: mirrored timetable t = q*n*v + j + 2(V-1) - s with
        # s = r + k*n; substituting k' = v-1-k gives the mixed-radix form
        # z = t - 2(V-1) + r - n = (q-1)*n*v + (k'+1-1)*n ... decoded below.
        z = t - 2 * (V - 1) + r - n
        remb = jnp.mod(z, nv)
        k_b = v - 1 - remb // n
        j_b = remb % n
        bm = (jnp.floor_divide(z, nv) + 1) * n + j_b
        valid_b = (bm >= 0) & (bm < M)
        bmc = jnp.clip(bm, 0, M - 1)
        # The seed point — virtual stage V-1 — is rank n-1's chunk v-1,
        # whose backward tick coincides with its own forward of the same
        # microbatch, so the tail cotangent seeds from this tick's y.
        seed = (r == n - 1) & (k_b == v - 1)
        last_valid = valid_b & seed
        av = jax.tree_util.tree_map(lambda a: a[bmc], aux_mb)
        loss_mb, d_tail_mb, g_tail = jax.lax.cond(
            last_valid,
            lambda _: tail_grad(y, av),
            lambda _: (jnp.zeros((), jnp.float32), _tree_zeros(tail_params),
                       jnp.zeros_like(y)),
            None,
        )
        g = jnp.where(seed, g_tail, bwd_hop).astype(y.dtype)
        slot_b = k_b * n_slots + bmc % n_slots
        a_saved = jax.lax.dynamic_index_in_dim(
            act_buf, slot_b, 0, keepdims=False
        )
        d_p, d_a = chunk_vjp(k_b, a_saved, g)
        d_stage = _tree_add(d_stage, _tree_where(valid_b, d_p, _tree_zeros(d_p)))
        d_tail = _tree_add(
            d_tail, _tree_where(last_valid, d_tail_mb, _tree_zeros(d_tail_mb))
        )
        loss_acc = loss_acc + jnp.where(last_valid, loss_mb, 0.0)
        prev_dx = jax.lax.dynamic_index_in_dim(dx_grid, bmc, 0, keepdims=False)
        dx_grid = jax.lax.dynamic_update_index_in_dim(
            dx_grid,
            jnp.where(valid_b & (r == 0) & (k_b == 0), d_a, prev_dx),
            bmc, 0,
        )

        fwd_hop = jax.lax.ppermute(y, pipe_axis, ring_fwd)
        bwd_hop = jax.lax.ppermute(d_a, pipe_axis, ring_bwd)
        return (fwd_hop, bwd_hop, act_buf, d_stage, d_tail, dx_grid,
                loss_acc, aux_acc), None

    carry0 = (
        jnp.zeros_like(mb[0]),
        jnp.zeros_like(mb[0]),
        jnp.zeros((v * n_slots,) + mb.shape[1:], mb.dtype),
        _tree_zeros(chunked),
        _tree_zeros(tail_params),
        jnp.zeros_like(mb),
        jnp.zeros((), jnp.float32),
        jnp.zeros((1,), jnp.float32),
    )
    ticks = M * v + n * v + n - 2  # == M + 2(n-1) at v=1
    (_, _, _, d_stage, d_tail, dx_grid, loss_acc, aux_acc), _ = jax.lax.scan(
        tick, carry0, jnp.arange(ticks)
    )

    inv_m = 1.0 / M
    is_last = (r == n - 1).astype(jnp.float32)
    total = loss_acc * is_last
    if aux_mode:
        total = total + jnp.asarray(aux_weight, jnp.float32) * aux_acc[0]
    loss = jax.lax.psum(total, pipe_axis) * inv_m
    d_tail = jax.tree_util.tree_map(
        lambda t_: jax.lax.psum(
            (t_ * is_last.astype(t_.dtype)).astype(t_.dtype), pipe_axis
        ) * jnp.asarray(inv_m, t_.dtype),
        d_tail,
    )
    dx = (jnp.where(r == 0, dx_grid, 0) * jnp.asarray(inv_m, dx_grid.dtype))
    dx = dx.astype(x.dtype).reshape((B,) + x.shape[1:])
    # back to the rank's stacked storage layout
    d_stage = jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), d_stage
    )
    d_stage = _tree_scale(d_stage, inv_m)
    return loss, (d_stage, d_tail, dx)


def _run_combined(stage_fn, tail_fn, pipe_axis, n_stages, microbatches,
                  virtual_stages, aux_weight, stage_params, tail_params,
                  x, aux):
    if virtual_stages == 1:
        return _run_1f1b(stage_fn, tail_fn, pipe_axis, n_stages, microbatches,
                         aux_weight, stage_params, tail_params, x, aux)
    return _run_1f1b_interleaved(
        stage_fn, tail_fn, pipe_axis, n_stages, microbatches, virtual_stages,
        aux_weight, stage_params, tail_params, x, aux,
    )


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6))
def pipeline_train_1f1b(stage_fn, tail_fn, pipe_axis, n_stages, microbatches,
                        virtual_stages, aux_weight,
                        stage_params, tail_params, x, aux):
    """1F1B training pipeline (plain at ``virtual_stages=1``, interleaved
    for ``virtual_stages > 1``): mean over microbatches of
    ``tail_fn(tail_params, stage_chain(x_m), aux_m)`` (plus
    ``aux_weight * sum_over_stages(stage_aux)`` when ``aux_weight != 0``,
    in which case ``stage_fn`` returns ``(y, aux_scalar)``).

    Call inside a shard_map whose manual axes include ``pipe_axis``. For
    the interleaved schedule, stage params must be packed chunk-major
    (`interleaved_layout`) and ``microbatches`` must divide by
    ``n_stages``. ``aux`` is a non-differentiated pytree of per-example
    arrays (targets, masks) microbatched alongside ``x``. The loss it
    returns is differentiable w.r.t. ``stage_params``/``tail_params``/``x``
    — but the gradients were already computed by the combined schedule in
    the forward pass (that is the point: fwd and bwd interleave in one
    scan, bounding the activation stash at O(n_stages * virtual_stages));
    the vjp rule just scales them by the upstream cotangent. Calling this
    without differentiating it wastes the backward work — use the GPipe
    path for inference.
    """
    loss, _ = _run_combined(stage_fn, tail_fn, pipe_axis, n_stages,
                            microbatches, virtual_stages, aux_weight,
                            stage_params, tail_params, x, aux)
    return loss


def _1f1b_fwd(stage_fn, tail_fn, pipe_axis, n_stages, microbatches,
              virtual_stages, aux_weight, stage_params, tail_params, x, aux):
    loss, grads = _run_combined(stage_fn, tail_fn, pipe_axis, n_stages,
                                microbatches, virtual_stages, aux_weight,
                                stage_params, tail_params, x, aux)
    return loss, grads


def _1f1b_bwd(stage_fn, tail_fn, pipe_axis, n_stages, microbatches,
              virtual_stages, aux_weight, res, ct):
    d_stage, d_tail, dx = res
    # The construct's forward ends in a psum over the pipe axis (the loss
    # broadcast); a true vjp would therefore deliver the SUM of all ranks'
    # upstream cotangents to the stashed gradients. The enclosing shard_map
    # splits a replicated output's cotangent 1/n_pipe per rank, so
    # short-circuiting with the raw per-rank ct would shrink every grad by
    # n_pipe. Emulate the psum transpose for the grads the machinery reads
    # per-rank (stage shards; stage-0's dx) — but NOT for d_tail, whose
    # replicated in_spec the machinery itself sums over the pipe axis.
    ct_sum = jax.lax.psum(ct, pipe_axis)
    return (_tree_scale(d_stage, ct_sum), _tree_scale(d_tail, ct),
            (dx * ct_sum).astype(dx.dtype), None)


pipeline_train_1f1b.defvjp(_1f1b_fwd, _1f1b_bwd)
