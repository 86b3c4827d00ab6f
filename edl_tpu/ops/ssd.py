"""The Mamba-2 SSD chunked scan: two Pallas TPU kernels behind one custom VJP.

Per head (group ``g = head // (H/G)``) the recurrence is ``S_t = exp(dt_t A)
S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``. The scan cuts the
sequence into chunks of Q positions. Within a chunk, position q reads k <= q
through the masked product ``C B^T`` weighted by the decay between the two;
between chunks the state each chunk leaves is carried on. As XLA einsums the
chunk's (Q, Q) decay tiles and the per-chunk states of every head were
arrays in HBM, some 3 GB a layer a pass at the benchmark's shapes; here they
live in VMEM and a pass moves x, B, C, dt in and y out (and once, for the
backward, the state ENTERING each chunk).

`ssd_fwd`: grid (batch, group, chunk), the chunk axis sequential. A step
holds the chunk's x for the group's R heads as one lane-dense (Q, R x P)
block, B and C (Q, N), and dt and the log-decay ``dt A`` as (R, Q) rows
(positions on lanes: the only layout work outside the kernels is the
transpose of those two small arrays). It takes the inclusive cumulative
log-decay of the chunk, ``C B^T`` once for the group, and per head the masked
decay tile, its product with ``x dt``, what the entering state gives the
chunk's positions and the state the chunk leaves (scratch (R x P, N)).

`ssd_bwd`: the same grid walked from the last chunk to the first with the
state's cotangent carried in the scratch. It recomputes the chunk's tiles
from x, B, C, dt and the saved entering state and gives dx, dB and dC (summed
over the group's heads in the step that holds them), and per position and
head the cotangents of dt (its direct part) and of the log-decay, and per
group the sum of ``dy x`` for D (summed over batch and P outside). The
cotangent of the cumulative log-decay at a position is what its row of the
output and its column of the input's cotangent carry: ``dy_q . y_q - dt_q
x_q . u_q`` with ``dx = dt u``; the chunk's last position adds ``<dS, S>`` of
the state the chunk leaves.

Arithmetic, as the einsums had it: the log-decay sums, every ``exp``, the
carried state and every accumulation are float32; the operands of the
products of C, B, x, dy and the states are bf16 on the MXU with float32
accumulation; the cumulative sums are float32 adds (a log-step scan of lane
rotations), the sums over a head's P float32 adds down the sublanes.

Which engine runs the kernels follows the platform the program is lowered
for (`flash_attention._pallas_call`): Mosaic on a TPU, where Q, N and R x P
must be multiples of the 128 lanes; the interpreter on the CPU, any shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops.flash_attention import _nt, _pallas_call

__all__ = ["ssd_scan"]

f32, bf16 = jnp.float32, jnp.bfloat16

#: a masked log-decay: exp() is exactly 0.0
_NEG = -1e30
#: lanes of a vreg: small per-head tables are padded to it before a transpose
_LANES = 128


def _tn(a, b):
    """a.T @ b, float32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=f32)


def _cumsum_lanes(a, reverse=False):
    """Inclusive cumulative sum along the lanes (positions) of (R, Q) rows
    by a log-step scan of rotations: float32 adds only. ``reverse`` sums
    from each position to the chunk's end."""
    Q = a.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    step = 1
    while step < Q:
        if reverse:
            moved, keep = pltpu.roll(a, Q - step, 1), lane < Q - step
        else:
            moved, keep = pltpu.roll(a, step, 1), lane >= step
        a = a + jnp.where(keep, moved, 0.0)
        step *= 2
    return a


def _columns(rows):
    """(M, Q) rows -> (Q, 128k) with column m the row m: per-position
    scalars as columns that broadcast along the lanes of a (Q, .) tile."""
    pad = (-rows.shape[0]) % _LANES
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.zeros((pad, rows.shape[1]), rows.dtype)], 0)
    return rows.T


def _chunk_tables(dt, a, N):
    """From a chunk's dt and log-decay rows (R, Q), a head a row: the
    inclusive cumulative log-decay ``cum`` as rows and as columns (Q, .);
    the decay from each position to the chunk's end, alone and times dt;
    ``exp(cum)``, what reaches a position of the state that entered; and the
    whole chunk's decay along N lanes."""
    R, Q = dt.shape
    cum = _cumsum_lanes(a)
    lane = jax.lax.broadcasted_iota(jnp.int32, cum.shape, 1)
    # the last lane by a reduction, whose result every lane holds: Mosaic
    # broadcasts a row down the sublanes or along the lanes, not both
    last = jnp.sum(jnp.where(lane == Q - 1, cum, 0.0), axis=1, keepdims=True)
    to_end = jnp.exp(last - cum)
    return (cum, _columns(cum), to_end, dt * to_end, jnp.exp(cum),
            jnp.broadcast_to(jnp.exp(last), (R, N)))


def _decay_t(cum, cum_cols, r):
    """Head r's decay tile, TRANSPOSED: [k, q] is exp of the log-decay over
    (k, q] where position q reads k <= q, and 0 elsewhere."""
    Q = cum.shape[1]
    seen = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    return jnp.exp(jnp.where(seen, cum[r:r + 1] - cum_cols[:, r:r + 1], _NEG))


# Both kernels work on TRANSPOSED tiles: a head's x, dy, y and dx as (P, Q),
# features on sublanes and positions on lanes. A head is then 64 whole rows of
# a (R x P, Q) array (no slice starts inside a vreg, no lane of a P = 64 tile
# is empty), every per-position scalar (dt, the decays) is a row that
# broadcasts down the sublanes as it arrives, and the sums over a head's P
# run down the sublanes on the VPU and come out as the rows they are stored
# as. The price is one transpose of each (Q, R x P) block a kernel reads or
# writes, on the XLU. Against tiles with positions on sublanes and two heads
# a 128-lane slab, a call at the benchmark's shapes took 3.06 ms against 3.65
# forward and 3.22 against 4.71 backward (chip runs, PR 28: PERF.md).


def _fwd_kernel(d_ref, x_ref, b_ref, c_ref, dt_ref, a_ref,
                y_ref, s_ref, state, y_t, to_state_t, *, R, P):
    g, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    Q, N = b_ref.shape
    dt = dt_ref[...]
    cum, cum_cols, _, dt_to_end, reach, leaves = _chunk_tables(
        dt, a_ref[...], N)
    Bb, Cb = b_ref[...].astype(bf16), c_ref[...].astype(bf16)
    cb_t = _nt(Bb, Cb)                                 # (Q, Q) [k, q], group's
    entering = state[...]                              # (R x P, N) float32
    s_ref[...] = entering
    x_t = x_ref[...].astype(f32).T                     # (R x P, Q)
    from_state = _nt(entering.astype(bf16), Cb)        # (R x P, Q)
    for r in range(R):
        rows = slice(r * P, (r + 1) * P)
        x = x_t[rows]                                  # (P, Q)
        v_t = (cb_t * _decay_t(cum, cum_cols, r)).astype(bf16)
        y_t[rows, :] = (
            jnp.dot((x * dt[r:r + 1]).astype(bf16), v_t,
                    preferred_element_type=f32)
            + reach[r:r + 1] * from_state[rows]
            + d_ref[g * R + r] * x)
        to_state_t[rows, :] = (x * dt_to_end[r:r + 1]).astype(bf16)
    y_ref[...] = y_t[...].T
    adds = jnp.dot(to_state_t[...], Bb, preferred_element_type=f32)
    for r in range(R):
        rows = slice(r * P, (r + 1) * P)
        state[rows, :] = leaves[r:r + 1] * entering[rows] + adds[rows]


def _bwd_kernel(d_ref, x_ref, b_ref, c_ref, dt_ref, a_ref, s_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref,
                dstate, s_next, d_acc, dx_t, dy_reach_t, to_state_t, d_cum,
                *, R, P):
    g, c = pl.program_id(1), pl.program_id(2)   # c counts from the last chunk

    @pl.when(c == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)
        s_next[...] = jnp.zeros_like(s_next)
        d_acc[...] = jnp.zeros_like(d_acc)

    Q, N = b_ref.shape
    dt = dt_ref[...]
    cum, cum_cols, to_end, dt_to_end, reach, leaves = _chunk_tables(
        dt, a_ref[...], N)
    Bb, Cb = b_ref[...].astype(bf16), c_ref[...].astype(bf16)
    cb_t = _nt(Bb, Cb)
    entering = s_ref[...]                              # (R x P, N) float32
    d_leaving = dstate[...]          # cotangent of the state the chunk leaves
    d_leaving_b = d_leaving.astype(bf16)
    # the last position's log-decay sum also scales the state the chunk
    # leaves, which is the state entering the chunk after it: <dS, S>
    d_last = (d_leaving_b.astype(f32) * s_next[...]).reshape(R, P, N) \
        .sum(axis=1).sum(axis=1, keepdims=True)        # (R, 1)
    s_next[...] = entering
    entering_b = entering.astype(bf16)
    x_all, dy_all = x_ref[...].astype(f32), dy_ref[...].astype(f32)
    d_acc[...] += dy_all * x_all
    x_t, dy_t = x_all.T, dy_all.T                      # (R x P, Q)
    from_state = _nt(entering_b, Cb)                   # (R x P, Q)
    from_d_state = _nt(d_leaving_b, Bb)                # (R x P, Q)
    d_cb_t = jnp.zeros((Q, Q), f32)
    for r in range(R):
        rows = slice(r * P, (r + 1) * P)
        x, dy = x_t[rows], dy_t[rows]                  # (P, Q)
        decay = _decay_t(cum, cum_cols, r)
        v_t = (cb_t * decay).astype(bf16)
        xdt = (x * dt[r:r + 1]).astype(bf16)
        dy_b = dy.astype(bf16)
        to_state = (x * dt_to_end[r:r + 1]).astype(bf16)
        y = jnp.dot(xdt, v_t, preferred_element_type=f32) \
            + reach[r:r + 1] * from_state[rows]        # the output less the skip
        u_within = _nt(dy_b, v_t)
        u = u_within + to_end[r:r + 1] * from_d_state[rows]
        dx_t[rows, :] = dt[r:r + 1] * u + d_ref[g * R + r] * dy
        ddt_ref[r:r + 1, :] = jnp.sum(x * u, axis=0, keepdims=True)
        # a position's cumulative log-decay scales its row of the output and,
        # inversely, its column of the input's cotangent. The two nearly
        # cancel over a chunk, so each product pairs the operands exactly as
        # the MXU saw them
        d_cum[r:r + 1, :] = jnp.sum(
            dy_b.astype(f32) * y - xdt.astype(f32) * u_within
            - to_state.astype(f32) * from_d_state[rows], axis=0, keepdims=True)
        d_cb_t += _tn(xdt, dy_b) * decay
        dy_reach_t[rows, :] = (dy * reach[r:r + 1]).astype(bf16)
        to_state_t[rows, :] = to_state
    dx_ref[...] = dx_t[...].T.astype(dx_ref.dtype)
    d_cb_t = d_cb_t.astype(bf16)
    dc_ref[...] = (_tn(dy_reach_t[...], entering_b) + _tn(d_cb_t, Bb)
                   ).astype(dc_ref.dtype)
    db_ref[...] = (_tn(to_state_t[...], d_leaving_b)
                   + jnp.dot(d_cb_t, Cb, preferred_element_type=f32)
                   ).astype(db_ref.dtype)
    adds = jnp.dot(dy_reach_t[...], Cb, preferred_element_type=f32)
    for r in range(R):
        rows = slice(r * P, (r + 1) * P)
        dstate[rows, :] = leaves[r:r + 1] * d_leaving[rows] + adds[rows]
    da_ref[...] = _cumsum_lanes(d_cum[...], reverse=True) + d_last

    @pl.when(c == pl.num_programs(2) - 1)
    def _emit():
        dd_ref[...] = d_acc[...].sum(axis=0, keepdims=True)


def _layout(x, Bm, dt_rows, G, Q, *, reverse):
    """Heads a group R, head width P, state N and chunks nc, and the block
    specs over the mixer's own layouts: x and y (B, S, H x P), B and C
    (B, S, G x N), dt rows (B, H, S), states (B, nc, G, R x P, N).
    ``reverse`` walks the chunks from the last."""
    H = dt_rows.shape[1]
    R, P, N, nc = H // G, x.shape[2] // H, Bm.shape[2] // G, x.shape[1] // Q
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    wide = pl.BlockSpec((None, Q, R * P), lambda b, g, c: (b, at(c), g))
    group = pl.BlockSpec((None, Q, N), lambda b, g, c: (b, at(c), g))
    rows = pl.BlockSpec((None, R, Q), lambda b, g, c: (b, g, at(c)))
    states = pl.BlockSpec((None, None, None, R * P, N),
                          lambda b, g, c: (b, at(c), g, 0, 0))
    return R, P, N, nc, wide, group, rows, states


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2**20)


def _forward(x, Bm, Cm, dt_rows, a_rows, D, *, G, Q):
    Bz, S, HP = x.shape
    R, P, N, nc, wide, group, rows, states = _layout(
        x, Bm, dt_rows, G, Q, reverse=False)
    return _pallas_call(
        functools.partial(_fwd_kernel, R=R, P=P), "ssd_fwd",
        grid=(Bz, G, nc),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  wide, group, group, rows, rows],
        out_specs=[wide, states],
        out_shape=[jax.ShapeDtypeStruct((Bz, S, HP), f32),
                   jax.ShapeDtypeStruct((Bz, nc, G, R * P, N), f32)],
        scratch_shapes=[pltpu.VMEM((R * P, N), f32),   # the state, carried on
                        pltpu.VMEM((R * P, Q), f32),   # y, transposed
                        pltpu.VMEM((R * P, Q), bf16)],  # x dt to_end, transposed
        compiler_params=_PARAMS,
    )(D, x, Bm, Cm, dt_rows, a_rows)


def _backward(x, Bm, Cm, dt_rows, a_rows, D, entering, dy, *, G, Q):
    Bz = x.shape[0]
    R, P, N, nc, wide, group, rows, states = _layout(
        x, Bm, dt_rows, G, Q, reverse=True)
    per_head = jax.ShapeDtypeStruct(dt_rows.shape, f32)
    return _pallas_call(
        functools.partial(_bwd_kernel, R=R, P=P), "ssd_bwd",
        grid=(Bz, G, nc),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  wide, group, group, rows, rows, states, wide],
        out_specs=[wide, group, group, rows, rows,
                   pl.BlockSpec((None, None, 1, R * P),
                                lambda b, g, c: (b, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(Bm.shape, Bm.dtype),
                   jax.ShapeDtypeStruct(Cm.shape, Cm.dtype),
                   per_head, per_head,
                   jax.ShapeDtypeStruct((Bz, G, 1, R * P), f32)],
        scratch_shapes=[pltpu.VMEM((R * P, N), f32),   # dS, carried back
                        pltpu.VMEM((R * P, N), f32),   # S entering chunk c + 1
                        pltpu.VMEM((Q, R * P), f32),   # dy x, summed for dD
                        pltpu.VMEM((R * P, Q), f32),   # dx, transposed
                        pltpu.VMEM((R * P, Q), bf16),  # dy exp(cum), transposed
                        pltpu.VMEM((R * P, Q), bf16),  # x dt to_end, transposed
                        pltpu.VMEM((R, Q), f32)],      # d cum, a head a row
        compiler_params=_PARAMS,
    )(D, x, Bm, Cm, dt_rows, a_rows, entering, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, Bm, Cm, dt_rows, a_rows, D, G, Q):
    return _forward(x, Bm, Cm, dt_rows, a_rows, D, G=G, Q=Q)[0]


def _scan_fwd(x, Bm, Cm, dt_rows, a_rows, D, G, Q):
    y, entering = _forward(x, Bm, Cm, dt_rows, a_rows, D, G=G, Q=Q)
    return y, (x, Bm, Cm, dt_rows, a_rows, D, entering)


def _scan_bwd(G, Q, res, dy):
    x, Bm, Cm, dt_rows, a_rows, D, entering = res
    dx, dB, dC, d_dt, d_a, d_D = _backward(
        x, Bm, Cm, dt_rows, a_rows, D, entering, dy, G=G, Q=Q)
    H = dt_rows.shape[1]
    return dx, dB, dC, d_dt, d_a, d_D.reshape(-1, H, x.shape[2] // H).sum((0, 2))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk: int):
    """``y_t = S_t C_t + D x_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    (x) B_t`` from a zero state. x (B, S, H, P); dt (B, S, H) > 0, A (H,) < 0
    and D (H,) float32; Bm and Cm (B, S, G, N), head h on group ``h // (H/G)``.
    Returns y (B, S, H, P) float32; differentiable in all six.

    A length that is no multiple of ``chunk`` is padded with dt = 0 (no decay,
    no input) and the rows cut off again."""
    Bz, S, H, P = x.shape
    G = Bm.shape[2]
    pad = (-S) % chunk
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (a.ndim - 2)) for a in (x, dt, Bm, Cm))
    dt_rows = dt.astype(f32).swapaxes(1, 2)            # (B, H, S)
    y = _scan(x.reshape(Bz, S + pad, H * P), Bm.reshape(Bz, S + pad, -1),
              Cm.reshape(Bz, S + pad, -1), dt_rows,
              dt_rows * A.astype(f32)[:, None], D.astype(f32), G, chunk)
    return y.reshape(Bz, S + pad, H, P)[:, :S]
