"""The plain reference of the hybrid decoder's sparse-attention layers
(`edl_tpu/models/hybrid.py`, kinds ``S`` and ``E`` with a softmax router and
gated experts): float32, every matmul at ``highest`` precision, one sequence
at a time, explicit attention scores under the selection's mask, `jax.lax.
top_k` for the selection, no kernel, no sort of assignments and no grouped
product; loss and, by `jax.grad` of it, gradients. It follows the published
description of the family (Qwen3-MoE's attention and experts under
DeepSeek-V3.2's lightning indexer) layer by layer and imports nothing from
the program; it reads the program's parameter tree (``params["layers"]
["00S"]`` ...) and any object with the configuration's sizes as attributes
(``cfg``).

Every layer is ``x = x + mixer(rmsnorm(x; w, eps))``:

- ``S``: ``q = rope(rmsnorm_head(h W_q))``, ``k = rope(rmsnorm_head(h W_k))``,
  ``v = h W_v``; query head j reads K/V head ``j // (Hq / Hkv)``; softmax of
  ``q k^T / sqrt(head_dim)`` over the SELECTED keys of the query alone, a
  block of 256 query rows after another so that 32 x 16,384 x 16,384 never
  exists whole. The selection ``S_t`` is the ``min(t + 1, topk)`` keys ``s <=
  t`` of largest indexer score ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s]) Di^-0.5 Hi^-0.5`` with ``qI = rope(h W_qI)``, ``kI = rope(LayerNorm(h
  W_kI))``, ``w = h W_w``, from the layer's normed input with the gradient
  stopped. **There are two ways to get it:** the reference's own indexer
  (``selection=None``), or a selection handed to it (``{layer: (B, S, S)}``,
  non-zero where the query attends to the key): with random weights
  attention is near uniform, so two selections that differ in a share f of
  their keys give outputs about ``sqrt(2 f)`` apart, and the bf16 rounding of
  the program's indexer moves about that many keys across the threshold. So
  the program's gradient is held to the reference GIVEN the program's
  selection, and the selection is held to the reference's indexer on its own
  (`selection_distances`).
- ``E``: the softmax router over all published experts in float32, top k,
  renormalised, scaled; then a loop over the experts HELD with a dense mask,
  each gated: ``(silu(h G) * (h U)) D`` (``w_up`` holds ``[G | U]``);
  assignments to experts held elsewhere are left out, as in the program; a
  shared expert only where the layer has one.

This file is kept twice, byte for byte: `benchmarks/reference_sparse.py` is
the yardstick (no later PR edits it), `edl_tpu/models/sparse_reference.py`
is the repo's own copy, which the CPU tests compare every layer, the loss and
the gradients with.
"""

from __future__ import annotations

import functools
import math

#: Every reading below is from the chip at the cell's sizes (1 x 16,384
#: tokens, published widths), PR 32: nine seeds of the first step alone (a
#: scratch script that runs the program's gradient, the program's selection
#: and this reference, programs compiled once: 3000000019, 17, 4100000007,
#: 123456789, 2500000033, 99, 2147483659, 4294967311, and `run.py`'s own run of
#: seed 2200000051), then `run.py`'s own runs of `train_keyevl2_1chip`, sound
#: and with a fault put in by `control_sparse.py` (PERF.md, Findings, PR 32).
#: Each limit is at least twice the worst sound reading and well under the
#: nearest control's. The sound readings hardly move with the seed because
#: the routing starts near uniform (the configuration's ``assumed.
#: initialiser``): with every token on the same eight experts a layer's
#: router and held experts had gradients next to nothing, and the leaf
#: furthest off read 0.15 to 1.65 by the seed.

#: |first step's loss - reference cross-entropy| in nats (16,384 tokens,
#: vocabulary 18,992): the limit of the harness's accepted cells
#: (`reference.LOSS_TOL`, `reference_hybrid.LOSS_TOL`). It ties the timed step
#: to the reference's data and weights and nothing finer: bf16 reads 3.8e-6
#: to 1.3e-4, over a hundred times of room, and the four controls 3.0e-5 to
#: 3.6e-4: the loss of 16,384 tokens hardly moves with any of them. What is
#: finer is below.
LOSS_TOL = 0.02

#: The timed step itself against the reference GIVEN THE PROGRAM'S SELECTION,
#: by what its first step left in the worker's state (`first_step_
#: distances`); every distance is ||got - want|| / ||want||, 0 agreement, 1
#: what zeros read. The step's gradient (Adam's first moment after one step,
#: over 1 - b1) against `jax.grad` of the reference: over all parameters
#: together, and over the leaves of one NAME in all layers together, the
#: name furthest off (`gradient_by_name`: a single leaf is the wrong unit
#: where a router may send a layer's held experts next to nothing). bf16
#: with float32 accumulation, what the configuration states, reads 0.0102 to
#: 0.0103 together and 0.060 to 0.066 at the worst name, the routers (0.067
#: to 0.075 at the worst single leaf, a router or a head norm). The routed
#: experts left out read 0.0986 together and 1 on `router`, `w_up` and
#: `w_down` (0.48 on `norm`); float8 (e4m3) operands, the nearest precision
#: below, 0.208 together and 1.00 to 1.02 on every name the attention and the
#: experts hold (their cotangents underflow; `head` 0.027). Another
#: selection handed to both sides reads sound: 0.0071 and 0.0072 (the latest
#: 2,048 keys; every causal key), as it must.
GRAD_TOL = 0.03
GRAD_NAME_TOL = 0.15

#: The parameters' change in the first step against Adam's first step on the
#: reference's gradient. A state left unchanged reads 1. Adam's first step is
#: the rate times the gradient's SIGN, so this distance is twice the root of
#: the share of elements whose sign differs: bf16 reads 0.168 to 0.173 (1.17
#: to 1.23% of the signs, the elements nearest zero; no fault of the step).
#: The limit lies between the bf16 reading and 1, with the more room above
#: the reading: the routed experts left out read 0.853 (69% of the signs),
#: float8 1.206 (45%).
UPDATE_TOL = 0.5

#: The same change against Adam's first step on the step's OWN gradient: the
#: optimizer's arithmetic alone (1e-5 to 2e-5 together); a rate or a moment
#: that is off reads its relative error.
OPTIMIZER_TOL = 0.01

#: The program's selection against the reference's own indexer ON THE SAME
#: LAYER INPUT, over the 17 query rows `Model.selection_stats` samples in
#: every S layer (`selection_distances`). ``differ``: the share of a row's
#: selected keys that the reference's selection does not hold. ``band``: over
#: those keys and the ones the reference holds instead, the distance of the
#: key's REFERENCE score from the reference's threshold for the row (its
#: k-th largest score), in standard deviations of the row's causal scores:
#: what rounding can move across the threshold, no more. bf16 operands
#: against float32 read a worst row of 0.0083 to 0.0117 differing and a
#: worst key 0.030 to 0.045 deviations off the threshold (seven more seeds
#: at the second hand-in: 0.0088 to 0.0132 and 0.034 to 0.046); float8
#: operands 0.0508 and 0.323; the latest 2,048 keys for the top 2,048 read
#: 0.881 and 6.15, every causal key 0.875 and 6.96 (and 14,336 queries a
#: layer with another count than min(t + 1, 2048)). Each limit is near the
#: geometric mean of the worst sound reading and float8's.
SELECT_DIFFER_TOL = 0.025
SELECT_BAND_TOL = 0.1

#: optax.adam's defaults, which `TrainerConfig(optimizer="adam")` takes
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8

#: rows of queries a block of materialised attention scores holds
QUERY_BLOCK = 256


def _hi():
    import jax

    return jax.lax.Precision.HIGHEST


def rmsnorm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layernorm(x, w, b, eps):
    import jax
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def rope(x, positions, theta):
    """Rotary embedding over the whole last axis of x (S, ..., d) at the
    given positions (S,): element i < d/2 pairs with i + d/2 (rotate-half),
    both turned by ``position x theta^(-2i/d)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * jnp.cos(angle) - second * jnp.sin(angle),
                            second * jnp.cos(angle) + first * jnp.sin(angle)],
                           axis=-1)


def indexer_scores(cfg, p, h, rows):
    """``I[t, s]`` for the queries at positions ``rows`` (R,) against every
    key, from the layer's normed input h (S, D): (R, S) float32; keys after
    the query read -inf."""
    import jax
    import jax.numpy as jnp

    S = h.shape[0]
    Hi, Di = cfg.indexer_heads, cfg.indexer_head_dim
    at = jnp.arange(S)
    qI = jnp.einsum("rd,de->re", h[rows], p["ix_wq"], precision=_hi())
    qI = rope(qI.reshape(-1, Hi, Di), rows, cfg.rope_theta)
    kI = layernorm(jnp.einsum("sd,de->se", h, p["ix_wk"], precision=_hi()),
                   p["ix_norm"], p["ix_norm_b"], cfg.norm_eps)
    kI = rope(kI, at, cfg.rope_theta)
    w = jnp.einsum("rd,dh->rh", h[rows], p["ix_ww"], precision=_hi())
    dots = jnp.einsum("rhd,sd->rhs", qI, kI, precision=_hi())
    scores = jnp.einsum("rh,rhs->rs", w, jax.nn.relu(dots), precision=_hi()) \
        * Di ** -0.5 * Hi ** -0.5
    return jnp.where(at[None, :] <= rows[:, None], scores, -jnp.inf)


def select(cfg, scores, rows):
    """The ``min(t + 1, topk)`` keys of largest score of each row (R, S)
    bool, by `jax.lax.top_k` (ties to the earlier key), and each row's
    threshold: the score of the last key it keeps."""
    import jax
    import jax.numpy as jnp

    S = scores.shape[1]
    k = min(cfg.indexer_topk, S)
    top, index = jax.lax.top_k(scores, k)
    kept = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], index].set(True)
    kept &= jnp.arange(S)[None, :] <= rows[:, None]
    last = jnp.minimum(rows + 1, k) - 1
    return kept, jnp.take_along_axis(top, last[:, None], axis=1)[:, 0]


def own_selection(cfg, p, h):
    """The reference's own selection for every query of one sequence, (S, S)
    bool, a block of query rows after another."""
    import jax
    import jax.numpy as jnp

    S = h.shape[0]
    n = S // QUERY_BLOCK if S % QUERY_BLOCK == 0 else 1
    rows = jnp.arange(S).reshape(n, S // n)
    return jax.lax.map(
        lambda r: select(cfg, indexer_scores(cfg, p, h, r), r)[0],
        rows).reshape(S, S)


def attention_mixer(cfg, p, h, selection=None):
    """h (S, D) float32, already normed -> (S, D). ``selection`` (S, S),
    non-zero where the query (row) attends to the key (column); None: the
    reference's own indexer on ``stop_gradient(h)``."""
    import jax
    import jax.numpy as jnp

    S = h.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if selection is None:
        selection = own_selection(cfg, p, jax.lax.stop_gradient(h))
    at = jnp.arange(S)
    q = jnp.einsum("sd,de->se", h, p["wq"], precision=_hi()).reshape(S, Hq, Dh)
    k = jnp.einsum("sd,de->se", h, p["wk"], precision=_hi()).reshape(S, Hkv, Dh)
    v = jnp.einsum("sd,de->se", h, p["wv"], precision=_hi()).reshape(S, Hkv, Dh)
    q = rope(rmsnorm(q, p["q_norm"], cfg.norm_eps), at, cfg.rope_theta)
    k = rope(rmsnorm(k, p["k_norm"], cfg.norm_eps), at, cfg.rope_theta)
    # query head j reads K/V head j // (Hq / Hkv)
    q = q.reshape(S, Hkv, Hq // Hkv, Dh)

    @jax.checkpoint  # a block's scores are made again for its gradient
    def block(qb, start, chosen):
        s = jnp.einsum("sgre,tge->grst", qb, k, precision=_hi()) \
            / math.sqrt(Dh)
        seen = (at[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]) \
            & (chosen != 0)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("grst,tge->sgre", w, v, precision=_hi())

    # one block of query rows after another (`lax.map`: one body, compiled
    # once); a sequence shorter than a block, or no whole number of them, is
    # one block
    n = S // QUERY_BLOCK if S % QUERY_BLOCK == 0 else 1
    a = jax.lax.map(lambda xs: block(*xs), (
        q.reshape(n, S // n, Hkv, Hq // Hkv, Dh),
        jnp.arange(n) * (S // n),
        selection.reshape(n, S // n, S))).reshape(S, Hq * Dh)
    return jnp.einsum("se,ed->sd", a, p["wo"], precision=_hi())


def route(cfg, p, h):
    """Chosen experts (S, k) and their weights (S, k): softmax over all
    published experts, the top k of the probabilities, renormalised over the
    chosen and scaled (by 1 here)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.softmax(jnp.einsum("sd,ed->se", h, p["router"],
                                  precision=_hi()), axis=-1)
    picked, chosen = jax.lax.top_k(s, cfg.top_k)
    return chosen, picked / picked.sum(-1, keepdims=True) * cfg.routed_scale


def expert(h, gate_up, down):
    """``(silu(h G) * (h U)) D`` with ``gate_up = [G | U]``."""
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(jnp.einsum("sd,df->sf", h, gate_up, precision=_hi()),
                         2, axis=-1)
    return jnp.einsum("sf,fd->sd", jax.nn.silu(gate) * up, down,
                      precision=_hi())


def moe_mixer(cfg, p, h, experts_held=None):
    """The share of the layer that the experts ``experts_held = (first,
    count)`` give (``p["w_up"]`` and ``p["w_down"]`` hold those experts and
    no others). The family has no shared expert."""
    import jax
    import jax.numpy as jnp

    first, count = experts_held or (cfg.experts_first, cfg.experts_count)
    chosen, weights = route(cfg, p, h)

    @jax.checkpoint  # an expert's activations are made again for its gradient
    def one(out, e):  # expert `index`, for every token, masked by its weight
        index, gate_up, down = e
        w_e = jnp.sum(jnp.where(chosen == index, weights, 0.0), axis=1)
        return out + w_e[:, None] * expert(h, gate_up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        first + jnp.arange(count), p["w_up"][:count], p["w_down"][:count]))
    return out


def reference_logits(cfg, params, tokens, selection=None):
    """tokens (S,) -> logits (S, V); row t is the distribution of token
    t + 1. ``selection``: ``{layer: (S, S)}`` for the S layers, or None."""
    import jax
    import jax.numpy as jnp

    x = params["embed"][tokens]
    for name in sorted(params["layers"]):
        def layer(x, p, *given, kind=name[-1]):
            h = rmsnorm(x, p["norm"], cfg.norm_eps)
            if kind == "S":
                return x + attention_mixer(cfg, p, h, *given)
            return x + moe_mixer(cfg, p, h)

        given = () if selection is None or name[-1] != "S" \
            else (selection[name],)
        # the gradient keeps a layer's input and makes the rest again
        x = jax.checkpoint(layer)(x, params["layers"][name], *given)
    return jnp.einsum("sd,dv->sv", rmsnorm(x, params["norm_f"], cfg.norm_eps),
                      params["head"], precision=_hi())


def sequence_loss(cfg, params, tokens, targets, selection=None):
    """Mean next-token cross-entropy of one sequence; differentiable."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(
            reference_logits(cfg, params, tokens, selection), -1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=1))


def _row(selection, i):
    return None if selection is None else {
        layer: picked[i] for layer, picked in selection.items()}


def loss_fn(cfg, params, batch, selection=None):
    """Mean over the batch's sequences, traced; `jax.grad` of it gives the
    reference's gradients (the CPU tests). ``selection``: ``{layer: (B, S,
    S)}`` or None."""
    import jax.numpy as jnp

    return jnp.mean(jnp.stack([
        sequence_loss(cfg, params, t, y, _row(selection, i))
        for i, (t, y) in enumerate(zip(batch["tokens"], batch["targets"]))]))


def reference_grads(cfg, params, batch, selection=None):
    """The gradient of the batch's mean loss, one jitted sequence at a
    time; the layers, the attention's blocks and the experts' loop are
    `jax.checkpoint`s, which changes where a value is kept and none of the
    arithmetic."""
    import jax

    one = jax.jit(jax.grad(
        lambda p, t, y, sel: sequence_loss(cfg, p, t, y, sel)))
    total = None
    for i, (t, y) in enumerate(zip(batch["tokens"], batch["targets"])):
        got = one(params, t, y, _row(selection, i))
        total = got if total is None else jax.tree_util.tree_map(
            lambda a, b: a + b, total, got)
    n = len(batch["tokens"])
    return jax.tree_util.tree_map(lambda a: a / n, total)


def _loss_and_grads(cfg):
    import jax

    return jax.jit(jax.value_and_grad(
        lambda p, t, y, sel: sequence_loss(cfg, p, t, y, sel)))


def loss_and_grads_program(cfg, params, tokens, targets, selection=None):
    """`reference_loss_and_grads`' program for ONE sequence, compiled ahead
    from arguments like its own or their `jax.ShapeDtypeStruct`s (tokens
    and targets (S,), ``selection`` ``{layer: (S, S)}`` or None): the
    benchmark's runner has it compiled on a thread of its own while it
    makes the program's selection, since compiling it is most of what the
    reference costs a run."""
    return _loss_and_grads(cfg).lower(params, tokens, targets,
                                      selection).compile()


def reference_loss_and_grads(cfg, params, batch, selection=None, program=None):
    """`reference_loss` and `reference_grads` from one program a sequence
    (the benchmark's runner: one compilation and one forward pass less).
    ``program``: `loss_and_grads_program`'s for these shapes, else it is
    compiled here."""
    import jax

    one = _loss_and_grads(cfg)
    if program is not None:  # a compiled program takes its operands placed
        def one(p, *row):
            return program(p, *jax.device_put(
                row, program.input_shardings[0][1:]))
    loss, total = 0.0, None
    for i, (t, y) in enumerate(zip(batch["tokens"], batch["targets"])):
        value, got = one(params, t, y, _row(selection, i))
        loss += float(value)
        total = got if total is None else jax.tree_util.tree_map(
            lambda a, b: a + b, total, got)
    n = len(batch["tokens"])
    return loss / n, jax.tree_util.tree_map(lambda a: a / n, total)


def reference_loss(cfg, params, batch, selection=None) -> float:
    """The same number for the benchmark: one jitted sequence at a time."""
    import jax
    import jax.numpy as jnp

    one = jax.jit(lambda p, t, y, sel: sequence_loss(cfg, p, t, y, sel))
    rows = [one(params, t, y, _row(selection, i)) for i, (t, y) in
            enumerate(zip(batch["tokens"], batch["targets"]))]
    return float(jnp.mean(jnp.stack(rows)))


@functools.lru_cache(maxsize=None)
def _distances_program(cfg):
    """`selection_distances`' program for one configuration (hashable),
    jitted once: the runner asks it of every S layer."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(p, x, rows, picked):
        with jax.default_matmul_precision("highest"):
            h = rmsnorm(x.astype(jnp.float32), p["norm"], cfg.norm_eps)
            scores = indexer_scores(cfg, p, h, rows)
        kept, threshold = select(cfg, scores, rows)
        seen = jnp.arange(x.shape[0])[None, :] <= rows[:, None]
        picked = picked != 0
        n = jnp.sum(seen, axis=1)
        mean = jnp.sum(jnp.where(seen, scores, 0.0), axis=1) / n
        var = jnp.sum(jnp.where(seen, (scores - mean[:, None]) ** 2, 0.0),
                      axis=1) / n
        off = jnp.abs(scores - threshold[:, None]) \
            / jnp.sqrt(var + 1e-30)[:, None]
        return {
            "count": jnp.sum(picked, axis=1),
            "future": jnp.sum(picked & ~seen, axis=1),
            "differ": jnp.sum(picked & ~kept, axis=1)
            / jnp.maximum(jnp.sum(picked, axis=1), 1),
            "band": jnp.max(jnp.where((picked ^ kept) & seen, off, 0.0),
                            axis=1)}

    return run


def selection_distances(cfg, p, x, rows, picked):
    """A program's selection against the reference's own indexer on the same
    layer input. x (S, D): the layer's input as the program saw it; ``rows``
    (R,): query positions; ``picked`` (R, S): non-zero where the program's
    query attends to the key; p: the layer's parameters. Returns host arrays
    over the rows: ``count`` (keys picked), ``future`` (picked keys after
    the query), ``differ`` (the share of the row's picked keys that the
    reference's selection does not hold) and ``band`` (the largest distance,
    over the keys either selection holds and the other does not, of the
    key's reference score from the reference's threshold for the row, in
    standard deviations of the row's causal scores; 0 where none differs)."""
    import jax.numpy as jnp
    import numpy as np

    return {k: np.asarray(v) for k, v in _distances_program(cfg)(
        p, jnp.asarray(x), jnp.asarray(rows, jnp.int32),
        jnp.asarray(picked)).items()}


def adam_first_step(grad, learning_rate):
    """What Adam adds to a parameter in its first step, plainly: the moments
    start at zero and are corrected for it, so ``m = g`` and ``v = g^2``."""
    m = (1 - ADAM_B1) * grad / (1 - ADAM_B1 ** 1)
    v = (1 - ADAM_B2) * grad * grad / (1 - ADAM_B2 ** 1)
    return -learning_rate * m / (v ** 0.5 + ADAM_EPS)


@functools.lru_cache(maxsize=None)
def _leaf_sums_program(learning_rate):
    """`first_step_distances`' sums over one leaf, jitted once a shape: the
    squared distance and the squared norm of each comparison and the count
    of flipped signs, in float32 on whatever device holds the operands. (In
    numpy on the host the 659 M parameters of the cell took 100 s a run, my
    chip run, PR 32; the arithmetic is the same.)"""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(p0, p1, m, want):
        p0, p1, m, want = (a.astype(jnp.float32) for a in (p0, p1, m, want))
        grad, moved = m / (1 - ADAM_B1), p1 - p0
        want_moved = adam_first_step(want, learning_rate)
        own_moved = adam_first_step(grad, learning_rate)

        def pair(got, to):
            return jnp.sum((got - to) ** 2), jnp.sum(to ** 2)

        return {"gradient": pair(grad, want),
                "update": pair(moved, want_moved),
                "optimizer": pair(moved, own_moved),
                "flipped": jnp.sum(jnp.sign(moved) != jnp.sign(want_moved))}

    return run


def first_step_distances(before, after, first_moment, want_grads,
                         learning_rate):
    """The first optimizer step of the program against the reference, from
    the parameters ``before`` and ``after`` it, Adam's ``first_moment``
    after it and the reference's gradient ``want_grads`` (four trees of one
    structure, each on the host or on a device: a leaf's sums are made by
    one jitted program where its operands lie). Returns ``{name: (together,
    by_leaf)}``, each distance ``||got - want|| / ||want||``:

    - ``gradient``: the step's gradient, ``first_moment / (1 - b1)``,
      against the reference's;
    - ``gradient_by_name``: the same over the leaves of one NAME in all
      layers together (every ``router``, every ``wq``, ...), where
      ``together`` is the name furthest off: a layer whose router sends the
      held experts next to nothing has a gradient next to nothing there,
      which rounding alone moves by its own size, and it weighs in its
      name's distance by what it is;
    - ``update``: ``after - before`` against Adam's first step on the
      reference's gradient;
    - ``optimizer``: ``after - before`` against Adam's first step on the
      step's own gradient;
    - ``flipped``: no distance, the share of elements whose change has
      another sign than Adam's first step on the reference's gradient.

    A leaf whose ``want`` is all zeros (the indexer's leaves take no
    gradient under the LM loss) reads 0 where ``got`` is zeros too, else
    infinity."""
    import jax

    def ratio(d, n):
        return (d / n) ** 0.5 if n else (0.0 if d == 0 else float("inf"))

    sums = {name: [0.0, 0.0, {}] for name in ("gradient", "update",
                                              "optimizer")}
    named = {}
    flipped = elements = 0
    run = _leaf_sums_program(float(learning_rate))
    flat = [jax.tree_util.tree_leaves(t)
            for t in (after, first_moment, want_grads)]
    for (path, p0), p1, m, want in zip(
            jax.tree_util.tree_leaves_with_path(before), *flat):
        leaf = jax.tree_util.keystr(path)
        got = jax.device_get(run(p0, p1, m, want))
        for name in sums:
            d, n = (float(x) for x in got[name])
            sums[name][0] += d
            sums[name][1] += n
            sums[name][2][leaf] = ratio(d, n)
        pair = named.setdefault(str(getattr(path[-1], "key", path[-1])),
                                [0.0, 0.0])
        pair[0] += float(got["gradient"][0])
        pair[1] += float(got["gradient"][1])
        flipped += int(got["flipped"])
        elements += p0.size
    out = {name: ((d / n) ** 0.5 if n else float("inf"), by_leaf)
           for name, (d, n, by_leaf) in sums.items()}
    by_name = {name: ratio(d, n) for name, (d, n) in named.items()}
    out["gradient_by_name"] = (max(by_name.values()), by_name)
    out["flipped"] = (flipped / elements, {})
    return out
