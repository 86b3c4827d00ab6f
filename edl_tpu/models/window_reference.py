"""The plain reference of the hybrid decoder's sliding-window family
(`edl_tpu/models/hybrid.py`, a pattern of ``*`` and ``W`` attention layers
each followed by an ``E`` layer whose router reads the ATTENTION sublayer's
normed input, gated relu experts): float32, every matmul at ``highest``
precision, one sequence at a time, explicit attention scores under an
explicit mask, no kernel, no sort of assignments and no grouped product;
loss and, by `jax.grad` of it, gradients. It follows the published layer of
the family (SmallThinker: a router placed before attention, global layers
without positions, sliding-window layers with rotary ones, ReGLU experts)
and imports nothing from the program; it reads the program's parameter tree
(``params["layers"]["00*"]``, ``["01E"]``, ``["02W"]`` ...) and any object
with the configuration's sizes as attributes (``cfg``).

One published layer, input x (S, D), is two entries of the tree, an
attention sublayer (``*`` or ``W``, leaves ``norm, wq, wk, wv, wo``) and the
expert sublayer after it (``E``, leaves ``norm, router, w_up, w_down``)::

    n1 = rmsnorm(x; g1)
    r  = n1 W_r^T                         the router reads n1, float32
    q, k, v = n1 W_q, n1 W_k, n1 W_v
    W:  q, k = rope(q), rope(k)           rotate-half, whole head; *: none
    visible(t, s) = s <= t  and  (*  or  t - s < window)
    x1 = x + softmax(q k^T / sqrt(head_dim) over visible) v W_o
    n2 = rmsnorm(x1; g2)
    chosen = top k of r;  w = softmax(r[chosen])
    f_e(h) = (relu(h G_e) * (h U_e)) D_e          w_up holds [G | U]
    x2 = x1 + sum over e in chosen and held of w_e f_e(n2)

then a final RMS norm, an untied head and the mean next-token
cross-entropy, the head and the loss a chunk of tokens at a time so that
(S, V) logits never exist whole; attention a block of `QUERY_BLOCK` query
rows after another so that heads x S x S scores never do. With
``cfg.router_frozen`` the routing weights ``w`` pass no gradient, to the
router or through it (the configuration's departure: a fine-tune with the
routers frozen), so the routers' leaves take a zero gradient here as in the
program. Assignments to experts held elsewhere are left out, as in the
program (``experts_first``, ``experts_count``: the chip's share).

This file is kept twice, byte for byte: `benchmarks/reference_window.py` is
the yardstick (no later PR edits it), `edl_tpu/models/window_reference.py`
is the repo's own copy, which the CPU tests compare every layer, the loss and
the gradients with.
"""

from __future__ import annotations

import functools
import math

#: Every reading below is from the chip at the cell's sizes (1 x 16,384
#: tokens, published widths), PR 35: `run.py`'s own runs of
#: `train_smallthinker21b_1chip` on the final tree over 13 seeds, ten of
#: them over 2^31 (2147483659, 3500000011, 4100000023, 31337, 777000111,
#: 2900000033, twice each; 4294967311, 2500000033, 123456789, 3999999979,
#: 2200000087 traced, 4500000071 with an empty compile cache, 4700000017
#: under the limits as they stand), sound, and
#: with a fault put in by `control_window.py` (seed 2600000047; PERF.md,
#: Findings, PR 35). Each limit is at least twice the worst sound reading
#: and at most half the reading of the nearest control it is there to
#: catch. The sound readings hardly move with the seed because the routing
#: starts near uniform and stays there (the configuration's ``assumed.
#: initialiser``).

#: |first step's loss - reference cross-entropy| in nats (16,384 tokens,
#: vocabulary 37,984): the limit of the harness's accepted cells
#: (`reference.LOSS_TOL`, `reference_hybrid.LOSS_TOL`, `reference_sparse.
#: LOSS_TOL`). It ties the timed step to the reference's data and weights
#: and nothing finer: bf16 reads 3e-6 to 1.0e-4, two hundred times of room,
#: and the three controls 7.7e-5 to 4.0e-4: the loss of 16,384 tokens hardly
#: moves with any of them. What is finer is below.
LOSS_TOL = 0.02

#: The timed step itself against this reference, by what its first step
#: left in the worker's state (`first_step_distances`); every distance is
#: ||got - want|| / ||want||, 0 agreement, 1 what zeros read. The step's
#: gradient (Adam's first moment after one step, over 1 - b1) against
#: `jax.grad` of the reference: over all parameters together, and over the
#: leaves of one NAME in all layers together, the name furthest off. bf16
#: with float32 accumulation, what the configuration states, reads 0.00546
#: to 0.00555 together (the embedding's and the head's gradients are most
#: of the norm) and 0.0556 to 0.0589 at the worst name, the experts' `w_up`
#: (`w_down` 0.047 to 0.049, `norm` 0.046 to 0.048, attention's four 0.009
#: to 0.010): a token whose sixth and seventh logits tie to bf16's rounding
#: of the router's input goes to another expert on one side. The routers'
#: leaves read 0 on both sides (frozen) and so 0, not NaN. The routed
#: experts left out read 0.0525 together and 1 on `w_up` and `w_down` (0.80
#: on `norm`); float8 (e4m3) operands, the nearest precision below, 0.0650
#: together and 1.000 to 1.001 on every name the layers hold (`head` 0.027);
#: the window layers run causal 0.0126 together, UNDER the limit together
#: (three layers' attention is a small part of the whole gradient's norm),
#: and 0.369, 0.369, 0.341, 0.341 on `wk`, `wq`, `wo`, `wv`: that control
#: is the name limit's (and the pair count's: `window_pairs_are_exact`).
GRAD_TOL = 0.02
GRAD_NAME_TOL = 0.15

#: The parameters' change in the first step against Adam's first step on the
#: reference's gradient. A state left unchanged reads 1. Adam's first step is
#: the rate times the gradient's SIGN, so this distance is twice the root of
#: the share of elements whose sign differs: bf16 reads 0.130 to 0.137 (0.84
#: to 0.91% of the signs, the elements nearest zero; no fault of the step).
#: The routed experts left out read 0.799 (58% of the signs), float8 1.083
#: (38%); the window layers run causal 0.271, under the limit (2.3% of the
#: signs: not this limit's control).
UPDATE_TOL = 0.35

#: The same change against Adam's first step on the step's OWN gradient: the
#: optimizer's arithmetic alone (8e-5 to 9e-5 together, and 1.2e-4 to 1.4e-4
#: under every control: no control is a fault of the optimizer); a rate or a
#: moment that is off reads its relative error. The limit of the harness's
#: accepted cells.
OPTIMIZER_TOL = 0.01

#: optax.adam's defaults, which `TrainerConfig(optimizer="adam")` takes
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8

#: rows of queries a block of materialised attention scores holds
QUERY_BLOCK = 256

#: tokens a chunk of the head and the loss holds
LOSS_BLOCK = 2048


def _hi():
    import jax

    return jax.lax.Precision.HIGHEST


def rmsnorm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


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


def visible_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs of one sequence with ``s <= t`` and, under a
    window, ``t - s < window``: query t sees ``min(t + 1, window)`` keys."""
    w = seq_len if window is None else min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_mixer(cfg, p, h, windowed: bool):
    """h (S, D) float32, already normed -> (S, D). ``windowed``: a ``W``
    layer (rotary positions on q and k; a query sees its latest
    ``cfg.window`` keys, itself included); else a ``*`` layer (no positions,
    every key at or before the query)."""
    import jax
    import jax.numpy as jnp

    S = h.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    at = jnp.arange(S)
    q = jnp.einsum("sd,de->se", h, p["wq"], precision=_hi()).reshape(S, Hq, Dh)
    k = jnp.einsum("sd,de->se", h, p["wk"], precision=_hi()).reshape(S, Hkv, Dh)
    v = jnp.einsum("sd,de->se", h, p["wv"], precision=_hi()).reshape(S, Hkv, Dh)
    if windowed:
        q, k = rope(q, at, cfg.rope_theta), rope(k, at, cfg.rope_theta)
    # query head j reads K/V head j // (Hq / Hkv)
    q = q.reshape(S, Hkv, Hq // Hkv, Dh)

    @jax.checkpoint  # a block's scores are made again for its gradient
    def block(qb, start):
        s = jnp.einsum("sgre,tge->grst", qb, k, precision=_hi()) \
            / math.sqrt(Dh)
        t = (start + jnp.arange(qb.shape[0]))[:, None]
        seen = at[None, :] <= t
        if windowed:
            seen &= t - at[None, :] < cfg.window
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("grst,tge->sgre", w, v, precision=_hi())

    # one block of query rows after another (`lax.map`: one body, compiled
    # once); a sequence shorter than a block, or no whole number of them, is
    # one block
    n = S // QUERY_BLOCK if S % QUERY_BLOCK == 0 else 1
    a = jax.lax.map(lambda xs: block(*xs), (
        q.reshape(n, S // n, Hkv, Hq // Hkv, Dh),
        jnp.arange(n) * (S // n))).reshape(S, Hq * Dh)
    return jnp.einsum("se,ed->sd", a, p["wo"], precision=_hi())


def route(cfg, p, n1):
    """Chosen experts (S, k) and their weights (S, k) from the router's
    input n1 (S, D): the top k of the logits over all published experts, a
    softmax over the chosen logits (which is the softmax over all of them
    renormalised over the chosen). Frozen routers: the weights are constants
    of the step."""
    import jax
    import jax.numpy as jnp

    r = jnp.einsum("sd,ed->se", n1, p["router"], precision=_hi())
    picked, chosen = jax.lax.top_k(r, cfg.top_k)
    weights = jax.nn.softmax(picked, axis=-1) * cfg.routed_scale
    if cfg.router_frozen:
        weights = jax.lax.stop_gradient(weights)
    return chosen, weights


def expert(h, gate_up, down):
    """``(relu(h G) * (h U)) D`` with ``gate_up = [G | U]``."""
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(jnp.einsum("sd,df->sf", h, gate_up, precision=_hi()),
                         2, axis=-1)
    return jnp.einsum("sf,fd->sd", jax.nn.relu(gate) * up, down,
                      precision=_hi())


def moe_mixer(cfg, p, h, n1, experts_held=None):
    """The share of the layer that the experts ``experts_held = (first,
    count)`` give (``p["w_up"]`` and ``p["w_down"]`` hold those experts and
    no others) for the normed tokens h (S, D), routed by what the router
    makes of n1 (S, D), the attention sublayer's normed input. The family
    has no shared expert."""
    import jax
    import jax.numpy as jnp

    first, count = experts_held or (cfg.experts_first, cfg.experts_count)
    chosen, weights = route(cfg, p, n1)

    @jax.checkpoint  # an expert's activations are made again for its gradient
    def one(out, e):  # expert `index`, for every token, masked by its weight
        index, gate_up, down = e
        w_e = jnp.sum(jnp.where(chosen == index, weights, 0.0), axis=1)
        return out + w_e[:, None] * expert(h, gate_up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        first + jnp.arange(count), p["w_up"][:count], p["w_down"][:count]))
    return out


def reference_hidden(cfg, params, tokens):
    """tokens (S,) -> the last layer's output (S, D), before the final
    norm."""
    import jax

    x = params["embed"][tokens]
    before = None  # the attention sublayer's input and its norm's scale
    for name in sorted(params["layers"]):
        p, kind = params["layers"][name], name[-1]
        if kind == "E":
            def layer(x, p, x0, g1):
                return x + moe_mixer(
                    cfg, p, rmsnorm(x, p["norm"], cfg.norm_eps),
                    rmsnorm(x0, g1, cfg.norm_eps))

            # the gradient keeps a layer's input and makes the rest again
            x = jax.checkpoint(layer)(x, p, *before)
        else:
            def layer(x, p, windowed=kind == "W"):
                return x + attention_mixer(
                    cfg, p, rmsnorm(x, p["norm"], cfg.norm_eps), windowed)

            before = (x, p["norm"])
            x = jax.checkpoint(layer)(x, p)
    return x


def sequence_loss(cfg, params, tokens, targets):
    """Mean next-token cross-entropy of one sequence; differentiable. The
    head and the loss a chunk of `LOSS_BLOCK` tokens at a time."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = reference_hidden(cfg, params, tokens)
        S = x.shape[0]
        n = S // LOSS_BLOCK if S % LOSS_BLOCK == 0 else 1

        @jax.checkpoint  # a chunk's logits are made again for its gradient
        def chunk(xs):
            xc, tc = xs
            logits = jnp.einsum(
                "sd,dv->sv", rmsnorm(xc, params["norm_f"], cfg.norm_eps),
                params["head"], precision=_hi())
            logp = jax.nn.log_softmax(logits, -1)
            return -jnp.sum(jnp.take_along_axis(logp, tc[:, None], axis=1))

        return jnp.sum(jax.lax.map(chunk, (
            x.reshape(n, S // n, -1), targets.reshape(n, S // n)))) / S


def loss_fn(cfg, params, batch):
    """Mean over the batch's sequences, traced; `jax.grad` of it gives the
    reference's gradients (the CPU tests)."""
    import jax.numpy as jnp

    return jnp.mean(jnp.stack([
        sequence_loss(cfg, params, t, y)
        for t, y in zip(batch["tokens"], batch["targets"])]))


def _loss_and_grads(cfg):
    import jax

    return jax.jit(jax.value_and_grad(
        lambda p, t, y: sequence_loss(cfg, p, t, y)))


def loss_and_grads_program(cfg, params, tokens, targets):
    """`reference_loss_and_grads`' program for ONE sequence, compiled ahead
    from arguments like its own or their `jax.ShapeDtypeStruct`s (tokens
    and targets (S,)): the benchmark's runner has it compiled on a thread
    of its own, since compiling it is most of what the reference costs a
    run."""
    return _loss_and_grads(cfg).lower(params, tokens, targets).compile()


def reference_loss_and_grads(cfg, params, batch, program=None):
    """The batch's mean loss and its gradient from one program a sequence;
    the layers, the attention's blocks, the experts' loop and the loss's
    chunks are `jax.checkpoint`s, which changes where a value is kept and
    none of the arithmetic. ``program``: `loss_and_grads_program`'s for
    these shapes, else it is compiled here."""
    import jax

    one = _loss_and_grads(cfg)
    if program is not None:  # a compiled program takes its operands placed
        def one(p, *row):
            return program(p, *jax.device_put(
                row, program.input_shardings[0][1:]))
    loss, total = 0.0, None
    for t, y in zip(batch["tokens"], batch["targets"]):
        value, got = one(params, t, y)
        loss += float(value)
        total = got if total is None else jax.tree_util.tree_map(
            lambda a, b: a + b, total, got)
    n = len(batch["tokens"])
    return loss / n, jax.tree_util.tree_map(lambda a: a / n, total)


def reference_grads(cfg, params, batch):
    return reference_loss_and_grads(cfg, params, batch)[1]


def reference_loss(cfg, params, batch) -> float:
    """The same number alone: one jitted sequence at a time."""
    import jax
    import jax.numpy as jnp

    one = jax.jit(lambda p, t, y: sequence_loss(cfg, p, t, y))
    return float(jnp.mean(jnp.stack([
        one(params, t, y)
        for t, y in zip(batch["tokens"], batch["targets"])])))


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
    of flipped signs, in float32 on whatever device holds the operands."""
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
      layers together (every ``wq``, every ``w_up``, ...), where
      ``together`` is the name furthest off;
    - ``update``: ``after - before`` against Adam's first step on the
      reference's gradient;
    - ``optimizer``: ``after - before`` against Adam's first step on the
      step's own gradient;
    - ``flipped``: no distance, the share of elements whose change has
      another sign than Adam's first step on the reference's gradient.

    A leaf or a name whose ``want`` is all zeros (frozen routers take no
    gradient) reads 0 where ``got`` is zeros too, else infinity: equal, not
    NaN."""
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
    out = {name: (ratio(d, n), by_leaf)
           for name, (d, n, by_leaf) in sums.items()}
    by_name = {name: ratio(d, n) for name, (d, n) in named.items()}
    out["gradient_by_name"] = (max(by_name.values()), by_name)
    out["flipped"] = (flipped / elements, {})
    return out
