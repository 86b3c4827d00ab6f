"""The plain reference of the hybrid decoder (`edl_tpu/models/hybrid.py`):
float32, every matmul at ``highest`` precision, one sequence at a time, no
kernel, no chunked scan, no sort and no grouped product; loss and, by
`jax.grad` of it, gradients. It follows the
published description of the Nemotron-H / Nemotron 3 family layer by layer
and imports nothing from the program; it reads the program's parameter tree
(``params["layers"]["00M"]`` ...) and any object with the configuration's
sizes as attributes (``cfg``).

Every layer is ``x = x + mixer(rmsnorm(x; w, eps))``:

- ``M``: the Mamba-2 mixer as the LITERAL recurrence, one position a step
  under `lax.scan`: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t =
  S_t C_t + D x_t``, after the causal depthwise conv and before the gated
  group norm.
- ``*``: grouped-query attention with materialised scores, a block of
  query rows after another so that 32 x 8,192 x 8,192 never exists whole;
  no positions.
- ``E``: the sigmoid router over all published experts, then a loop over the
  experts HELD with a dense mask; assignments to experts held elsewhere are
  left out, as in the program.
- ``-``: the dense relu^2 MLP.

This file is kept twice, byte for byte: `benchmarks/reference_hybrid.py` is
the yardstick (no later PR edits it), `edl_tpu/models/hybrid_reference.py`
is the repo's own copy, which the CPU tests compare every layer, the loss and
the gradients with.
"""

from __future__ import annotations

import math

#: |first step's loss - reference cross-entropy| in nats, at the cell's
#: sizes (16,384 tokens, vocabulary 16,384): the limit of the harness's
#: accepted cells (`reference.LOSS_TOL`). It ties the timed step to the
#: reference's data and weights (other data or weights move the loss by 3e-3
#: and more) and nothing finer: the step's bf16 matmuls read 2.8e-5 to 5.2e-4
#: over this PR's seeds on the chip, the same step with every matmul operand
#: rounded to float8 (e4m3, the nearest precision below bf16) 1.3e-4 and
#: 3.3e-3, the reference itself in float8 1.1e-3 and 2.1e-3, and a whole
#: mixer left out 3e-4 to 6.5e-3 (the out-projections start small): the loss
#: of 16,384 tokens hardly moves with any of them. What does is below.
LOSS_TOL = 0.02

#: The timed step itself against the reference, by what its first step left
#: in the worker's state (`first_step_distances`); every distance is ||got -
#: want|| / ||want||, 0 agreement, 1 what zeros read. The readings are from
#: `run.py`'s own runs of `train_nemotron3nano_1chip` on the chip, sound and
#: with a fault put in by `control_hybrid.py` (PERF.md, Findings, PR 27).
#:
#: The step's gradient (Adam's first moment after one step, over 1 - b1)
#: against `jax.grad` of the reference: over all parameters together, and
#: the leaf furthest off. bf16 with float32 accumulation, what the
#: configuration states, reads 0.050 to 0.054 together and 0.25 to 0.28 at
#: the worst leaf (a router: a bf16 rounding moves some token's sixth
#: choice). With the routed experts left out it reads 0.353 together and 1
#: on their leaves and the routers', whose gradient is then zero; other terms
#: left out read 0.19 to 1.4 together; float8 (e4m3) operands, the nearest
#: precision below, about 1 everywhere (cotangents underflow). Each limit
#: lies between, with about a factor of two on either side.
GRAD_TOL = 0.1
GRAD_LEAF_TOL = 0.6

#: The parameters' change in the first step against Adam's first step on the
#: reference's gradient. A state left unchanged reads 1. Adam's first step is
#: the rate times the gradient's SIGN, so this distance is twice the root of
#: the share of elements whose sign differs (`flipped`): bf16 reads 0.353
#: where the gradient itself reads 0.053, 3.1% of the signs (the elements
#: that flip are those nearest zero, which the gradient's norm hardly feels:
#: a reading over a tenth is the optimizer's doing, not a fault of the
#: step); the routed experts left out read 0.842. The limit lies between
#: the bf16 reading and 1, with the more room above the reading.
UPDATE_TOL = 0.75

#: The same change against Adam's first step on the step's OWN gradient: the
#: optimizer's arithmetic alone. It reads 1e-5 together and 4e-4 at the worst
#: leaf (``dt_bias``: float32 rounding of ``p + u`` where p is near -7 and u
#: is 3e-4); a rate or a moment that is off reads its relative error.
OPTIMIZER_TOL = 0.01

#: optax.adam's defaults, which `TrainerConfig(optimizer="adam")` takes
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8

#: rows of queries a block of materialised attention scores holds
QUERY_BLOCK = 1024

#: positions of the Mamba recurrence a `jax.checkpoint` holds (`mamba_mixer`)
BLOCK = 128


def _hi():
    import jax

    return jax.lax.Precision.HIGHEST


def rmsnorm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def relu2(x):
    import jax
    import jax.numpy as jnp

    return jnp.square(jax.nn.relu(x))


def mamba_mixer(cfg, p, h):
    """h (S, D) float32, already normed -> (S, D)."""
    import jax
    import jax.numpy as jnp

    S = h.shape[0]
    H, P, G, N = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups,
                  cfg.state_size)
    inner, K = H * P, cfg.conv_kernel
    proj = jnp.einsum("sd,de->se", h, p["in_proj"], precision=_hi())
    z, xBC, dt = (proj[:, :inner], proj[:, inner:inner + inner + 2 * G * N],
                  proj[:, inner + inner + 2 * G * N:])
    # causal depthwise conv: position t reads t-K+1 .. t of its own channel
    padded = jnp.pad(xBC, ((K - 1, 0), (0, 0)))
    conv = sum(padded[j:j + S] * p["conv_w"][:, j] for j in range(K))
    xBC = jax.nn.silu(conv + p["conv_b"])
    x = xBC[:, :inner].reshape(S, H, P)
    B = xBC[:, inner:inner + G * N].reshape(S, G, N)
    C = xBC[:, inner + G * N:].reshape(S, G, N)
    # head i reads group i // (H / G)
    B, C = (jnp.repeat(a, H // G, axis=1) for a in (B, C))
    dt = jax.nn.softplus(dt + p["dt_bias"])          # (S, H)
    A = -jnp.exp(p["A_log"])                         # (H,)

    def step(state, t):
        x_t, B_t, C_t, dt_t = t
        state = jnp.exp(dt_t * A)[:, None, None] * state \
            + dt_t[:, None, None] * x_t[:, :, None] * B_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, C_t, precision=_hi())

    # position by position; walked in blocks of BLOCK positions, each a
    # `jax.checkpoint`, so that the gradient keeps a state a block and one
    # block's states, not all S of them (17 GB a layer at S 8,192)
    def block(state, ts):
        return jax.lax.scan(step, state, ts)

    ts = (x, B, C, dt)
    if S % BLOCK == 0 and S > BLOCK:
        ts = tuple(a.reshape(S // BLOCK, BLOCK, *a.shape[1:]) for a in ts)
        _, y = jax.lax.scan(jax.checkpoint(block),
                            jnp.zeros((H, P, N), jnp.float32), ts)
        y = y.reshape(S, H, P)
    else:
        _, y = block(jnp.zeros((H, P, N), jnp.float32), ts)
    y = y + p["D"][:, None] * x
    y = (y.reshape(S, inner) * jax.nn.silu(z)).reshape(S, G, inner // G)
    y = rmsnorm(y, p["gate_norm"].reshape(G, inner // G), cfg.norm_eps)
    return jnp.einsum("se,ed->sd", y.reshape(S, inner), p["out_proj"],
                      precision=_hi())


def attention_mixer(cfg, p, h):
    import jax
    import jax.numpy as jnp

    S = h.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("sd,de->se", h, p["wq"], precision=_hi()).reshape(S, Hq, Dh)
    k = jnp.einsum("sd,de->se", h, p["wk"], precision=_hi()).reshape(S, Hkv, Dh)
    v = jnp.einsum("sd,de->se", h, p["wv"], precision=_hi()).reshape(S, Hkv, Dh)
    # query head j reads K/V head j // (Hq / Hkv)
    q = q.reshape(S, Hkv, Hq // Hkv, Dh)
    keys = jnp.arange(S)

    @jax.checkpoint  # a block's scores are made again for its gradient
    def block(qb, start):
        s = jnp.einsum("sgre,tge->grst", qb, k, precision=_hi()) \
            / math.sqrt(Dh)
        seen = keys[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]
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


def route(cfg, p, h):
    """Chosen experts (S, k) and their weights (S, k): sigmoid scores over
    all published experts, the top k of score + selection bias, the scores
    of the chosen renormalised and scaled."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.einsum("sd,ed->se", h, p["router"],
                                  precision=_hi()))
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg.top_k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * cfg.routed_scale


def expert(h, up, down):
    import jax.numpy as jnp

    return jnp.einsum("sf,fd->sd",
                      relu2(jnp.einsum("sd,df->sf", h, up, precision=_hi())),
                      down, precision=_hi())


def moe_mixer(cfg, p, h, experts_held=None, shared=True):
    """The share of the layer that the experts ``experts_held = (first,
    count)`` give (``p["w_up"]`` and ``p["w_down"]`` hold those experts and
    no others), plus the shared expert if ``shared``."""
    import jax
    import jax.numpy as jnp

    first, count = experts_held or (cfg.experts_first, cfg.experts_count)
    chosen, weights = route(cfg, p, h)

    def one(out, e):  # expert `index`, for every token, masked by its weight
        index, up, down = e
        w_e = jnp.sum(jnp.where(chosen == index, weights, 0.0), axis=1)
        return out + w_e[:, None] * expert(h, up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        first + jnp.arange(count), p["w_up"][:count], p["w_down"][:count]))
    if shared:
        out = out + expert(h, p["shared_up"], p["shared_down"])
    return out


def mlp_mixer(cfg, p, h):
    return expert(h, p["w_up"], p["w_down"])


MIXERS = {"M": mamba_mixer, "*": attention_mixer, "E": moe_mixer,
          "-": mlp_mixer}


def reference_logits(cfg, params, tokens):
    """tokens (S,) -> logits (S, V); row t is the distribution of token
    t + 1."""
    import jax.numpy as jnp

    import jax

    x = params["embed"][tokens]
    for name in sorted(params["layers"]):
        def layer(x, p, mixer=MIXERS[name[-1]]):
            return x + mixer(cfg, p, rmsnorm(x, p["norm"], cfg.norm_eps))

        # the gradient keeps a layer's input and makes the rest again
        x = jax.checkpoint(layer)(x, params["layers"][name])
    return jnp.einsum("sd,dv->sv", rmsnorm(x, params["norm_f"], cfg.norm_eps),
                      params["head"], precision=_hi())


def sequence_loss(cfg, params, tokens, targets):
    """Mean next-token cross-entropy of one sequence; differentiable."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(reference_logits(cfg, params, tokens), -1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=1))


def loss_fn(cfg, params, batch):
    """Mean over the batch's sequences, traced; `jax.grad` of it gives the
    reference's gradients (the CPU tests)."""
    import jax.numpy as jnp

    return jnp.mean(jnp.stack([
        sequence_loss(cfg, params, t, y)
        for t, y in zip(batch["tokens"], batch["targets"])]))


def reference_grads(cfg, params, batch):
    """The gradient of the batch's mean loss, one jitted sequence at a
    time; the layers, the attention's blocks and the recurrence's blocks
    are `jax.checkpoint`s, which changes where a value is kept and none of
    the arithmetic."""
    import jax

    one = jax.jit(jax.grad(lambda p, t, y: sequence_loss(cfg, p, t, y)))
    total = None
    for t, y in zip(batch["tokens"], batch["targets"]):
        got = one(params, t, y)
        total = got if total is None else jax.tree_util.tree_map(
            lambda a, b: a + b, total, got)
    n = len(batch["tokens"])
    return jax.tree_util.tree_map(lambda a: a / n, total)


def adam_first_step(grad, learning_rate):
    """What Adam adds to a parameter in its first step, plainly: the moments
    start at zero and are corrected for it, so ``m = g`` and ``v = g^2``."""
    import numpy as np

    m = (1 - ADAM_B1) * grad / (1 - ADAM_B1 ** 1)
    v = (1 - ADAM_B2) * grad * grad / (1 - ADAM_B2 ** 1)
    return -learning_rate * m / (np.sqrt(v) + ADAM_EPS)


def first_step_distances(before, after, first_moment, want_grads,
                         learning_rate):
    """The first optimizer step of the program against the reference, from
    the parameters ``before`` and ``after`` it, Adam's ``first_moment``
    after it and the reference's gradient ``want_grads`` (four trees of one
    structure, on the host). Returns ``{name: (together, by_leaf)}``, each
    distance ``||got - want|| / ||want||``:

    - ``gradient``: the step's gradient, ``first_moment / (1 - b1)``,
      against the reference's;
    - ``update``: ``after - before`` against Adam's first step on the
      reference's gradient;
    - ``optimizer``: ``after - before`` against Adam's first step on the
      step's own gradient;
    - ``flipped``: no distance, the share of elements whose change has
      another sign than Adam's first step on the reference's gradient.

    A leaf whose ``want`` is all zeros (the selection bias takes no
    gradient) reads 0 where ``got`` is zeros too, else infinity."""
    import jax
    import numpy as np

    sums = {name: [0.0, 0.0, {}] for name in ("gradient", "update",
                                              "optimizer")}
    flipped = elements = 0

    def add(name, leaf, got, want):
        d, n = float(np.sum((got - want) ** 2)), float(np.sum(want ** 2))
        sums[name][0] += d
        sums[name][1] += n
        sums[name][2][leaf] = (d / n) ** 0.5 if n else \
            (0.0 if d == 0 else float("inf"))

    flat = [jax.tree_util.tree_leaves(t)
            for t in (after, first_moment, want_grads)]
    for (path, p0), p1, m, want in zip(
            jax.tree_util.tree_leaves_with_path(before), *flat):
        leaf = jax.tree_util.keystr(path)
        p0, p1, m, want = (np.asarray(a, np.float32)
                           for a in (p0, p1, m, want))
        grad, moved = m / (1 - ADAM_B1), p1 - p0
        want_moved = adam_first_step(want, learning_rate)
        add("gradient", leaf, grad, want)
        add("update", leaf, moved, want_moved)
        add("optimizer", leaf, moved, adam_first_step(grad, learning_rate))
        flipped += int(np.sum(np.sign(moved) != np.sign(want_moved)))
        elements += moved.size
    out = {name: ((d / n) ** 0.5 if n else float("inf"), by_leaf)
           for name, (d, n, by_leaf) in sums.items()}
    out["flipped"] = (flipped / elements, {})
    return out


def reference_loss(cfg, params, batch) -> float:
    """The same number for the benchmark: one jitted sequence at a time."""
    import jax
    import jax.numpy as jnp

    one = jax.jit(lambda p, t, y: sequence_loss(cfg, p, t, y))
    rows = [one(params, t, y)
            for t, y in zip(batch["tokens"], batch["targets"])]
    return float(jnp.mean(jnp.stack(rows)))
