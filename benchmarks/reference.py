"""The plain reference: the decoder's forward pass in straightforward
``jax.numpy``, float32, every matmul at ``highest`` precision; no kernel, no
K/V cache, no bf16, no batching. Copied from ``chip_smoke.py`` (PR 21), where
it was proven on the chip, so that later PRs may change the smoke and never
the yardstick. It follows this repo's block (RMS norm with a scale, untied
head: the departures the configuration files state), not GPT-2's LayerNorm.
"""

from __future__ import annotations

import math

#: a served token may differ from the float32 reference's argmax only where
#: the reference's logit for it is within this of its maximum (logit units):
#: the engine's matmuls run in bf16, whose rounding moves a logit of order 1
#: by up to about 0.01, so two candidates closer than a few of those can swap.
#: PR 21 measured a worst near-tie gap of under 0.05 on the chip.
NEAR_TIE = 0.05

#: |first step's loss - reference cross-entropy| in nats. The step computes
#: its matmuls in bf16 (8 mantissa bits) with float32 accumulation and the
#: loss in float32; at initialisation the logits have a standard deviation
#: of about 0.6, and a relative error of 2^-8 in them moves the mean loss of
#: 32k tokens by well under 1e-2. A step run in a lower precision than bf16,
#: or on other data or weights, misses by far more (a different batch of
#: uniform tokens alone moves the loss by about 3e-3, wrong weights by 0.1+).
LOSS_TOL = 0.02


def reference_logits(cfg, params, tokens):
    """tokens (S,) -> logits (S, V); row t is the distribution of token
    t + 1. ``cfg`` needs ``head_dim`` only."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    S = tokens.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g

    def layer(x, bp):
        h = norm(x, bp["ln1"])
        qkv = jnp.einsum("sd,dthe->sthe", h, bp["wqkv"], precision=hi) \
            + bp["bqkv"]
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        s = jnp.einsum("she,the->hst", q, k, precision=hi) \
            / math.sqrt(cfg.head_dim)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = jnp.einsum("hst,the->she", w, v, precision=hi)
        x = x + jnp.einsum("she,hed->sd", a, bp["wo"], precision=hi) + bp["bo"]
        h = norm(x, bp["ln2"])
        f = jax.nn.gelu(
            jnp.einsum("sd,df->sf", h, bp["win"], precision=hi) + bp["bin"])
        x = x + jnp.einsum("sf,fd->sd", f, bp["wout"], precision=hi) \
            + bp["bout"]
        return x, None

    x = params["embed"][tokens] + params["pos"][:S]
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return jnp.einsum("sd,dv->sv", norm(x, params["lnf"]), params["head"],
                      precision=hi)


def reference_loss(cfg, params, batch) -> float:
    """Mean next-token cross-entropy of ``batch`` (``tokens`` and ``targets``,
    each (B, S)), one sequence at a time."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def one(p, tokens, targets):
        logp = jax.nn.log_softmax(reference_logits(cfg, p, tokens), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=1))

    rows = [one(params, t, y)
            for t, y in zip(batch["tokens"], batch["targets"])]
    return float(jnp.mean(jnp.stack(rows)))


def check_greedy(cfg, params, prompts, served, pad_to: int) -> dict:
    """Every served token against the reference, teacher-forced on the
    engine's own prefix. Returns counts and ``ok``; a token that is neither
    the reference's argmax nor within ``NEAR_TIE`` of it is ``wrong``."""
    import jax
    import numpy as np

    ref = jax.jit(lambda p, t: reference_logits(cfg, p, t))
    exact = near = wrong = 0
    worst_gap = 0.0
    for prompt, tokens in zip(prompts, served):
        seq = list(prompt) + list(tokens)
        padded = np.zeros((-(-len(seq) // pad_to) * pad_to,), np.int32)
        padded[:len(seq)] = seq
        logits = np.asarray(ref(params, padded))
        if not np.isfinite(logits[:len(seq)]).all():
            wrong += len(tokens)
            continue
        for i, tok in enumerate(tokens):
            row = logits[len(prompt) - 1 + i]
            gap = float(row.max() - row[tok])
            if int(row.argmax()) == tok:
                exact += 1
            elif gap < NEAR_TIE:
                near += 1
            else:
                wrong += 1
            worst_gap = max(worst_gap, gap)
    return {"ok": wrong == 0 and exact + near > 0,
            "tokens_checked": exact + near + wrong, "tokens_exact": exact,
            "tokens_near_tie": near, "tokens_wrong": wrong,
            "worst_gap": worst_gap, "band": NEAR_TIE}
