#!/usr/bin/env python3
"""Controls of the sparse-attention cells' comparison: `run.py`'s own run of
a cell, with the program changed first.

    python3 benchmarks/control_sparse.py <change> --workload <cell> --seed <n> --seconds <s>

Each is a fault, and the result line must read ``correct: false``. By which
check and how far, on the chip at the cell's sizes (my chip runs, PR 32; the
sound readings beside the limits in ``reference_sparse.py``):

- ``float8``: every matmul operand of the model, the indexer's too, rounded
  to float8 e4m3, the nearest precision below the bf16 the configuration
  states. Fails ``grads_are_reference`` (0.208 together, limit 0.03; 1.00 to
  1.02 at every leaf name of the attention and the experts, limit 0.15) and
  ``update_is_reference`` (1.206, limit 0.5): the reference is GIVEN the
  faulty program's selection, so these read the arithmetic alone; and
  ``selection_is_reference`` (a worst row 0.0508 differing, limit 0.025; a
  worst key 0.323 deviations off the threshold, limit 0.1).
- ``recent_selection``: a query attends to the latest ``topk`` keys in place
  of the ``topk`` of largest indexer score: the right count, causal, and the
  wrong keys. Fails ``selection_is_reference`` alone (0.881 of a row's keys,
  6.15 deviations): the gradient is held to the reference given THIS
  selection and agrees with it (0.0071 together).
- ``no_selection``: every causal key. Fails ``selection_is_reference`` by the
  count (14,336 of 16,384 queries a layer do not hold ``min(t + 1, topk)``)
  and by the keys (0.875, 6.96); the gradient agrees (0.0072).
- ``no_routed_experts``: the expert layers give nothing. Fails
  ``grads_are_reference`` (0.0986 together; 1.0 on ``router``, ``w_up``,
  ``w_down``) and ``update_is_reference`` (0.853).

The readings that the limits of ``reference_sparse.py`` stand on were taken
this way (PERF.md, Findings, PR 32); `tests/test_benchmark_sparse.py` does
the same at toy widths.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def changes() -> dict:
    """``{change: {attribute of edl_tpu.models.hybrid: replacement}}``."""
    import jax.numpy as jnp
    from edl_tpu.models import hybrid

    mm, grouped = hybrid._mm, hybrid._grouped

    def low(a):
        return a.astype(jnp.float8_e4m3fn).astype(hybrid.bf16)

    def causal(scores_t, keep):  # (B, keys, queries), as `_select` gives it
        S = scores_t.shape[1]
        s = jnp.arange(S, dtype=jnp.int32)[None, :, None]
        t = jnp.arange(S, dtype=jnp.int32)[None, None, :]
        return jnp.broadcast_to((s <= t) & keep(s, t),
                                scores_t.shape).astype(jnp.int8)

    return {
        "float8": {
            "_mm": lambda spec, a, b, out=jnp.float32:
                mm(spec, low(a), low(b), out),
            "_grouped": lambda rows, w, sizes, held:
                grouped(low(rows), low(w), sizes, held)},
        "recent_selection": {
            "_select": lambda scores_t, topk:
                causal(scores_t, lambda s, t: s > t - topk)},
        "no_selection": {
            "_select": lambda scores_t, topk:
                causal(scores_t, lambda s, t: True)},
        "no_routed_experts": {
            "_routed": lambda cfg, tok, *_: jnp.zeros(tok.shape, jnp.float32)},
    }


if __name__ == "__main__":
    from edl_tpu.models import hybrid

    import run

    for name, replacement in changes()[sys.argv[1]].items():
        setattr(hybrid, name, replacement)
    sys.exit(run.main(sys.argv[2:]))
