#!/usr/bin/env python3
"""Controls of the sliding-window cells' comparison: `run.py`'s own run of a
cell, with the program changed first.

    python3 benchmarks/control_window.py <change> --workload <cell> --seed <n> --seconds <s>

Each is a fault, and the result line must read ``correct: false``. By which
check and how far, on the chip at the cell's sizes (my chip runs, PR 35,
seed 2600000047; the sound readings beside the limits in
``reference_window.py``):

- ``float8``: every matmul operand of the model rounded to float8 e4m3, the
  nearest precision below the bf16 the configuration states. Fails
  ``grads_are_reference`` (0.0650 together, limit 0.02; 1.000 to 1.001 at
  every leaf name the layers hold, limit 0.15) and ``update_is_reference``
  (1.083, limit 0.35).
- ``no_window``: the window layers run causal: the flash kernels (and the
  plain path) are called without their ``window``, so a query of a ``W``
  layer sees every key at or before it; rotary positions stay. Fails
  ``window_pairs_are_exact`` (the core's own count reads 134,225,920 pairs
  a window layer where the reference counts 58,722,304) and
  ``grads_are_reference`` by the name limit (0.369 on ``wk`` and ``wq``,
  0.341 on ``wo`` and ``wv``; 0.0126 together, under that limit: three
  layers' attention is a small part of the whole gradient's norm). Its step
  takes 715.9 ms where the sound one takes 606.3: the three window layers'
  kernels walk every causal tile.
- ``no_routed_experts``: the expert layers give nothing. Fails
  ``grads_are_reference`` (0.0525 together; 1.0 on ``w_up`` and ``w_down``)
  and ``update_is_reference`` (0.799).

`tests/test_benchmark_window.py` does the same at toy widths.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def changes() -> dict:
    """``{change: {module: {attribute: replacement}}}``."""
    import importlib

    import jax.numpy as jnp
    from edl_tpu import ops
    from edl_tpu.models import hybrid

    # the module: the package exports a function under the same name
    ring_attention = importlib.import_module(
        "edl_tpu.parallel.ring_attention")

    mm, grouped = hybrid._mm, hybrid._grouped
    flash, dense = ops.flash_attention, ring_attention.dense_attention
    pairs = ring_attention.visible_pairs

    def low(a):
        return a.astype(jnp.float8_e4m3fn).astype(hybrid.bf16)

    def causal(attention):  # the same call without its window
        return lambda *args, window=None, **kw: attention(*args, **kw)

    return {
        "float8": {hybrid: {
            "_mm": lambda spec, a, b, out=jnp.float32:
                mm(spec, low(a), low(b), out),
            "_grouped": lambda rows, w, sizes, held:
                grouped(low(rows), low(w), sizes, held)}},
        "no_window": {
            ops: {"flash_attention": causal(flash)},
            ring_attention: {"dense_attention": causal(dense),
                             "visible_pairs": lambda S, window=None: pairs(S)}},
        "no_routed_experts": {hybrid: {
            "_routed": lambda cfg, tok, *_: jnp.zeros(tok.shape, jnp.float32)}},
    }


if __name__ == "__main__":
    import run

    for module, replacements in changes()[sys.argv[1]].items():
        for name, replacement in replacements.items():
            setattr(module, name, replacement)
    sys.exit(run.main(sys.argv[2:]))
