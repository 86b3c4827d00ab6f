#!/usr/bin/env python3
"""Controls of the hybrid cells' comparison: `run.py`'s own run of a cell,
with the program changed first.

    python3 benchmarks/control_hybrid.py <change> --workload <cell> --seed <n> --seconds <s>

``float8`` (every matmul operand of the model rounded to float8 e4m3, the
nearest precision below the bf16 the configuration states) and
``no_routed_experts`` (a term left out: the expert layers give their shared
expert alone) are faults, and the result line must read ``correct: false``.
``all_to_held`` is no fault: a selection bias that sends every assignment to
the experts held, twelve tiles of `_experts_held` a layer where the cell's
traffic takes one, and the line must read ``correct: true``. The readings
that the limits of ``reference_hybrid.py`` stand on were taken this way
(PERF.md, Findings, PR 27); `tests/test_benchmark_hybrid.py` does the same at
toy widths.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def changes() -> dict:
    """``{change: {attribute of edl_tpu.models.hybrid: replacement}}``."""
    import jax.numpy as jnp
    from edl_tpu.models import hybrid

    mm, grouped, init_layer = hybrid._mm, hybrid._grouped, hybrid._init_layer

    def low(a):
        return a.astype(jnp.float8_e4m3fn).astype(hybrid.bf16)

    def biased(cfg, kind, key):
        p = init_layer(cfg, kind, key)
        if kind == "E":
            first, count = cfg.experts_held
            p["router_bias"] = p["router_bias"].at[first:first + count].set(10.)
        return p

    return {
        "float8": {
            "_mm": lambda spec, a, b, out=jnp.float32:
                mm(spec, low(a), low(b), out),
            "_grouped": lambda rows, w, sizes, held:
                grouped(low(rows), low(w), sizes, held)},
        "no_routed_experts": {
            "_routed": lambda cfg, tok, *_: jnp.zeros(tok.shape, jnp.float32)},
        "all_to_held": {"_init_layer": biased},
    }


if __name__ == "__main__":
    from edl_tpu.models import hybrid

    import run

    for name, replacement in changes()[sys.argv[1]].items():
        setattr(hybrid, name, replacement)
    sys.exit(run.main(sys.argv[2:]))
