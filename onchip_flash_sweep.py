"""On-chip tile sweep of the three flash kernels: the measurement behind
`edl_tpu.ops.flash_attention._TILE` and `_MAX_SPAN`.

For each shape (B, S, H, D), bf16 and causal, on arrays laid out as the
kernels take them, (B, S, H*D), and each tile (block_q, block_k) it times
`flash_fwd`, `flash_bwd_dq` and `flash_bwd_dkv` apart (a jit that returns
only dQ leaves XLA nothing of the dK/dV kernel to run, and the other way
round) and prints one JSON line a point; the same lines go to
``chiprun_out/flash_sweep.jsonl``. A tile may name a third number, the most
rows of the streamed operand a grid step takes (`_MAX_SPAN`): ``[128, 128,
128]`` is the grid the kernels had before the retiling, one tile a step.
Only a chip gives these times: the script exits non-zero without a TPU.

Usage: `python onchip_flash_sweep.py [SHAPES_JSON [TILES_JSON]]`.
"""

from __future__ import annotations

import importlib
import itertools
import json
import os
import sys
import time

#: the dense cell's attention shape (two heads a block), a ring hop's and a
#: longer sequence at the same token count, a wider head, and the hybrid
#: cell's (one head a block, two spans a head)
SHAPES = [[32, 1024, 16, 64], [16, 2048, 16, 64], [8, 4096, 16, 64],
          [16, 1024, 16, 128], [2, 8192, 32, 128]]
TILES = [[128, 128, 128]] + [
    list(t) for t in itertools.product([128, 256, 512, 1024], repeat=2)]
REPS = 10


def _ms(fn, *args) -> float:
    import jax

    jax.block_until_ready(fn(*args))  # compile, and Mosaic's verdict
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return round(1e3 * (time.perf_counter() - t0) / REPS, 4)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    # the module, not the function `edl_tpu.ops` exports under its name
    fa = importlib.import_module("edl_tpu.ops.flash_attention")

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"no TPU: {device.platform}"}))
        return 1
    shapes = json.loads(argv[1]) if len(argv) > 1 else SHAPES
    tiles = json.loads(argv[2]) if len(argv) > 2 else TILES
    os.makedirs("chiprun_out", exist_ok=True)
    rng = np.random.default_rng(0)
    span_default = fa._MAX_SPAN
    with open("chiprun_out/flash_sweep.jsonl", "a") as sink:
        for B, S, H, D in shapes:
            q, k, v, do = (
                jnp.asarray(rng.standard_normal((B, S, H * D)), jnp.bfloat16)
                for _ in range(4))
            zero = jnp.zeros((1,), jnp.int32)
            for blk_q, blk_k, *span in tiles:
                if max(blk_q, blk_k) > S:
                    continue
                fa._MAX_SPAN = span[0] if span else span_default
                kw = dict(scale=D ** -0.5, causal=True, k_len=S,
                          blk_q=blk_q, blk_k=blk_k, head_dim=D)
                rec = {"shape_BSHD": [B, S, H, D], "tile": [blk_q, blk_k],
                       "max_span": fa._MAX_SPAN,
                       "device_kind": device.device_kind}
                fwd = jax.jit(lambda q, k, v: fa._fwd(
                    q, k, v, zero, zero, out_dtype=jnp.bfloat16, **kw))
                try:
                    o, lse = fwd(q, k, v)
                    rec["fwd_ms"] = _ms(fwd, q, k, v)
                    bwd = lambda pick: jax.jit(lambda q, k, v, o, l, do: pick(
                        fa._bwd(q, k, v, o, l, do, jnp.zeros_like(l), zero,
                                zero, **kw)))
                    args = (q, k, v, o, lse, do)
                    rec["dq_ms"] = _ms(bwd(lambda g: g[0]), *args)
                    rec["dkv_ms"] = _ms(bwd(lambda g: g[1:]), *args)
                    rec["sum_ms"] = round(2 * rec["fwd_ms"] + rec["dq_ms"]
                                          + rec["dkv_ms"], 4)
                except Exception as e:  # noqa: BLE001 -- a refusal is data
                    rec["error"] = str(e)[:300]
                line = json.dumps(rec)
                print(line, flush=True)
                sink.write(line + "\n")
    fa._MAX_SPAN = span_default
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
