"""On-chip tile sweep of the three flash kernels: the measurement behind
`edl_tpu.ops.flash_attention._TILE`, `_MAX_SPAN`, `_MAX_STEP_HEADS` and
`_MAX_BLOCK_BYTES`.

For each shape (B, S, H, D[, Hkv[, selected]]), bf16 and causal, on arrays
laid out as the kernels take them, (B, S, H*D) and K/V of (B, S, Hkv*D), and
each tile (block_q, block_k) it times `flash_fwd`, `flash_bwd_dq` and
`flash_bwd_dkv` apart (a jit that returns only dQ leaves XLA nothing of the
dK/dV kernel to run, and the other way round) and prints one JSON line a
point; the same lines go to ``chiprun_out/flash_sweep.jsonl``. ``Hkv`` under
``H`` is a call with groups (a K/V head's group of query heads to a grid
step); ``selected`` 1 adds the int8 selection operand of (B, S, S). A tile
may name up to three more numbers: the most rows of the streamed operand a
grid step takes (`_MAX_SPAN`; ``[128, 128, 128]`` is the grid the kernels had
before the retiling, one tile a step), the most query heads of a group a
step walks (`_MAX_STEP_HEADS`) and the most MiB of blocks a step holds
(`_MAX_BLOCK_BYTES`); each line says which heads a step each kernel took.
Only a chip gives these times: the script exits non-zero without a TPU.

Usage: `python onchip_flash_sweep.py [SHAPES_JSON [TILES_JSON]]`, or
`python onchip_flash_sweep.py grouped` for `GROUPED_SHAPES` at `GROUP_TILES`.
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
#: cell's as it was called before PR 33, K and V repeated to 32 heads (one
#: head a block, one span a head)
SHAPES = [[32, 1024, 16, 64], [16, 2048, 16, 64], [8, 4096, 16, 64],
          [16, 1024, 16, 128], [2, 8192, 32, 128]]
#: the calls with groups (PR 33): the hybrid cell's 32 query heads on 2 K/V
#: heads, the sparse cell's 32 on 4 with its selection, and the latter as it
#: was called before, K and V repeated; `python onchip_flash_sweep.py grouped`
#: runs them at `GROUP_TILES`
GROUPED_SHAPES = [[2, 8192, 32, 128, 2], [1, 16384, 32, 128, 4, 1],
                  [1, 16384, 32, 128, 32, 1]]
TILES = [[128, 128, 128]] + [
    list(t) for t in itertools.product([128, 256, 512, 1024], repeat=2)]
#: the 512 x 512 tile at 16, 8, 4, 2 and 1 heads of a group a step in spans of
#: 8,192 rows, one span of 16,384 rows, and `flash_bwd_dkv` with room for 8
#: and for 2 heads (PR 33's sweep; what it found stands beside `_MAX_SPAN`,
#: `_MAX_STEP_HEADS` and `_MAX_BLOCK_BYTES`)
GROUP_TILES = [[512, 512, 8192, heads, 48] for heads in (16, 8, 4, 2, 1)] + [
    [512, 512, 16384, 8, 72], [512, 512, 8192, 8, 80], [512, 512, 8192, 8, 28]]
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
    if argv[1:] == ["grouped"]:
        argv = [argv[0], json.dumps(GROUPED_SHAPES), json.dumps(GROUP_TILES)]
    shapes = json.loads(argv[1]) if len(argv) > 1 else SHAPES
    tiles = json.loads(argv[2]) if len(argv) > 2 else TILES
    os.makedirs("chiprun_out", exist_ok=True)
    rng = np.random.default_rng(0)
    rule = ("_MAX_SPAN", "_MAX_STEP_HEADS", "_MAX_BLOCK_BYTES")
    default = {name: getattr(fa, name) for name in rule}
    with open("chiprun_out/flash_sweep.jsonl", "a") as sink:
        for B, S, H, D, *grouped in shapes:
            Hkv = grouped[0] if grouped else H
            q, k, v, do = (
                jnp.asarray(rng.standard_normal((B, S, heads * D)),
                            jnp.bfloat16) for heads in (H, Hkv, Hkv, H))
            picked = None
            if grouped[1:] and grouped[1]:
                pairs = jnp.asarray(rng.random((B, S, S)) < 0.25, jnp.int8)
                picked = (pairs, pairs.swapaxes(1, 2))
            zero = jnp.zeros((1,), jnp.int32)
            for blk_q, blk_k, *most in tiles:
                if max(blk_q, blk_k) > S:
                    continue
                for name, value, unit in zip(rule, most, (1, 1, 2**20)):
                    setattr(fa, name, value * unit)
                kw = dict(scale=D ** -0.5, causal=True, k_len=S,
                          blk_q=blk_q, blk_k=blk_k, head_dim=D)
                tiling = fa._tiling(B, S, S, H, Hkv, D, 2, blk_q, blk_k,
                                    picked is not None)
                rec = {"shape_BSHD": [B, S, H, D], "kv_heads": Hkv,
                       "selected": picked is not None,
                       "tile": [blk_q, blk_k], "max_span": fa._MAX_SPAN,
                       "heads_a_step": {name: at["heads_a_step"][0]
                                        for name, at in tiling.items()
                                        if name.startswith("flash_")},
                       "device_kind": device.device_kind}
                fwd = jax.jit(lambda q, k, v: fa._fwd(
                    q, k, v, zero, zero, picked, out_dtype=jnp.bfloat16,
                    **kw))
                try:
                    o, lse = fwd(q, k, v)
                    rec["fwd_ms"] = _ms(fwd, q, k, v)
                    bwd = lambda pick: jax.jit(lambda q, k, v, o, l, do: pick(
                        fa._bwd(q, k, v, o, l, do, jnp.zeros_like(l), zero,
                                zero, picked, **kw)))
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
                for name in rule:
                    setattr(fa, name, default[name])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
