"""On-chip flash block-size sweep: find and persist the fastest VMEM tiles.

Sweeps ``block_q`` x ``block_k`` over {128, 256, 512}^2 for each
benchmark shape (fwd+bwd, the training direction), on the chip only —
interpret mode has no VMEM and its timings are meaningless. The
winners land in two places:

- ``FLASH_SWEEP.json`` — the full grid with per-config ms/step (artifact);
- ``edl_tpu/ops/flash_blocks.json`` — the tuning table the kernel's
  default path consults (`ops/flash_tuning.lookup`); commit both.

Configs whose VMEM demand exceeds the chip fail to lower — recorded as
such and skipped (that's the graceful-fallback evidence, not an error).
Timing within one process on one shape: the ranking is what is read,
kernels dominate and transfers are constant across configs.

Usage: `python onchip_flash_sweep.py` on the chip; EDL_SWEEP_SHAPES /
EDL_SWEEP_BLOCKS override the grid.
"""

from __future__ import annotations

import itertools
import json
import os
import statistics
import time

#: (B, S, H, D) — the bench_flash shapes plus the LM-bench attention shape
_DEFAULT_SHAPES = [
    [4, 1024, 8, 64],
    [4, 2048, 8, 64],
    [2, 4096, 8, 64],
    [1, 8192, 8, 128],
]
_DEFAULT_BLOCKS = [128, 256, 512]


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import require_devices

    devices = require_devices()
    backend = devices[0].platform
    if backend == "cpu" and os.environ.get("EDL_SWEEP_ALLOW_CPU") != "1":
        print(json.dumps({
            "metric": "flash_block_sweep",
            "error": "refusing to tune VMEM tiles in interpret mode on CPU "
                     "(timings meaningless); EDL_SWEEP_ALLOW_CPU=1 to force "
                     "a harness smoke",
        }))
        return

    from edl_tpu.ops import flash_attention, flash_tuning

    shapes = json.loads(os.environ.get("EDL_SWEEP_SHAPES", "null")) \
        or _DEFAULT_SHAPES
    grid = json.loads(os.environ.get("EDL_SWEEP_BLOCKS", "null")) \
        or _DEFAULT_BLOCKS
    steps = max(1, int(os.environ.get("EDL_BENCH_STEPS", "10")))
    reps = max(1, int(os.environ.get("EDL_BENCH_WINDOWS", "3")))

    rng = np.random.default_rng(0)
    records = []
    winners = {}
    for B, S, H, D in shapes:
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
        best = None
        for bq, bk in itertools.product(grid, grid):
            if bq > S or bk > S:
                continue
            rec = {"shape_BSHD": [B, S, H, D], "block_q": bq, "block_k": bk}
            try:
                step = jax.jit(jax.grad(
                    lambda q: jnp.sum(flash_attention(
                        q, k, v, block_q=bq, block_k=bk) ** 2)
                ))
                step(q).block_until_ready()  # compile + lowering check
                times = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        g = step(q)
                    jax.block_until_ready(g)
                    times.append((time.perf_counter() - t0) / steps)
                ms = 1e3 * statistics.median(times)
                rec["ms_per_step"] = round(ms, 3)
                if best is None or ms < best[0]:
                    best = (ms, bq, bk)
            except Exception as e:  # noqa: BLE001 — VMEM overflow is data
                rec["error"] = str(e)[:300]
            records.append(rec)
            print(json.dumps(rec), flush=True)
        if best is not None:
            key = flash_tuning._key(flash_tuning._bucket(S), D, "bfloat16")
            # keep the better winner if two shapes share a bucket
            if key not in winners or best[0] < winners[key][0]:
                winners[key] = best

    meta = {
        "backend": backend,
        "device_kind": str(getattr(devices[0], "device_kind", "")),
        "steps": steps,
        "reps": reps,
        "note": "fwd+bwd ms/step medians; see FLASH_SWEEP.json for the grid",
    }
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "FLASH_SWEEP.json"), "w") as f:
        json.dump({"metric": "flash_block_sweep", "meta": meta,
                   "grid": records,
                   "winners": {k: {"ms_per_step": round(v[0], 3),
                                   "blocks": [v[1], v[2]]}
                               for k, v in winners.items()}}, f, indent=1)
    if backend != "cpu":
        flash_tuning.save_table(
            {k: (v[1], v[2]) for k, v in winners.items()}, meta
        )
    print(json.dumps({
        "metric": "flash_block_sweep",
        "winners": {k: [v[1], v[2]] for k, v in winners.items()},
        "configs_timed": sum(1 for r in records if "ms_per_step" in r),
        "configs_failed": sum(1 for r in records if "error" in r),
        "table_written": backend != "cpu",
    }))


if __name__ == "__main__":
    main()
