"""Microbenchmark: Pallas flash attention vs the dense einsum path.

Times causal self-attention forward+backward at transformer-realistic
shapes on the live backend and prints one JSON line per shape with the
paired speedup (interleaved windows, same methodology as bench.py).
Dense materializes the (S, S) score matrix, so its
memory grows O(S^2) and it eventually OOMs where flash keeps O(S);
shapes that fail on one arm are reported as such rather than crashed on.

Usage:
  python bench_flash.py                   # on the chip
  JAX_PLATFORMS=cpu EDL_BENCH_PLATFORM=cpu python bench_flash.py  # interpret-mode smoke
  EDL_FLASH_SHAPES='[[1,2048,8,64]]' python bench_flash.py
"""

from __future__ import annotations

import json
import os
import statistics
import time

#: (B, S, H, D) — S sweeps past where dense's S^2 scores dominate HBM
_DEFAULT_SHAPES = [
    [4, 1024, 8, 64],
    [4, 2048, 8, 64],
    [2, 4096, 8, 64],
    [1, 8192, 8, 128],
]


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import require_devices

    devices = require_devices()

    from edl_tpu.ops import flash_attention
    from edl_tpu.parallel.ring_attention import dense_attention

    shapes = json.loads(os.environ.get("EDL_FLASH_SHAPES", "null")) \
        or _DEFAULT_SHAPES
    windows = max(1, int(os.environ.get("EDL_BENCH_WINDOWS", "5")))
    # clamped: this tool has no zero-step probe mode (bench.py's
    # EDL_BENCH_STEPS=0 convention), and 0 would divide the ms-per-step
    steps = max(1, int(os.environ.get("EDL_BENCH_STEPS", "10")))

    def arm(fn, q, k, v):
        # Full training direction: grads w.r.t. q AND k/v. Grad-of-q alone
        # would let XLA dead-code-eliminate the flash dk/dv backward kernel
        # (it is a separate pallas_call) and overstate MFU by ~50%.
        loss = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2)
        ))

        def window():
            t0 = time.perf_counter()
            for _ in range(steps):
                g = loss(q, k, v)
            jax.block_until_ready(g)
            return time.perf_counter() - t0

        jax.block_until_ready(loss(q, k, v))  # compile + warm
        return window

    from edl_tpu.tools.mfu import peak_tflops_per_chip

    peak = peak_tflops_per_chip(devices[0])

    def attn_train_flops(B, S, H, D):
        """fwd+bwd matmul FLOPs of causal attention (MFU convention:
        QK^T and PV are 2*S*D/token each, halved by the mask, x3 for the
        backward; the flash backward's score recompute is excluded like
        any remat)."""
        return 3.0 * 0.5 * (4 * S * D) * B * S * H

    rng = np.random.default_rng(0)
    for B, S, H, D in shapes:
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
        record = {"metric": "flash_attention_speedup",
                  "shape_BSHD": [B, S, H, D], "steps": steps}
        try:
            run_flash = arm(lambda q, k, v: flash_attention(q, k, v), q, k, v)
        except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
            record["flash_error"] = str(e)[:200]
            print(json.dumps(record))
            continue
        try:
            run_dense = arm(
                lambda q, k, v: dense_attention(q, k, v), q, k, v
            )
        except Exception as e:  # noqa: BLE001 — dense OOMs first at long S
            record["dense_error"] = str(e)[:200]
            record["note"] = "dense arm failed (expected at long S); flash ran"
            ts = [run_flash() for _ in range(windows)]
            flash_ms = 1e3 * statistics.median(ts) / steps
            flops = attn_train_flops(B, S, H, D)
            achieved = flops / (flash_ms / 1e3) / 1e12
            record.update(
                flash_ms_per_step=round(flash_ms, 3),
                model_flops=flops,
                flops_method="analytic",
                tflops_per_sec=round(achieved, 3),
                peak_tflops=peak,
                mfu=round(achieved / peak, 4) if peak else None,
            )
            print(json.dumps(record))
            continue
        fl, dn, ratios = [], [], []
        for i in range(windows):
            if i % 2 == 0:
                f, d = run_flash(), run_dense()
            else:
                d, f = run_dense(), run_flash()
            fl.append(f)
            dn.append(d)
            ratios.append(d / f)
        flash_ms = 1e3 * statistics.median(fl) / steps
        flops = attn_train_flops(B, S, H, D)
        achieved = flops / (flash_ms / 1e3) / 1e12
        record.update(
            flash_ms_per_step=round(flash_ms, 3),
            dense_ms_per_step=round(1e3 * statistics.median(dn) / steps, 3),
            speedup=round(statistics.median(ratios), 3),
            paired_ratios=[round(r, 3) for r in ratios],
            model_flops=flops,
            flops_method="analytic",
            tflops_per_sec=round(achieved, 3),
            peak_tflops=peak,
            mfu=round(achieved / peak, 4) if peak else None,
        )
        print(json.dumps(record))


if __name__ == "__main__":
    main()
