"""Transformer-LM train-step bench: tokens/s/chip, achieved TFLOP/s, MFU.

CTR (bench.py's flagship) is embedding/host-bound and cannot answer "how
close to peak does this framework run the MXU" — this bench can: a
GPT-2-small-shaped decoder (124M params, seq 1024) whose per-step host
transfer is only the (B, S) token ids, so the host link barely touches
the measurement.

Paired arms, same methodology as bench.py (same-run interleaved windows):

- **flash arm** (reported ``value`` + MFU) — the Pallas flash-attention
  kernel path (`TransformerConfig.flash=True`), remat per env.
- **dense arm** (``vs_baseline`` denominator) — identical model with the
  O(S^2)-materializing einsum attention, the pre-kernel configuration.

MFU uses the models' analytic accounting (`edl_tpu.tools.mfu`): causal-
halved attention, train = 3x forward, remat recompute excluded.

Env: EDL_LM_D_MODEL/LAYERS/HEADS/D_FF/SEQ/VOCAB/BATCH, EDL_LM_REMAT=1,
EDL_LM_MOE=<experts> (bench a switch-MoE variant; 0 = dense),
EDL_BENCH_WINDOWS/STEPS/PLATFORM as in bench.py. Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import statistics
import time


def main() -> None:
    import jax
    import numpy as np

    from bench import median_of_best, require_devices

    devices = require_devices()
    n_chips = len(devices)

    from edl_tpu.models.transformer import TransformerConfig, make_model
    from edl_tpu.parallel import MeshSpec, build_mesh
    from edl_tpu.runtime import Trainer, TrainerConfig
    from edl_tpu.tools.mfu import mfu_fields

    def env_int(name, default):
        return int(os.environ.get(name, str(default)))

    base = dict(
        d_model=env_int("EDL_LM_D_MODEL", 768),
        n_layers=env_int("EDL_LM_LAYERS", 12),
        n_heads=env_int("EDL_LM_HEADS", 12),
        d_ff=env_int("EDL_LM_D_FF", 3072),
        seq_len=env_int("EDL_LM_SEQ", 1024),
        vocab_size=env_int("EDL_LM_VOCAB", 32000),
        remat=os.environ.get("EDL_LM_REMAT") == "1",
        # EDL_LM_MOE=8 benches a switch-MoE variant (single chip: experts
        # colocated, still exercises routing/dispatch cost)
        moe_experts=env_int("EDL_LM_MOE", 0),
    )
    batch_size = env_int("EDL_LM_BATCH", 8)
    windows = env_int("EDL_BENCH_WINDOWS", 5)
    steps = max(1, env_int("EDL_BENCH_STEPS", 10))
    keep = env_int("EDL_BENCH_KEEP", 3)
    tokens_per_step = batch_size * base["seq_len"]

    mesh = build_mesh(MeshSpec({"data": n_chips}), devices)
    rng = np.random.default_rng(0)

    def make_arm(flash: bool):
        model = make_model(TransformerConfig(flash=flash, **base))
        trainer = Trainer(
            model, mesh, TrainerConfig(optimizer="adam", learning_rate=3e-4)
        )
        state = trainer.init_state()
        batches = [
            trainer.place_batch(model.synthetic_batch(rng, batch_size))
            for _ in range(2)
        ]
        arm = {"trainer": trainer, "state": state, "batches": batches,
               "loss": None, "model": model}

        def window(n=steps):
            state, loss = arm["state"], arm["loss"]
            for i in range(n):
                state, loss = trainer.train_step(state, batches[i % 2])
            jax.block_until_ready(loss)
            arm["state"], arm["loss"] = state, loss

        arm["window"] = window
        return arm

    flash_arm = make_arm(flash=True)
    dense_arm = make_arm(flash=False)
    flash_arm["window"](2)  # compile + warm
    dense_arm["window"](2)

    def timed(arm):
        t0 = time.perf_counter()
        arm["window"]()
        return steps * tokens_per_step / (time.perf_counter() - t0)

    fl, dn, ratios = [], [], []
    for k in range(windows):
        if k % 2 == 0:
            f, d = timed(flash_arm), timed(dense_arm)
        else:
            d, f = timed(dense_arm), timed(flash_arm)
        fl.append(f)
        dn.append(d)
        ratios.append(f / d)

    per_chip = median_of_best(fl, keep) / n_chips
    accounting = mfu_fields(
        flash_arm["model"],
        batch_size,
        steps_per_sec=median_of_best(fl, keep) / tokens_per_step,
        n_chips=n_chips,
        device=devices[0],
        mesh=mesh,
    )
    print(json.dumps({
        "metric": "lm_train_tokens_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(statistics.median(ratios), 4),
        "baseline_arm": "dense O(S^2) attention, same model/optimizer/mesh",
        "config": {**base, "batch": batch_size, "params_m": round(
            sum(x.size for x in jax.tree_util.tree_leaves(
                flash_arm["state"].params)) / 1e6, 1)},
        "windows_tokens_per_sec_per_chip": [round(t / n_chips, 1) for t in fl],
        "windows_dense_arm": [round(t / n_chips, 1) for t in dn],
        "paired_ratios": [round(r, 3) for r in ratios],
        **accounting,
        "pairing": (
            "vs_baseline = median per-pair flash/dense ratio of interleaved "
            "same-run windows"
        ),
    }))


if __name__ == "__main__":
    main()
