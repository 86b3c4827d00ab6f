"""Pipeline-schedule crossover bench: gpipe vs 1f1b vs interleaved 1f1b.

Sweeps the three pipeline schedules over microbatch counts (and virtual-
stage counts for the interleaved schedule) on ONE model and ONE mesh,
timing full train steps and recording each configuration's analytic
bubble fraction and activation-stash footprint. This replaces the
unquantified "flip to 1f1b when memory binds" guidance with numbers:
the emitted BENCH_PIPELINE.json is the artifact behind the schedule
guidance in doc/performance.md.

What to expect (and what the closed forms say):
- gpipe wastes (n-1)/(M+n-1) of each of its two scans but stashes
  M + n - 1 microbatch inputs per device — O(M) memory.
- plain 1f1b's combined scan wastes 2(n-1)/(M+2(n-1)) — MORE than gpipe
  at equal M — but stashes only min(M, 2n-1): it buys memory, not speed.
- interleaved 1f1b (v virtual stage chunks per rank) wastes
  (nv+n-2)/(Mv+nv+n-2), strictly below plain 1f1b for v >= 2 when
  n >= 3, while stashing v*min(M, 3n) — the schedule that wins
  wall-clock AND stays O(n*v) in memory.

Defaults run on the CPU-sim mesh (8 forced host devices, pp=4 x data=2;
pp=4 because at pp=2 interleaving exactly ties plain 1f1b). CPU step
times are NOT TPU step times — masked bubble ticks still execute real
FLOPs under XLA, so the relative ordering across schedules at equal M is
meaningful, the absolute ms are not. The CPU run is declared
(EDL_BENCH_PLATFORM=cpu, the default here) and selected by the environment
(JAX_PLATFORMS=cpu, as `make bench-pipeline` sets it).

Env: EDL_PIPE_DEVICES (8), EDL_PIPE_PP (4), EDL_PIPE_MS ([4,8,16]),
EDL_PIPE_VS ([2,4]), EDL_PIPE_VOCAB/D_MODEL/LAYERS/HEADS/D_FF/SEQ
(model dims, for smoke-scale runs), EDL_PIPE_OUT (output path),
EDL_BENCH_WINDOWS (3), EDL_BENCH_STEPS (5), EDL_BENCH_PLATFORM (cpu).
Writes BENCH_PIPELINE.json next to this file and prints a one-line
summary JSON.
"""

from __future__ import annotations

import json
import os
import statistics
import time


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, str(default)))


def _env_list(name: str, default: list) -> list:
    val = json.loads(os.environ.get(name, "null"))
    if val is None or val == []:
        return default
    return val if isinstance(val, list) else [val]


def main() -> dict:
    n_dev = _env_int("EDL_PIPE_DEVICES", 8)
    os.environ.setdefault("EDL_BENCH_PLATFORM", "cpu")
    if os.environ["EDL_BENCH_PLATFORM"] == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_dev}"
            ).strip()

    import jax
    import numpy as np

    from bench import require_devices

    devices = require_devices()

    from edl_tpu.models import transformer
    from edl_tpu.parallel import MeshSpec, build_mesh
    from edl_tpu.parallel.pipeline import bubble_fraction, stash_slots
    from edl_tpu.runtime import Trainer, TrainerConfig

    pp = _env_int("EDL_PIPE_PP", 4)
    data = max(1, len(devices) // pp)
    ms_sweep = [int(m) for m in _env_list("EDL_PIPE_MS", [4, 8, 16])]
    vs_sweep = [int(v) for v in _env_list("EDL_PIPE_VS", [2, 4])]
    windows = _env_int("EDL_BENCH_WINDOWS", 3)
    steps = max(1, _env_int("EDL_BENCH_STEPS", 5))

    base = dict(
        vocab_size=_env_int("EDL_PIPE_VOCAB", 128),
        d_model=_env_int("EDL_PIPE_D_MODEL", 64),
        n_layers=_env_int("EDL_PIPE_LAYERS", 16),
        n_heads=_env_int("EDL_PIPE_HEADS", 8),
        d_ff=_env_int("EDL_PIPE_D_FF", 256),
        seq_len=_env_int("EDL_PIPE_SEQ", 64),
        remat=True,
    )
    local_batch = max(ms_sweep)  # divisible by every M in the sweep
    batch = data * local_batch
    mesh = build_mesh(MeshSpec({"pipe": pp, "data": data}),
                      devices[: pp * data])

    configs = [("gpipe", m, 1) for m in ms_sweep]
    configs += [("1f1b", m, 1) for m in ms_sweep]
    configs += [
        ("1f1b-interleaved", m, v)
        for m in ms_sweep
        for v in vs_sweep
        if base["n_layers"] % (pp * v) == 0 and m % pp == 0
    ]

    rng = np.random.default_rng(0)
    records = []
    for schedule, m, v in configs:
        model = transformer.make_model(
            pipeline_schedule=schedule, microbatches=m, virtual_stages=v,
            **base,
        )
        trainer = Trainer(
            model, mesh, TrainerConfig(optimizer="adam", learning_rate=1e-3)
        )
        state = trainer.init_state()
        placed = trainer.place_batch(model.synthetic_batch(rng, batch))
        for _ in range(2):  # compile + warm
            state, loss = trainer.train_step(state, placed)
        jax.block_until_ready(loss)
        walls = []
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(steps):
                state, loss = trainer.train_step(state, placed)
            jax.block_until_ready(loss)
            walls.append((time.perf_counter() - t0) / steps)
        slots = stash_slots(schedule, pp, m, v)
        # boundary activations are (local_batch/M, S, D) bf16 per slot;
        # per-block internals are remat's story, not the schedule's
        slot_bytes = (local_batch // m) * base["seq_len"] * base["d_model"] * 2
        records.append({
            "schedule": schedule,
            "microbatches": m,
            "virtual_stages": v,
            "step_ms": round(1e3 * statistics.median(walls), 2),
            "step_ms_windows": [round(1e3 * w, 2) for w in walls],
            "bubble_fraction": round(bubble_fraction(schedule, pp, m, v), 4),
            "stash_slots": slots,
            "stash_bytes_per_device": slots * slot_bytes,
        })
        print(json.dumps(records[-1]), flush=True)

    # crossover summary: at each M, which schedule's measured step is best,
    # and plain-1f1b's step-time ratio vs gpipe / vs best-interleaved
    by_m = {}
    for m in ms_sweep:
        at_m = [r for r in records if r["microbatches"] == m]
        g = next(r for r in at_m if r["schedule"] == "gpipe")
        f = next(r for r in at_m if r["schedule"] == "1f1b")
        il = [r for r in at_m if r["schedule"] == "1f1b-interleaved"]
        best_il = min(il, key=lambda r: r["step_ms"]) if il else None
        by_m[str(m)] = {
            "fastest": min(at_m, key=lambda r: r["step_ms"])["schedule"],
            "1f1b_vs_gpipe_step_ratio": round(f["step_ms"] / g["step_ms"], 3),
            "best_interleaved_vs_1f1b_step_ratio": round(
                best_il["step_ms"] / f["step_ms"], 3
            ) if best_il else None,
            "gpipe_vs_1f1b_stash_ratio": round(
                g["stash_bytes_per_device"]
                / max(1, f["stash_bytes_per_device"]), 2
            ),
        }

    summary = {
        "metric": "pipeline_schedule_crossover",
        "unit": "ms/step",
        "backend": devices[0].platform,
        "mesh": {"pipe": pp, "data": data},
        "model": base,
        "batch": batch,
        "steps": steps,
        "windows": windows,
        "timing_caveat": (
            "CPU-sim numbers: masked bubble ticks execute real FLOPs, so "
            "relative ordering across schedules at equal M is meaningful; "
            "absolute ms are not TPU step times"
        ),
        "crossover": by_m,
        "records": records,
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out = os.environ.get(
        "EDL_PIPE_OUT", os.path.join(here, "BENCH_PIPELINE.json")
    )
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({
        "metric": summary["metric"],
        "backend": summary["backend"],
        "configs": len(records),
        "crossover": by_m,
    }))
    return summary


if __name__ == "__main__":
    main()
