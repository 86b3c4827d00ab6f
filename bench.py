"""Benchmark: CTR deep-wide steady-state throughput (samples/sec/chip).

The driver's headline metric (BASELINE.json): CTR samples/sec/chip at steady
state. The reference publishes no absolute throughput in-tree (its story is
cluster-utilization percentages, BASELINE.md), so ``vs_baseline`` compares
against this framework's own static-mesh raw-transport configuration.

Harness notes: absolute host-fed throughput depends on the host link and
on what else the host is doing, so a comparison of numbers from two
separate runs can measure the conditions, not the code. This harness
therefore measures BOTH arms in ONE process with interleaved windows:

- the **wire arm** — the framework's production transport (compact codec,
  decode fused into the jitted step) — is the reported ``value``;
- the **raw arm** — identical model/optimizer/mesh with raw host->device
  transport, i.e. the pre-wire static-mesh baseline configuration —
  is the denominator, re-measured under the same conditions;
- ``vs_baseline`` = median of per-pair wire/raw ratios. Pair order
  alternates (wire-first on even pairs) so slow drift cancels.

Every window of both arms is recorded in the JSON line so future
regressions can be diagnosed from artifacts alone.

Modes (``EDL_BENCH_MODE``):
- ``synthetic`` (default) — pre-generated host batches; paired wire/raw
  arms as above (the headline number).
- ``file`` — the wire arm feeds from real on-disk ``.npz`` shards through
  ``FileShardSource`` with prefetch + shuffle and coordinator leases (the
  full production data path); the paired raw arm feeds
  pre-generated host batches with raw transport, so ``vs_baseline`` prices
  the whole data path + codec against the in-memory baseline. Caveat: the
  interleaved raw window gives the one-shard-deep prefetcher idle time, so
  up to 1 of the ~4 shard reads per wire window lands outside the timed
  span — the same one-shard head start the prefetcher holds in production
  steady state, but a bias to remember when comparing against the old
  back-to-back file harness.

A third paired measurement prices the input pipeline itself: the same
wire-transport configuration stepped through ``DevicePrefetcher``
(placement on a pump thread) vs placing synchronously, interleaved the
same way. Its ``pipelined`` record carries per-window ``place_ms`` /
``step_ms`` splits — see doc/performance.md for how to read them.

``EDL_BENCH_RECORD_BASELINE=1`` additionally writes the raw arm's absolute
numbers to BENCH_BASELINE.json (same run, same harness, same conditions).

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile
import time
from typing import Optional


def require_devices(cpu_declared: Optional[bool] = None):
    """``jax.devices()``, asked plainly. A bench measures the chip: where JAX
    finds no TPU it prints why on stderr and exits non-zero, with no record
    on stdout. ``EDL_BENCH_PLATFORM=cpu`` declares a deliberate CPU run of
    the harness (tiny shapes; its numbers are counts, never device
    metrics) — it permits the CPU, it does not select it: which platform
    JAX uses is the environment's business (``JAX_PLATFORMS``). A bench
    with its own declaration (bench_rescale.py's simulation mesh) passes
    ``cpu_declared`` itself."""
    import jax

    if cpu_declared is None:
        cpu_declared = os.environ.get("EDL_BENCH_PLATFORM") == "cpu"
    devices = jax.devices()
    platform = devices[0].platform
    if platform == "tpu" or (platform == "cpu" and cpu_declared):
        return devices
    sys.exit(
        f"{os.path.basename(sys.argv[0])}: JAX found platform {platform!r} "
        f"({devices[0].device_kind}), not a TPU; a CPU run of the harness "
        "is declared (EDL_BENCH_PLATFORM=cpu) and selected "
        "(JAX_PLATFORMS=cpu) in the environment")


def median_of_best(rates, keep: int) -> float:
    return statistics.median(sorted(rates, reverse=True)[: max(1, keep)])


def main() -> None:
    batch_size = int(os.environ.get("EDL_BENCH_BATCH", "8192"))
    measure_steps = int(os.environ.get("EDL_BENCH_STEPS", "20"))
    windows = int(os.environ.get("EDL_BENCH_WINDOWS", "7"))
    keep = int(os.environ.get("EDL_BENCH_KEEP", "3"))
    mode = os.environ.get("EDL_BENCH_MODE", "synthetic")
    record_baseline = os.environ.get("EDL_BENCH_RECORD_BASELINE") == "1"
    warmup_steps = 5

    import jax
    import numpy as np

    devices = require_devices()
    n_chips = len(devices)

    from edl_tpu.models import ctr
    from edl_tpu.parallel import MeshSpec, build_mesh
    from edl_tpu.runtime import Trainer, TrainerConfig

    mesh = build_mesh(MeshSpec({"data": n_chips}), devices)
    model = ctr.MODEL
    rng = np.random.default_rng(0)
    host_batches = [model.synthetic_batch(rng, batch_size) for _ in range(4)]

    def make_arm(wire: bool):
        trainer = Trainer(
            model,
            mesh,
            TrainerConfig(optimizer="adagrad", learning_rate=0.05,
                          wire_transport=wire),
        )
        return {"trainer": trainer, "state": trainer.init_state(), "loss": None}

    def synthetic_window(arm, steps=measure_steps):
        trainer = arm["trainer"]
        state = arm["state"]
        loss = arm["loss"]  # tolerate steps=0 (EDL_BENCH_STEPS=0 probes)
        for i in range(steps):
            state, loss = trainer.train_step(
                state, trainer.place_batch(host_batches[i % 4])
            )
        if loss is not None:
            jax.block_until_ready(loss)
        arm["state"], arm["loss"] = state, loss
        return steps * batch_size

    wire_arm = make_arm(wire=True)
    raw_arm = make_arm(wire=False)

    if mode == "file":
        from edl_tpu.coordinator import InProcessCoordinator
        from edl_tpu.runtime import (
            FileShardSource, LeaseReader, shard_names, write_shard,
        )

        data_dir = os.environ.get("EDL_BENCH_DATA_DIR") or tempfile.mkdtemp(
            prefix="edl-bench-"
        )
        rows_per_shard = measure_steps * batch_size // 4
        n_shards = 4 * (windows + 1)  # one window's worth per 4 shards
        shards = shard_names("bench", n_shards)
        existing = FileShardSource(root=data_dir, batch_size=batch_size)
        have = set(existing.list_shards())
        for shard in shards:
            # Per-shard (not count-based) reuse check: a dir written under a
            # different geometry regenerates rather than silently feeding the
            # wrong row budget; shard size changes are caught by row counts.
            if shard not in have or existing.rows(shard) != rows_per_shard:
                write_shard(data_dir, shard,
                            model.synthetic_batch(rng, rows_per_shard))
        source = FileShardSource(root=data_dir, batch_size=batch_size,
                                 shuffle_seed=0)
        coord = InProcessCoordinator(task_lease_sec=3600.0)
        client = coord.client("bench")
        client.register()
        client.add_tasks(shards)
        reader = iter(LeaseReader(client, source, prefetch=True))

        def measured_window(arm):
            trainer = arm["trainer"]
            state = arm["state"]
            loss = arm["loss"]  # keeps block_until_ready sane on a dry reader
            n = 0
            for _ in range(measure_steps):
                batch = next(reader, None)
                if batch is None:
                    break
                state, loss = trainer.train_step(state, trainer.place_batch(batch))
                n += 1
            if loss is not None:
                jax.block_until_ready(loss)
            arm["state"], arm["loss"] = state, loss
            return n * batch_size

        # warmup compiles the wire jit against file-shaped batches
        for _ in range(warmup_steps):
            wire_arm["state"], wire_arm["loss"] = wire_arm["trainer"].train_step(
                wire_arm["state"], wire_arm["trainer"].place_batch(next(reader))
            )
        jax.block_until_ready(wire_arm["loss"])
        metric = "ctr_train_samples_per_sec_per_chip_filefed"
    else:
        measured_window = synthetic_window
        synthetic_window(wire_arm, steps=warmup_steps)
        metric = "ctr_train_samples_per_sec_per_chip"

    synthetic_window(raw_arm, steps=warmup_steps)

    def timed(run, arm):
        t0 = time.perf_counter()
        samples = run(arm)
        elapsed = time.perf_counter() - t0
        return samples / elapsed if samples else 0.0

    wire_rates, raw_rates, ratios = [], [], []
    for k in range(windows):
        # Alternate order so slow drift cancels out of the pair ratios.
        if k % 2 == 0:
            w = timed(measured_window, wire_arm)
            r = timed(synthetic_window, raw_arm)
        else:
            r = timed(synthetic_window, raw_arm)
            w = timed(measured_window, wire_arm)
        wire_rates.append(w)
        raw_rates.append(r)
        if w and r:
            ratios.append(w / r)

    per_chip = median_of_best(wire_rates, keep) / n_chips
    raw_per_chip = median_of_best(raw_rates, keep) / n_chips
    vs_baseline = statistics.median(ratios) if ratios else 1.0

    # -- paired pipelined-vs-synchronous arm ------------------------------------
    # Same interleaved-window pairing as wire/raw, now pricing the input
    # pipeline itself: one wire-transport trainer stepped through
    # DevicePrefetcher (encode + H2D placement on a pump thread) vs the same
    # trainer placing synchronously on the dispatch thread. Each window
    # reports its place/step split: place_ms is the placement WORK either
    # way; the sync arm pays it inside the wall (step_ms = wall - place),
    # the pipelined arm overlaps it (step_ms ~= wall).
    from edl_tpu.runtime.pipeline import DevicePrefetcher

    pipe_arm = make_arm(wire=True)
    synthetic_window(pipe_arm, steps=warmup_steps)

    def window_batches():
        return (host_batches[i % 4] for i in range(measure_steps))

    def pipelined_window(arm):
        trainer, state, loss = arm["trainer"], arm["state"], arm["loss"]
        n, place = 0, 0.0
        with DevicePrefetcher(window_batches(), trainer.place_bound,
                              depth=2) as pf:
            for item in pf:
                placed, step_fn = item.payload
                state, loss = step_fn(state, placed)
                n += 1
                place += item.place_seconds
        if loss is not None:
            jax.block_until_ready(loss)
        arm["state"], arm["loss"] = state, loss
        return n * batch_size, place

    def sync_split_window(arm):
        trainer, state, loss = arm["trainer"], arm["state"], arm["loss"]
        n, place = 0, 0.0
        for batch in window_batches():
            t0 = time.perf_counter()
            placed, step_fn = trainer.place_bound(batch)
            place += time.perf_counter() - t0
            state, loss = step_fn(state, placed)
            n += 1
        if loss is not None:
            jax.block_until_ready(loss)
        arm["state"], arm["loss"] = state, loss
        return n * batch_size, place

    def timed_split(run, arm):
        t0 = time.perf_counter()
        samples, place = run(arm)
        elapsed = max(time.perf_counter() - t0, 1e-9)
        return samples / elapsed if samples else 0.0, place * 1e3, elapsed * 1e3

    pipe_rates, sync_rates, pipe_ratios = [], [], []
    pipe_place_ms, sync_place_ms, pipe_step_ms, sync_step_ms = [], [], [], []
    for k in range(windows):
        if k % 2 == 0:
            p_rate, p_place, p_wall = timed_split(pipelined_window, pipe_arm)
            s_rate, s_place, s_wall = timed_split(sync_split_window, pipe_arm)
        else:
            s_rate, s_place, s_wall = timed_split(sync_split_window, pipe_arm)
            p_rate, p_place, p_wall = timed_split(pipelined_window, pipe_arm)
        pipe_rates.append(p_rate)
        sync_rates.append(s_rate)
        pipe_place_ms.append(p_place)
        sync_place_ms.append(s_place)
        pipe_step_ms.append(p_wall)  # placement overlapped: wall ~= step time
        sync_step_ms.append(s_wall - s_place)
        if p_rate and s_rate:
            pipe_ratios.append(p_rate / s_rate)

    pipelined = {
        "value": round(median_of_best(pipe_rates, keep) / n_chips, 2),
        "vs_sync": round(statistics.median(pipe_ratios), 4) if pipe_ratios else 1.0,
        "windows": [round(t / n_chips, 2) for t in pipe_rates],
        "windows_sync": [round(t / n_chips, 2) for t in sync_rates],
        "place_ms": [round(t, 2) for t in pipe_place_ms],
        "place_ms_sync": [round(t, 2) for t in sync_place_ms],
        "step_ms": [round(t, 2) for t in pipe_step_ms],
        "step_ms_sync": [round(t, 2) for t in sync_step_ms],
        "paired_ratios": [round(r, 4) for r in pipe_ratios],
    }

    # Analytic data-plane accounting for the measured configuration
    # (Trainer.data_plane): gradient bytes-on-wire per step and the
    # bandwidth-model collective estimate, next to the measured rates —
    # the same closed form bench_collective.py sweeps across grad_sync
    # modes and mesh hierarchies.
    plane = wire_arm["trainer"].data_plane(wire_arm["state"].params)
    data_plane = {
        "grad_sync": plane["grad_sync"],
        "grad_bytes_per_step": plane["grad_bytes_per_step"],
        "bytes_per_step": plane["bytes_per_step"],
        "collective_ms_est": round(plane["collective_seconds"] * 1e3, 4),
    }

    from edl_tpu.tools.mfu import mfu_fields

    accounting = mfu_fields(
        model,
        batch_size,
        steps_per_sec=median_of_best(wire_rates, keep) / batch_size,
        n_chips=n_chips,
        device=devices[0],
        mesh=mesh,
    )

    here = os.path.dirname(os.path.abspath(__file__))
    if record_baseline:
        with open(os.path.join(here, "BENCH_BASELINE.json"), "w") as f:
            json.dump(
                {
                    "samples_per_sec_per_chip": round(raw_per_chip, 2),
                    "note": (
                        "static-mesh raw-transport CTR throughput: the raw "
                        "arm of the paired harness (median of best "
                        f"{keep}/{windows} windows, {measure_steps} steps x "
                        f"batch {batch_size}). Absolute level is "
                        "condition-dependent; the honest comparison is "
                        "each run's paired vs_baseline, not this number."
                    ),
                    "windows_samples_per_sec_per_chip": [
                        round(t / n_chips, 2) for t in raw_rates
                    ],
                },
                f,
                indent=1,
            )

    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(per_chip, 2),
                "unit": "samples/s/chip",
                "vs_baseline": round(vs_baseline, 4),
                "baseline_arm_value": round(raw_per_chip, 2),
                "windows": [round(t / n_chips, 2) for t in wire_rates],
                "windows_baseline_arm": [
                    round(t / n_chips, 2) for t in raw_rates
                ],
                "paired_ratios": [round(r, 4) for r in ratios],
                "pipelined": pipelined,
                "data_plane": data_plane,
                "median_of_best": keep,
                **accounting,
                "pairing": (
                    "vs_baseline = median per-pair ratio of interleaved "
                    "wire/raw windows in one process (a cross-run "
                    "comparison of host-fed rates measures the conditions)"
                ),
            }
        )
    )


if __name__ == "__main__":
    main()
