"""Data-plane collective bench: implicit psum vs explicit reduce-scatter
vs bucketed-overlap accumulation, on flat and hierarchical meshes.

Three arms of the SAME model/optimizer/batch under ZeRO-1 moment sharding,
interleaved-window paired in one process (the bench.py / bench_pipeline.py
honest-accounting convention):

- ``psum`` — the implicit data plane (seed behavior): XLA all-reduces the
  full gradient and all-gathers the updated params behind the moment
  sharding. Analytic bytes/chip/step: AR(grads) + AG(params) =
  3·P·(N−1)/N.
- ``reduce_scatter`` — the explicit plane (``grad_sync="reduce_scatter"``):
  gradients pinned to their ZeRO shard layout before the optimizer update,
  so the reduction lowers as reduce-scatter, the update runs on 1/N
  shards, and one all-gather rebuilds the params. 2·P·(N−1)/N — the
  strict-inequality invariant this artifact commits.
- ``bucketed_overlap`` — the explicit plane under scan-based gradient
  accumulation (``grad_accum_microbatches``): microbatch k's gradient
  buckets reduce with no data dependence on microbatch k+1's backward.
  Per-bucket byte accounting from `Trainer.data_plane`.

Every record carries BOTH the measured step wall time and the analytic
bytes-on-wire from `parallel.collective.collective_bytes` (the closed
form validated leaf-by-leaf in tests/test_collective.py), per mesh tier —
on the hierarchical ``("dcn", "data")`` mesh the DCN row shows the
cross-slice hop staying at shard size under the explicit plane.

CPU-sim caveat (same stance as bench_pipeline.py): the 8 forced host
devices share one memory system, so "collectives" are local copies —
measured ms establish that the explicit plane costs no compute-side
regression and exact numerics parity holds, while the committed
bytes-on-wire numbers are the analytic truth the fabric will see. The
CPU run is declared (EDL_BENCH_PLATFORM=cpu, the default here) and selected
by the environment (JAX_PLATFORMS=cpu, as `make bench-collective` sets it).

Env: EDL_COLL_DEVICES (8), EDL_COLL_MESHES (JSON list of axis dicts,
default [{"data": 8}, {"dcn": 2, "data": 4}]), EDL_COLL_BATCH (64),
EDL_COLL_ACCUM (4), EDL_COLL_BUCKET_MB (0.25),
EDL_COLL_VOCAB/D_MODEL/LAYERS/HEADS/D_FF/SEQ (model dims),
EDL_COLL_OPT (adam), EDL_BENCH_WINDOWS (3), EDL_BENCH_STEPS (5),
EDL_COLL_OUT (output path), EDL_BENCH_PLATFORM (cpu). Writes
BENCH_COLLECTIVE.json next to this file and prints one summary JSON line.
"""

from __future__ import annotations

import json
import os
import statistics
import time


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, str(default)))


def _env_float(name: str, default: float) -> float:
    return float(os.environ.get(name, str(default)))


def _env_json(name: str, default):
    val = json.loads(os.environ.get(name, "null"))
    return default if val is None else val


def main() -> dict:
    n_dev = _env_int("EDL_COLL_DEVICES", 8)
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
    from edl_tpu.parallel import MeshSpec, build_hierarchical_mesh, build_mesh
    from edl_tpu.runtime import Trainer, TrainerConfig

    meshes = _env_json(
        "EDL_COLL_MESHES", [{"data": n_dev}, {"dcn": 2, "data": n_dev // 2}]
    )
    batch_size = _env_int("EDL_COLL_BATCH", 64)
    accum = _env_int("EDL_COLL_ACCUM", 4)
    bucket_mb = _env_float("EDL_COLL_BUCKET_MB", 0.25)
    windows = _env_int("EDL_BENCH_WINDOWS", 3)
    steps = max(1, _env_int("EDL_BENCH_STEPS", 5))
    optimizer = os.environ.get("EDL_COLL_OPT", "adam")

    base = dict(
        vocab_size=_env_int("EDL_COLL_VOCAB", 256),
        d_model=_env_int("EDL_COLL_D_MODEL", 64),
        n_layers=_env_int("EDL_COLL_LAYERS", 4),
        n_heads=_env_int("EDL_COLL_HEADS", 8),
        d_ff=_env_int("EDL_COLL_D_FF", 256),
        seq_len=_env_int("EDL_COLL_SEQ", 64),
    )
    model = transformer.make_model(**base)
    rng = np.random.default_rng(0)
    host_batch = model.synthetic_batch(rng, batch_size)

    ARMS = ("psum", "reduce_scatter", "bucketed_overlap")

    records = []
    crossover = {}
    for axes in meshes:
        axes = {k: int(v) for k, v in axes.items()}
        spec = MeshSpec(axes)
        use = devices[: spec.size()]
        mesh = (
            build_hierarchical_mesh(spec, use)
            if axes.get("dcn", 1) > 1
            else build_mesh(spec, use)
        )
        batch_axis = ("dcn", "data") if "dcn" in mesh.axis_names else "data"
        mesh_key = "x".join(f"{k}{v}" for k, v in axes.items())

        def make_arm(arm: str):
            cfg = TrainerConfig(
                optimizer=optimizer,
                shard_opt_state=True,
                batch_axis=batch_axis,
                grad_sync="psum" if arm == "psum" else "reduce_scatter",
                grad_accum_microbatches=accum if arm == "bucketed_overlap" else 1,
                grad_bucket_mb=bucket_mb,
            )
            trainer = Trainer(model, mesh, cfg)
            state = trainer.init_state()
            placed = trainer.place_batch(host_batch)
            return {"trainer": trainer, "state": state, "placed": placed,
                    "loss": None}

        def window(arm_state, n=steps):
            state, loss = arm_state["state"], arm_state["loss"]
            for _ in range(n):
                state, loss = arm_state["trainer"].train_step(
                    state, arm_state["placed"]
                )
            jax.block_until_ready(loss)
            arm_state["state"], arm_state["loss"] = state, loss
            return loss

        arms = {name: make_arm(name) for name in ARMS}
        for a in arms.values():  # compile + warm outside the timed windows
            window(a, n=2)
        # exact-numerics check rides the warmup: psum and rs arms saw the
        # identical batch/seed, so their losses must agree to fp32 exactness
        parity = {
            name: float(arms[name]["loss"]) for name in ("psum", "reduce_scatter")
        }

        walls = {name: [] for name in ARMS}
        for k in range(windows):
            # rotate arm order per window so drift cancels from the pairs
            order = list(ARMS[k % len(ARMS):]) + list(ARMS[: k % len(ARMS)])
            for name in order:
                t0 = time.perf_counter()
                window(arms[name])
                walls[name].append((time.perf_counter() - t0) / steps)

        for name in ARMS:
            plane = arms[name]["trainer"].data_plane(arms[name]["state"].params)
            rec = {
                "mesh": axes,
                "mesh_key": mesh_key,
                "arm": name,
                "grad_sync": plane["grad_sync"],
                "grad_accum_microbatches": plane["grad_accum_microbatches"],
                "step_ms": round(1e3 * statistics.median(walls[name]), 2),
                "step_ms_windows": [round(1e3 * w, 2) for w in walls[name]],
                "grad_bytes_per_step": plane["grad_bytes_per_step"],
                "param_bytes_per_step": plane["param_bytes_per_step"],
                "bytes_per_step": plane["bytes_per_step"],
                "per_tier_bytes": plane["per_tier_bytes"],
                "collective_ms_est": round(
                    1e3 * plane["collective_seconds"], 4
                ),
                "n_buckets": plane["n_buckets"],
                "bucket_nbytes": plane["bucket_nbytes"],
            }
            records.append(rec)
            print(json.dumps(rec), flush=True)

        by_arm = {r["arm"]: r for r in records if r["mesh_key"] == mesh_key}
        rs, ps = by_arm["reduce_scatter"], by_arm["psum"]
        assert rs["bytes_per_step"] < ps["bytes_per_step"], (
            "explicit reduce-scatter must move strictly fewer bytes than "
            f"implicit psum; got {rs['bytes_per_step']} vs "
            f"{ps['bytes_per_step']}"
        )
        crossover[mesh_key] = {
            "rs_vs_psum_bytes_ratio": round(
                rs["bytes_per_step"] / ps["bytes_per_step"], 4
            ),
            "rs_vs_psum_step_ratio": round(
                rs["step_ms"] / ps["step_ms"], 3
            ),
            "bucketed_vs_psum_step_ratio": round(
                by_arm["bucketed_overlap"]["step_ms"] / ps["step_ms"], 3
            ),
            "dcn_bytes_rs_vs_psum": (
                round(
                    rs["per_tier_bytes"]["dcn"] / ps["per_tier_bytes"]["dcn"],
                    4,
                )
                if "dcn" in rs["per_tier_bytes"]
                else None
            ),
            "loss_parity_abs_diff": abs(
                parity["psum"] - parity["reduce_scatter"]
            ),
        }

    summary = {
        "metric": "collective_data_plane",
        "unit": "ms/step",
        "backend": devices[0].platform,
        "meshes": meshes,
        "model": base,
        "optimizer": optimizer,
        "batch": batch_size,
        "grad_accum_microbatches": accum,
        "grad_bucket_mb": bucket_mb,
        "steps": steps,
        "windows": windows,
        "timing_caveat": (
            "CPU-sim numbers: forced host devices share one memory system, "
            "so measured ms establish numerics parity and the absence of a "
            "compute-side regression; the committed bytes-on-wire columns "
            "are the analytic closed form the fabric will see"
        ),
        "crossover": crossover,
        "records": records,
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out = os.environ.get(
        "EDL_COLL_OUT", os.path.join(here, "BENCH_COLLECTIVE.json")
    )
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({
        "metric": summary["metric"],
        "backend": summary["backend"],
        "configs": len(records),
        "crossover": crossover,
    }))
    return summary


if __name__ == "__main__":
    main()
