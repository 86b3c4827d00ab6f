"""On-chip warm-restart pricing: the rescale terms the CPU sim can't see.

BENCH_RESCALE.json proves the <30 s / >=90 % north-star on the 8-device CPU
simulation mesh — but a REAL rescale pays TPU runtime bring-up and XLA
recompilation, which the sim prices at CPU rates. This bench measures the
full single-chip warm-restart path with two separate OS processes on the
chip, exactly what a pod pays after ``RESCALE_EXIT_CODE=75``:

  phase A (doomed pod):   backend init -> trainer build+compile -> train ->
                          checkpoint -> exit(75)
  phase B (restarted pod): backend init -> trainer build -> restore ->
                          first step (recompile) -> ready

One process per chip: a chip belongs to one process at a time, and a parent
that has touched JAX would hold it while its child needs it. This parent
never imports jax (``import jax`` lives in ``_phase_main``, which only the
children run), and the two children run strictly one after the other
(``subprocess.run`` returns before the next starts), so at every moment at
most one process holds the chip.

``recovery_seconds`` = A's stop decision (checkpoint start) through B's
first optimizer step, the elastic-budget span. Every term is itemized so a
>30 s result indicts a specific cost. JAX's persistent compilation cache is
on for both phases (the framework's recommended deployment config — a warm
restart re-runs the SAME program, so B's compile term should be a cache
hit), in the directory ``JAX_COMPILATION_CACHE_DIR`` names or else the
checkout's fixed ``.jax_cache/`` (`edl_tpu.launcher.launch.jax_cache_dir`) —
never a per-run temporary directory, which could not hit across runs. A's
"cold" reference terms are cold only on an empty cache.
EDL_RESCALE_NO_COMPILE_CACHE=1 turns the cache off and prices the cold
path. Writes BENCH_RESCALE_ONCHIP.json; prints one JSON line; exits non-zero
where a phase fails or finds no TPU.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time


def phase_env() -> dict:
    from edl_tpu.launcher.launch import jax_cache_dir  # imports no jax

    env = dict(os.environ)
    if os.environ.get("EDL_RESCALE_NO_COMPILE_CACHE") == "1":
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    else:
        env["JAX_COMPILATION_CACHE_DIR"] = jax_cache_dir()
        # cache even fast-compiling programs (default threshold 1s)
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return env


def run_phase(phase: str, workdir: str, timeout: float) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--phase", phase, workdir],
        env=phase_env(), timeout=timeout,
        capture_output=True, text=True,
    )
    marks_path = os.path.join(workdir, f"{phase}.json")
    if not os.path.exists(marks_path):
        raise RuntimeError(
            f"phase {phase} left no marks (rc={out.returncode}): "
            f"{out.stderr[-800:]}"
        )
    with open(marks_path) as f:
        marks = json.load(f)
    marks["returncode"] = out.returncode
    return marks


def _phase_main(phase: str, workdir: str) -> None:
    """Runs inside each pod subprocess; writes monotonic-ish wall marks
    keyed off time.time() so the parent can splice A and B timelines."""
    marks = {"start": time.time()}

    from bench import require_devices
    from edl_tpu.models import ctr
    from edl_tpu.parallel import MeshSpec, build_mesh
    from edl_tpu.runtime import Trainer, TrainerConfig
    from edl_tpu.runtime.checkpoint import (
        Checkpointer, abstract_like, live_state_specs,
    )
    import jax
    import numpy as np

    devices = require_devices()  # exits non-zero where it finds no TPU
    marks["backend_ready"] = time.time()
    marks["backend"] = devices[0].platform

    batch_size = int(os.environ.get("EDL_RESCALE_BATCH", "8192"))
    model = ctr.MODEL
    mesh = build_mesh(MeshSpec({"data": len(devices)}), devices)
    trainer = Trainer(model, mesh,
                      TrainerConfig(optimizer="adagrad", learning_rate=0.05))
    rng = np.random.default_rng(0)
    batch = trainer.place_batch(model.synthetic_batch(rng, batch_size))
    ckpt = Checkpointer(os.path.join(workdir, "ck"))

    if phase == "train":
        state = trainer.init_state()
        state, loss = trainer.train_step(state, batch)
        jax.block_until_ready(loss)
        marks["first_step_done"] = time.time()  # includes train compile
        for _ in range(10):
            state, loss = trainer.train_step(state, batch)
        jax.block_until_ready(loss)
        marks["steady_done"] = time.time()
        # the stop decision: SIGTERM/rescale arrived; checkpoint and leave
        marks["stop_decision"] = time.time()
        ckpt.save(int(state.step), state)
        ckpt.wait()
        marks["checkpoint_done"] = time.time()
        with open(os.path.join(workdir, f"{phase}.json"), "w") as f:
            json.dump(marks, f)
        os._exit(75)  # RESCALE_EXIT_CODE
    else:  # restore
        fresh = trainer.init_state()  # param alloc, no step compile yet
        marks["state_built"] = time.time()
        state = ckpt.restore(abstract_like(fresh), mesh,
                             live_state_specs(fresh))
        marks["restore_done"] = time.time()
        state, loss = trainer.train_step(state, batch)
        jax.block_until_ready(loss)
        marks["first_step_done"] = time.time()
        with open(os.path.join(workdir, f"{phase}.json"), "w") as f:
            json.dump(marks, f)


def main() -> None:
    if "--phase" in sys.argv:
        i = sys.argv.index("--phase")
        _phase_main(sys.argv[i + 1], sys.argv[i + 2])
        return

    workdir = tempfile.mkdtemp(prefix="edl-rescale-onchip-")  # marks + ckpt
    timeout = float(os.environ.get("EDL_RESCALE_TIMEOUT", "900"))
    t_gap0 = time.time()
    a = run_phase("train", workdir, timeout)
    t_gap1 = time.time()
    if a["returncode"] != 75:
        sys.exit(f"train phase rc={a['returncode']} != 75")
    b = run_phase("restore", workdir, timeout)
    if b["returncode"] != 0:
        sys.exit(f"restore phase rc={b['returncode']} != 0")

    # pod-runtime respawn gap: parent splice minus A's post-mark teardown
    recovery = b["first_step_done"] - a["stop_decision"]
    result = {
        "metric": "onchip_warm_restart_recovery_seconds",
        "value": round(recovery, 3),
        "unit": "seconds",
        "pass_under_30s": recovery < 30.0,
        "backend": b.get("backend"),
        "compile_cache": os.environ.get("EDL_RESCALE_NO_COMPILE_CACHE") != "1",
        "terms": {
            "A_checkpoint_seconds": round(
                a["checkpoint_done"] - a["stop_decision"], 3),
            "A_exit_to_B_spawn_seconds": round(b["start"] -
                                               a["checkpoint_done"], 3),
            "B_backend_init_seconds": round(b["backend_ready"] - b["start"],
                                            3),
            "B_trainer_build_seconds": round(b["state_built"] -
                                             b["backend_ready"], 3),
            "B_restore_seconds": round(b["restore_done"] - b["state_built"],
                                       3),
            "B_first_step_seconds": round(b["first_step_done"] -
                                          b["restore_done"], 3),
        },
        "reference_terms": {
            "A_cold_backend_init_seconds": round(
                a["backend_ready"] - a["start"], 3),
            "A_cold_first_step_seconds": round(
                a["first_step_done"] - a["backend_ready"], 3),
            "parent_overhead_seconds": round(t_gap1 - t_gap0 -
                                             (a["checkpoint_done"] -
                                              a["start"]), 3),
        },
        "note": (
            "recovery = checkpoint start in the doomed pod through first "
            "optimizer step in a fresh OS process on the live backend; "
            "B_first_step is the XLA compile term (persistent cache on "
            "unless EDL_RESCALE_NO_COMPILE_CACHE=1)"
        ),
    }
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_RESCALE_ONCHIP.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
