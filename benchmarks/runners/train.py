"""Runner ``train``: elastic training on a static membership.

The path of ``chip_smoke.train_phase`` (PR 21): ``InProcessCoordinator``
leases -> ``SyntheticShardSource`` -> ``ElasticWorker`` (its defaults:
heartbeat 1 s, ``pipeline_depth`` 2, warm compile) -> ``Trainer.train_step``.

The worker cannot be told to stop: it ends when the queue is empty. So the
runner feeds it. One batch to a shard; warm-up shards plus ``queue_ahead`` are
queued at the start; ``ElasticConfig.step_callback`` (worker's thread, after
the loss is on the host) stamps each step and queues one more shard while the
shards outstanding would not outlast the window. Leases are completed only at
a checkpoint, and the only checkpoint is the worker's final one, after the
window, so lease and heartbeat times outlast the run.
"""

from __future__ import annotations

import math
import os
import statistics
import time


def run(cell, compiles):
    import jax
    from edl_tpu.coordinator.inprocess import InProcessCoordinator
    from edl_tpu.models import transformer
    from edl_tpu.obs.tracing import Tracer
    from edl_tpu.parallel import MeshSpec, build_mesh
    from edl_tpu.runtime import (ElasticConfig, ElasticWorker,
                                 SyntheticShardSource)
    from edl_tpu.runtime.train_loop import Trainer, TrainerConfig

    import reference
    from cell import Outcome

    w, traffic, log = cell.workload, cell.traffic, cell.log
    if traffic["seq_len"] != cell.model_kwargs["seq_len"]:
        raise ValueError("the traffic's seq_len is not the configuration's")
    batch, warm, ahead = (traffic["batch"], traffic["warmup_steps"],
                          traffic["queue_ahead"])
    tokens_per_step = batch * traffic["seq_len"]
    model = transformer.make_model(**cell.model_kwargs, remat=w["remat"])
    # PRNGKey takes 32 signed bits; the driver's seeds are larger
    tcfg = TrainerConfig(optimizer=w["optimizer"],
                         learning_rate=w["learning_rate"],
                         seed=cell.seed % (2**31 - 1))
    source = SyntheticShardSource(model, batch_size=batch, batches_per_shard=1)

    def shard(i: int) -> str:  # SyntheticShardSource seeds a shard by its name
        return f"bench-{cell.name}-seed{cell.seed}/part-{i:05d}"

    coord = InProcessCoordinator(task_lease_sec=3600.0,
                                 heartbeat_ttl_sec=3600.0)
    coord.add_tasks([shard(i) for i in range(warm + ahead)])
    s = {"queued": warm + ahead, "stamps": [], "open": None, "close": None,
         "tracing": False, "traced": False, "state": None, "peak": 0,
         "compiles_at_open": 0, "compiles_in_window": None, "setup_s": None}
    traced_steps = w["traced_steps"]

    def on_step(step: int, state) -> None:
        now = time.perf_counter()
        s["stamps"].append(now)
        s["state"] = state
        n = len(s["stamps"])
        if n == warm:  # the window opens at the last warm-up step's stamp
            s["open"], s["close"] = now, now + cell.seconds
            s["setup_s"] = now - cell.t0
            s["compiles_at_open"] = compiles["hits"] + compiles["misses"]
        if s["open"] is None:
            return
        if now >= s["close"] and s["compiles_in_window"] is None:
            s["compiles_in_window"] = (compiles["hits"] + compiles["misses"]
                                       - s["compiles_at_open"])
            s["memory_stats"] = cell.devices[0].memory_stats() or {}
            s["peak"] = s["memory_stats"].get("peak_bytes_in_use", 0)
        if cell.trace and not s["traced"]:
            if not s["tracing"] and n == warm + 2:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(cell.trace_dir,
                                         profiler_options=options)
                s["tracing"] = True
            elif s["tracing"] and n == warm + 2 + traced_steps:
                jax.profiler.stop_trace()
                s["tracing"], s["traced"] = False, True
        # feed: keep `ahead` shards outstanding until they would outlast
        # the window, so that the queue runs dry just after it closes
        step_s = (now - s["open"]) / (n - warm) if n > warm \
            else now - s["stamps"][-2]
        outstanding = s["queued"] - n
        if now + outstanding * step_s < s["close"] + step_s:
            coord.add_tasks([shard(s["queued"])])
            s["queued"] += 1

    tracer = Tracer(component="benchmark")
    worker = ElasticWorker(
        model, coord.client("worker-0"), source,
        ElasticConfig(checkpoint_dir=os.path.join(cell.workdir, "ckpt"),
                      checkpoint_interval=10**9, trainer=tcfg,
                      step_callback=on_step),
        device_planner=lambda world: cell.devices, tracer=tracer)
    t_run = time.perf_counter()
    summary = worker.run()
    t_done = time.perf_counter()
    if s["tracing"]:
        jax.profiler.stop_trace()
    losses = list(worker.losses)
    stamps = s["stamps"]
    if s["open"] is None or s["compiles_in_window"] is None:
        raise RuntimeError(f"the window never opened or closed: "
                           f"{len(stamps)} steps")

    # the window: the opening stamp and every stamp up to its close
    inside = [i for i, t in enumerate(stamps)
              if i >= warm - 1 and t <= s["close"]]
    window = [stamps[i] for i in inside]
    steps = len(window) - 1
    if steps < 2:
        raise RuntimeError(f"only {steps} step(s) completed in the window")
    rate = steps * tokens_per_step / (window[-1] - window[0])
    gaps = [b - a for a, b in zip(window, window[1:])]
    in_window = [losses[i] for i in inside[1:]]
    failed = sum(not math.isfinite(x) for x in in_window)
    log(f"train: {len(stamps)} steps in {t_done - t_run:.2f} s of run(), "
        f"{steps} in the window of {window[-1] - window[0]:.3f} s, "
        f"{len(stamps) - inside[-1] - 1} after it; step "
        f"{statistics.median(gaps) * 1e3:.1f} ms median, "
        f"{min(gaps) * 1e3:.1f} to {max(gaps) * 1e3:.1f}")
    log(f"losses: {[round(x, 4) for x in losses]}")
    log(f"teardown_s (drain and the worker's final checkpoint): "
        f"{t_done - s['close']:.2f}")

    # -- checks, all after the window ------------------------------------------
    checks = {"losses_finite": all(math.isfinite(x) for x in losses),
              "no_compile_in_window": s["compiles_in_window"] == 0,
              "no_rescale": not worker.rescales
              and int(summary["steps"]) == len(stamps)}
    # training learns what there is to learn: the tokens are uniform, so the
    # loss moves from its initial value towards log(vocabulary). The last
    # quarter of the window is nearer to it than the first step was, or
    # inside the noise of one step's mean (a toy model starts there).
    floor = math.log(cell.model_kwargs["vocab_size"])
    last = in_window[-max(len(in_window) // 4, 1):]
    checks["loss_towards_log_vocab"] = abs(statistics.fmean(last) - floor) \
        < max(abs(losses[0] - floor), 3.0 / math.sqrt(tokens_per_step))

    # the step the worker ran, compiled again (from the cache) to be read
    mesh = build_mesh(MeshSpec({"data": len(cell.devices)}), cell.devices)
    trainer = Trainer(model, mesh, tcfg)
    host_batch = next(iter(source.read(shard(0))))
    trainer.warm_compile(s["state"], {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype)
        for k, v in host_batch.items()})
    compiled = trainer._warm.fn
    text, mem = compiled.as_text(), compiled.memory_analysis()
    kernel = "tpu_custom_call" in text
    interpreted = "flash_attention_interpreted" in text
    log(f"compiled step: tpu_custom_call {kernel}, interpreter {interpreted}, "
        f"temp {mem.temp_size_in_bytes} B, arguments "
        f"{mem.argument_size_in_bytes} B; memory_stats at the window's close "
        f"{s['memory_stats']}")
    if cell.devices[0].platform == "tpu":
        checks["flash_kernel_in_step"] = kernel and not interpreted
    s["state"] = None
    del trainer, compiled, worker

    # the first step's loss against the plain float32 forward, on the first
    # shard's batch and the parameters Trainer.init_state makes from the seed
    params = model.init(jax.random.PRNGKey(tcfg.seed), mesh)
    want = reference.reference_loss(model.config, params, host_batch)
    del params
    checks["first_loss_is_reference"] = \
        abs(losses[0] - want) <= reference.LOSS_TOL
    log(f"first step's loss {losses[0]:.5f}, reference {want:.5f}, "
        f"difference {abs(losses[0] - want):.5f} (tolerance "
        f"{reference.LOSS_TOL})")
    log(f"checks: {checks}")

    return Outcome(
        correct=all(checks.values()), attempted=steps, failed=failed,
        end_to_end={"setup_s": s["setup_s"], "train_tokens_per_s": rate},
        memory_peak_bytes=s["peak"],
        # the steady rate: a traced run's own rate holds the seconds that
        # stopping the profiler takes, inside one step's gap
        values={"step_s_p50": statistics.median(gaps),
                "steady_tokens_per_s": tokens_per_step / statistics.median(gaps),
                "steps": float(steps)},
        spans=list(tracer.spans))
