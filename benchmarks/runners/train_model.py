"""Runner ``train_model``: elastic training on a static membership, for a
model of any zoo family.

The wiring and the window of runner ``train`` (PR 24), which stays as it is
for the cell that names it: ``InProcessCoordinator`` leases ->
``SyntheticShardSource`` -> ``ElasticWorker`` (its defaults) ->
``Trainer.train_step``; one batch to a shard, fed from ``step_callback`` so
that the queue runs dry just after the window closes. What differs: the
model is made by ``edl_tpu.models.resolve(config["model"], sizes)`` and the
plain reference is the module of this directory that the configuration file
names (``config["reference"]``: ``reference_loss(cfg, params, batch)`` and
``LOSS_TOL``), so the next configuration of any family is files only. The
sequence length is the traffic's.

Where the reference has ``reference_grads`` and ``first_step_distances`` and
the optimizer is Adam, the TIMED step is held to it: the worker's parameters
and first moment after its first step are copied to the host (in warm-up, so
set-up pays for it), and after the window the step's gradient and the
parameters' change are compared with the reference's gradient and Adam's
first step on it: ``grads_are_reference``, ``update_is_reference``,
``optimizer_is_adam``, each under the reference's limit.

A model with routed experts (``Model.routing_stats``) is asked, after the
window, what its expert layers do with the window's first batch at the seed's
parameters and with its last batch at the parameters the window left:
``no_token_dropped`` (held assignments less the rows its grouped product gave
a value), ``assignments_conserved``, and ``moe_load_max_over_mean``.
"""

from __future__ import annotations

import importlib
import math
import os
import statistics
import time


def _first_moment(opt_state):
    """Adam's first moment out of an optax state; None where it has none."""
    import jax

    has = lambda x: hasattr(x, "mu")
    return next((part.mu for part in jax.tree_util.tree_leaves(
        opt_state, is_leaf=has) if has(part)), None)


def run(cell, compiles):
    import jax
    from edl_tpu import models as zoo
    from edl_tpu.coordinator.inprocess import InProcessCoordinator
    from edl_tpu.obs.tracing import Tracer
    from edl_tpu.parallel import MeshSpec, build_mesh
    from edl_tpu.runtime import (ElasticConfig, ElasticWorker,
                                 SyntheticShardSource)
    from edl_tpu.runtime.train_loop import Trainer, TrainerConfig

    from cell import Outcome

    w, traffic, log = cell.workload, cell.traffic, cell.log
    batch, warm, ahead = (traffic["batch"], traffic["warmup_steps"],
                          traffic["queue_ahead"])
    tokens_per_step = batch * traffic["seq_len"]
    model = zoo.resolve(cell.config["model"], dict(
        cell.model_kwargs, seq_len=traffic["seq_len"], remat=w["remat"]))
    reference = importlib.import_module(cell.config["reference"])
    # PRNGKey takes 32 signed bits; the driver's seeds are larger
    tcfg = TrainerConfig(optimizer=w["optimizer"],
                         learning_rate=w["learning_rate"],
                         seed=cell.seed % (2**31 - 1))
    source = SyntheticShardSource(model, batch_size=batch, batches_per_shard=1)

    def shard(i: int) -> str:  # SyntheticShardSource seeds a shard by its name
        return f"bench-{cell.name}-seed{cell.seed}/part-{i:05d}"

    def batch_of(i: int) -> dict:
        return next(iter(source.read(shard(i))))

    coord = InProcessCoordinator(task_lease_sec=3600.0,
                                 heartbeat_ttl_sec=3600.0)
    coord.add_tasks([shard(i) for i in range(warm + ahead)])
    s = {"queued": warm + ahead, "stamps": [], "open": None, "close": None,
         "tracing": False, "traced": False, "state": None, "peak": 0,
         "compiles_at_open": 0, "compiles_in_window": None, "setup_s": None,
         "after_first": None}
    held_to_reference = hasattr(reference, "first_step_distances")
    traced_steps = w["traced_steps"]

    def on_step(step: int, state) -> None:
        now = time.perf_counter()
        s["stamps"].append(now)
        s["state"] = state
        n = len(s["stamps"])
        if n == 1 and held_to_reference:  # what the first step left
            s["after_first"] = jax.device_get(
                (state.params, _first_moment(state.opt_state)))
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
    # the tokens are uniform, so the loss moves from its initial value
    # towards log(vocabulary): the last quarter of the window is nearer to
    # it than the first step was, or inside the noise of one step's mean
    floor = math.log(model.config.vocab_size)
    last = in_window[-max(len(in_window) // 4, 1):]
    checks["loss_towards_log_vocab"] = abs(statistics.fmean(last) - floor) \
        < max(abs(losses[0] - floor), 3.0 / math.sqrt(tokens_per_step))

    values = {"step_s_p50": statistics.median(gaps),
              "steady_tokens_per_s": tokens_per_step / statistics.median(gaps),
              "steps": float(steps), "seq_len": float(traffic["seq_len"]),
              "tokens_per_step": float(tokens_per_step)}

    # the step the worker ran, compiled again (from the cache) to be read
    mesh = build_mesh(MeshSpec({"data": len(cell.devices)}), cell.devices)
    trainer = Trainer(model, mesh, tcfg)
    host_batch = batch_of(0)
    trainer.warm_compile(s["state"], {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype)
        for k, v in host_batch.items()})
    compiled = trainer._warm.fn
    text, mem = compiled.as_text(), compiled.memory_analysis()
    # the flash kernel by its own name: other custom calls may be in the step
    kernel = "tpu_custom_call" in text and "flash_fwd" in text
    interpreted = "flash_attention_interpreted" in text
    log(f"compiled step: flash kernel {kernel}, interpreter {interpreted}, "
        f"temp {mem.temp_size_in_bytes} B, arguments "
        f"{mem.argument_size_in_bytes} B; memory_stats at the window's close "
        f"{s['memory_stats']}")
    if cell.devices[0].platform == "tpu":
        checks["flash_kernel_in_step"] = kernel and not interpreted
    del trainer, compiled

    # What is compiled from here on is for the checks alone, and is kept out
    # of the persistent cache: the reference's two programs and the model's
    # gradient are 50 to 85 MB of code each, and in a cache of some 190 MB
    # they evicted the step's own program, so that every run compiled it cold
    # (my chip runs, PR 27: set-up 163 s a run).
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    # how the router deals with the window's last batch at the parameters the
    # window left, asked of the model after the window (a forward pass of its
    # own); the first batch at the seed's parameters follows below
    routing = {}
    if model.routing_stats is not None:
        routing[f"step {inside[-1]}, final parameters"] = \
            model.routing_stats(s["state"].params, batch_of(inside[-1]))
    s["state"] = None
    del worker

    # the first step's loss against the plain float32 reference, on the first
    # shard's batch and the parameters Trainer.init_state makes from the seed
    params = model.init(jax.random.PRNGKey(tcfg.seed), mesh)
    want = reference.reference_loss(model.config, params, host_batch)
    if model.routing_stats is not None:
        routing[f"step {inside[1]}, the seed's parameters"] = \
            model.routing_stats(params, batch_of(inside[1]))
    # and the worker's own first step against the reference's gradient and
    # Adam's first step on it: the loss alone hardly moves with the precision
    # or with a term left out (the reference's limits say what does)
    after, moment = s["after_first"] or (None, None)
    if moment is not None:
        distances = reference.first_step_distances(
            jax.device_get(params), after, moment, jax.device_get(
                reference.reference_grads(model.config, params, host_batch)),
            w["learning_rate"])
        for name, (far, by_leaf) in distances.items():
            worst = sorted(by_leaf.items(), key=lambda kv: -kv[1])[:4]
            log(f"first step against the reference, {name}: distance "
                f"{far:.5f}; the leaves furthest off: "
                f"{[(k, round(v, 4)) for k, v in worst]}")
        (grad, grad_leaves), (update, _), (adam, _) = (
            distances[k] for k in ("gradient", "update", "optimizer"))
        checks["grads_are_reference"] = grad <= reference.GRAD_TOL \
            and max(grad_leaves.values()) <= reference.GRAD_LEAF_TOL
        checks["update_is_reference"] = update <= reference.UPDATE_TOL
        checks["optimizer_is_adam"] = adam <= reference.OPTIMIZER_TOL
        log(f"limits: gradient {reference.GRAD_TOL} together and "
            f"{reference.GRAD_LEAF_TOL} a leaf, update "
            f"{reference.UPDATE_TOL}, optimizer {reference.OPTIMIZER_TOL}")
    del params, after, moment
    s["after_first"] = None
    if routing:
        made_right = kept = True
        load, share = [], {}
        for when, layers in routing.items():
            for layer, st in layers.items():
                log(f"routing, {when}, layer {layer}: {st}")
                made_right &= st["made"] == tokens_per_step \
                    * model.config.top_k and sum(st["per_expert"]) == st["held"]
                kept &= st["dropped"] == 0
                if st["held"]:
                    load.append(max(st["per_expert"])
                                / statistics.fmean(st["per_expert"]))
            share[when] = statistics.fmean(
                st["held"] / st["made"] for st in layers.values())
        log(f"share of the assignments that go to held experts: {share}")
        checks["no_token_dropped"] = kept
        checks["assignments_conserved"] = made_right
        if load:
            values["moe_load_max_over_mean"] = statistics.fmean(load)
        # held assignments a token a layer at the window's start: the work
        # that the held experts' roofline share is reckoned from
        values["moe_held_per_token"] = model.config.top_k * next(
            v for when, v in share.items() if "seed" in when)
    checks["first_loss_is_reference"] = \
        abs(losses[0] - want) <= reference.LOSS_TOL
    log(f"first step's loss {losses[0]:.6f}, reference {want:.6f}, "
        f"difference {abs(losses[0] - want):.6f} (tolerance "
        f"{reference.LOSS_TOL})")
    log(f"checks: {checks}")
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()

    return Outcome(
        correct=all(checks.values()), attempted=steps, failed=failed,
        end_to_end={"setup_s": s["setup_s"], "train_tokens_per_s": rate},
        memory_peak_bytes=s["peak"], values=values,
        spans=list(tracer.spans))
