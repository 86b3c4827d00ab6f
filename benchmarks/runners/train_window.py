"""Runner ``train_window``: runner ``train_sparse``'s wiring, window, checks
and phases (``InProcessCoordinator`` leases -> ``SyntheticShardSource`` ->
``ElasticWorker`` (its defaults) -> ``Trainer.train_step``; the model by
``edl_tpu.models.resolve(config["model"], sizes)``, the reference by the
module the configuration names; one batch to a shard, fed from
``step_callback``), for a model whose attention layers are a mix of global
and SLIDING-WINDOW ones (``Model.window_stats``) and whose comparison with
the reference has one part: no selection stands between the program and the
reference, so the TIMED step's first step (the worker's parameters and first
moment after it are copied to the host in warm-up) is held to the plain
reference directly. ``train_sparse.py`` and ``train_model.py`` stay as they
are for the cells that name them.

- ``grads_are_reference``, ``update_is_reference``, ``optimizer_is_adam``,
  ``first_loss_is_reference``: as ``train_sparse``'s (the gradient together
  and the leaf NAME furthest off over all layers), loss and gradient from
  one program (`reference.reference_loss_and_grads`), the distances summed
  on the chip, where the seed's parameters and the reference's gradient
  lie. Leaves that take a zero gradient on both sides (frozen routers) read
  equal.
- ``window_pairs_are_exact``: from ``Model.window_stats`` on the first
  step's batch: every attention layer's core sees exactly the (query, key)
  pairs the reference counts (`reference.visible_pairs`: ``min(t + 1,
  window)`` a query in a window layer, ``t + 1`` in a global one), counted
  by the core's own mask and loops. Their share of the causal pairs over
  the window layers is ``window_pairs_share``.

Kept from ``train_sparse``: ``losses_finite``, ``no_compile_in_window``,
``no_rescale``, ``loss_towards_log_vocab``, ``no_token_dropped``,
``assignments_conserved``, and ``flash_kernel_in_step``, read from the step
as LOWERED.

Where a run's seconds go is logged phase by phase (``seconds: ...``): the
driver stops a run at 360 s. The reference's program compiles on a thread
from the window's close, the model's forward pass for its routing hook on
another once the worker has returned, while this thread lowers the step;
the first step's distances are summed on the chip.
"""

from __future__ import annotations

import gc
import importlib
import math
import os
import statistics
import time
from concurrent.futures import ThreadPoolExecutor


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
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cell import Outcome

    w, traffic, log = cell.workload, cell.traffic, cell.log
    batch, warm, ahead = (traffic["batch"], traffic["warmup_steps"],
                          traffic["queue_ahead"])
    S = traffic["seq_len"]
    tokens_per_step = batch * S
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
         "after_first": None, "copy_s": 0.0, "program": None}
    traced_steps = w["traced_steps"]
    clock = [time.perf_counter()]
    mesh = build_mesh(MeshSpec({"data": len(cell.devices)}), cell.devices)
    host_batch = batch_of(0)
    # A run's seconds after the window are mostly compilation (the driver
    # allows a run 360 s). So the reference's program compiles on a thread
    # of its own from the shapes of its operands, from the moment the window
    # has closed (`on_step`), and the model's forward pass for its routing
    # hook on another (below).
    threads = ThreadPoolExecutor(max_workers=2)
    writes_after = jax.config.jax_persistent_cache_min_compile_time_secs

    def compile_reference(params):
        # What is compiled from here on is for the checks alone. It is not
        # WRITTEN to the persistent cache: the reference's program and the
        # forward pass are 50 to 85 MB of code each, and in a cache of some
        # 190 MB they evicted the step's own program, so that every run
        # compiled it cold (my chip runs, PR 27: set-up 163 s a run).
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)

        def like(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=NamedSharding(mesh, P()))

        return threads.submit(
            reference.loss_and_grads_program, model.config,
            jax.tree_util.tree_map(lambda x: like(x.shape, x.dtype), params),
            like((S,), host_batch["tokens"].dtype),
            like((S,), host_batch["targets"].dtype))

    def mark(what: str) -> None:  # where a run's seconds go, phase by phase
        now = time.perf_counter()
        log(f"seconds: {what} {now - clock[0]:.1f} (at {now - cell.t0:.1f})")
        clock[0] = now

    def on_step(step: int, state) -> None:
        now = time.perf_counter()
        s["stamps"].append(now)
        s["state"] = state
        n = len(s["stamps"])
        if n == 1:  # what the first step left
            s["after_first"] = jax.device_get(
                (state.params, _first_moment(state.opt_state)))
            s["copy_s"] = time.perf_counter() - now
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
            s["program"] = compile_reference(state.params)
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
    mark("imports, model, worker")
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
    log(f"steps in the window (ms): {[round(g * 1e3, 1) for g in gaps]}")
    log(f"losses: {[round(x, 4) for x in losses]}")
    log(f"teardown_s (drain and the worker's final checkpoint): "
        f"{t_done - s['close']:.2f}; first step's stamp "
        f"{stamps[0] - t_run:.1f} s into run(), its state's copy to the "
        f"host {s['copy_s']:.1f} s, window opened at {s['open'] - t_run:.1f}")
    clock[0] = t_done
    spent = {}
    for span in tracer.spans:
        n, total = spent.get(span.name, (0, 0.0))
        spent[span.name] = (n + 1, total + span.seconds)
    spent = {k: (n, round(t, 1)) for k, (n, t) in spent.items() if t >= 1}
    log(f"the worker's spans of a second or more in all (count, seconds): "
        f"{spent}")

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

    # Every program the worker loaded goes: the chip's room for loaded
    # programs is apart from its room for arrays, and the reference's
    # program did not load beside the step's and the forward passes' (my
    # chip runs, PR 32: "Error loading program ... 61.61M free" with 12 GB
    # of HBM free).
    del worker
    jax.clear_caches()
    gc.collect()

    # how the router deals with the window's last batch at the parameters the
    # window left, asked of the model after the window (a forward pass of its
    # own, compiled on a thread of its own by this first call); the first
    # batch at the seed's parameters follows below
    routing, last_routing = {}, None
    if model.routing_stats is not None:
        last_routing = threads.submit(model.routing_stats, s["state"].params,
                                      batch_of(inside[-1]))

    # The step the worker ran, lowered again to be read: the flash kernel by
    # its own name (other custom calls may be in the step) and no interpreted
    # branch. Lowered and not compiled: this trace's Mosaic payloads are not
    # the worker's byte for byte, so the persistent cache never answered, and
    # compiling the step a third time was 40 s of a run that has 360 (my
    # chip runs, PR 32); what the compiler makes of the same text at these
    # sizes, temporaries included, is
    # `tests/test_tpu_compile.py::test_window_train_step_at_the_cell`.
    trainer = Trainer(model, mesh, tcfg)
    text = trainer._jit_step.lower(
        s["state"], trainer.place_batch(host_batch)).as_text()
    kernel = "tpu_custom_call" in text and "flash_fwd" in text
    interpreted = "flash_attention_interpreted" in text
    log(f"lowered step: flash kernel {kernel}, interpreter {interpreted}; "
        f"memory_stats at the window's close {s['memory_stats']}")
    if cell.devices[0].platform == "tpu":
        checks["flash_kernel_in_step"] = kernel and not interpreted
    del trainer, text
    mark("the step lowered again and read")

    if last_routing is not None:
        routing[f"step {inside[-1]}, final parameters"] = last_routing.result()
    mark("routing_stats, final parameters")
    # the reference's program needs the chip: the worker's state goes now (its
    # final checkpoint is written), by name and not by reference count
    for leaf in jax.tree_util.tree_leaves(s["state"]):
        leaf.delete()
    s["state"] = None
    gc.collect()

    # the first step's loss against the plain float32 reference, on the first
    # shard's batch and the parameters Trainer.init_state makes from the seed
    params = model.init(jax.random.PRNGKey(tcfg.seed), mesh)
    if model.routing_stats is not None:
        routing[f"step {inside[1]}, the seed's parameters"] = \
            model.routing_stats(params, batch_of(inside[1]))
    mark("the seed's parameters and their routing_stats")

    # every attention layer's core against the reference's count of the
    # pairs a query sees, on the first step's batch
    seen = model.window_stats(params, host_batch)
    exact, visible, causal = True, 0, 0
    for layer, st in seen.items():
        window = model.config.window if layer[-1] == "W" else None
        exact &= st["visible"] == batch * reference.visible_pairs(S, window) \
            and st["causal"] == batch * reference.visible_pairs(S)
        if window is not None:
            visible, causal = visible + st["visible"], causal + st["causal"]
        log(f"pairs seen, layer {layer}: {st['visible']} of {st['causal']} "
            f"causal; the reference counts "
            f"{batch * reference.visible_pairs(S, window)}")
    checks["window_pairs_are_exact"] = bool(exact and seen)
    if causal:
        values["window_pairs_share"] = visible / causal
    mark("window_stats")
    program = s["program"].result()
    threads.shutdown()
    mark("the wait for the reference's program")
    log(f"memory before the reference's program runs: "
        f"{cell.devices[0].memory_stats() or {}}")
    want, want_grads = reference.reference_loss_and_grads(
        model.config, params, host_batch, program)
    del program
    mark("reference_loss_and_grads")
    # and the worker's own first step against the reference's gradient and
    # Adam's first step on it: the loss alone hardly moves with the precision
    # or with a term left out (the reference's limits say what does)
    after, moment = s["after_first"] or (None, None)
    if moment is not None:
        # the seed's parameters and the reference's gradient stay on the
        # chip, what the first step left goes back to it a leaf at a time
        distances = reference.first_step_distances(
            params, after, moment, want_grads, w["learning_rate"])
        for name, (far, by_leaf) in distances.items():
            worst = sorted(by_leaf.items(), key=lambda kv: -kv[1])[:4]
            log(f"first step against the reference, {name}: distance "
                f"{far:.5f}; the leaves furthest off: "
                f"{[(k, round(v, 4)) for k, v in worst]}")
        (grad, _), (named, by_name), (update, _), (adam, _) = (
            distances[k] for k in ("gradient", "gradient_by_name", "update",
                                   "optimizer"))
        log(f"first step against the reference, gradient by leaf name over "
            f"all layers: {({k: round(v, 4) for k, v in by_name.items()})}")
        checks["grads_are_reference"] = grad <= reference.GRAD_TOL \
            and named <= reference.GRAD_NAME_TOL
        checks["update_is_reference"] = update <= reference.UPDATE_TOL
        checks["optimizer_is_adam"] = adam <= reference.OPTIMIZER_TOL
        log(f"limits: gradient {reference.GRAD_TOL} together and "
            f"{reference.GRAD_NAME_TOL} a leaf name, update "
            f"{reference.UPDATE_TOL}, optimizer {reference.OPTIMIZER_TOL}")
    del params, after, moment, want_grads
    mark("first_step_distances")
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
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      writes_after)

    return Outcome(
        correct=all(checks.values()), attempted=steps, failed=failed,
        end_to_end={"setup_s": s["setup_s"], "train_tokens_per_s": rate},
        memory_peak_bytes=s["peak"], values=values,
        spans=list(tracer.spans))
