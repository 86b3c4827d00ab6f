#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the LM main path once on a TPU v5e, through the entry
points a user calls, at GPT-2-medium widths (d_model 1024, 16 heads of 64,
d_ff 4096, vocabulary 50,257, 1,024 positions, 24 layers, flash attention,
bf16 compute). Weights and data come from ``SEED``; sizes are the constants
below. Nothing outside the checkout is read or written, no network is used.

    python chip_smoke.py             # one chip: device -> train -> serve
    python chip_smoke.py --chips 4   # four chips: device -> rescale, nothing else

Phases, in order; the first that fails ends the run with a non-zero exit code
and its name on stderr (no exception is caught and carried past):

- *device*: ``jax.devices()``; fails unless the platform is ``tpu`` and the
  count is the one asked for.
- *train*: the path of ``examples/lm/train.py``'s local twin —
  ``InProcessCoordinator`` leases -> ``SyntheticShardSource`` ->
  ``ElasticWorker`` -> ``Trainer.train_step`` — for a handful of steps, then
  the final checkpoint is read back. Checks: the compiled step holds the
  Pallas kernel (``tpu_custom_call``; not the interpreter, not the dense
  path), every loss is finite, the last loss is below the first, the step
  counter advanced and survived the checkpoint.
- *serve*: ``save_inference_model`` of the restored weights ->
  ``LMServingReplica.start()`` on a sequence ladder up to 1,024 -> ``POST
  /generate`` over its HTTP port, prompts of several lengths, one alone and
  the rest concurrently. Checks: every request returns the tokens it asked
  for; every token is the greedy choice of a plain float32 forward of the
  same weights written here in ``jax.numpy`` (teacher-forced on the engine's
  own prefix; a token may differ only where that reference's own top two
  logits are closer than ``NEAR_TIE``, the bf16 band); the jit dispatch cache
  did not grow under traffic (the AOT contract of doc/serving.md).
- *rescale* (``--chips 4`` only): elastic data-parallel training with ZeRO-1
  through ``ElasticWorker`` on a ``{data: 4}`` mesh, one membership change
  4 -> 2 -> 4 chips through the real rescale path (checkpoint -> rendezvous
  -> rebuild mesh -> restore), compared with the same seed and batches run
  statically on four chips. Checks: parameters live on, and optimizer shards
  are split over, every device of each mesh; each rescale restored from the
  checkpoint and the step counter continued; the loss at every step stays
  within ``RESCALE_BAND`` of the static run's.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

JAX's persistent compilation cache stays where ``JAX_COMPILATION_CACHE_DIR``
puts it; where that is unset it goes to the fixed, git-ignored ``.jax_cache/``
of the checkout (``edl_tpu.launcher.launch.jax_cache_dir``). Work files go to
the git-ignored ``.chip_smoke/`` and are removed at the end.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(HERE, ".chip_smoke")

SEED = 0

#: GPT-2-medium; no width is cut.
WIDTHS = dict(vocab_size=50257, d_model=1024, n_heads=16, d_ff=4096,
              seq_len=1024)

#: one chip, 16 GB. Compile-time memory_analysis() for a described v5e chip:
#: batch 8 without remat needs 16.75 GiB (refused), batch 4 without remat
#: 14.2 GiB, batch 8 with per-block remat 7.4 GiB — so remat goes on and
#: neither the batch nor the depth is cut.
TRAIN = dict(n_layers=24, remat=True, batch=8, shards=2, batches_per_shard=2,
             passes=3, learning_rate=3e-4,
             kernel_shape=(2, 1024, 16, 64))  # (B, S, H, Dh) of the check

SERVE = dict(batch_buckets=(1, 4), seq_buckets=(128, 256, 512, 1024),
             kv_blocks=256, kv_block_tokens=16,
             prompt_lens=(5, 40, 200, 700), max_new_tokens=8)

#: four chips. Depth is halved so that three blocking checkpoints of the
#: whole state (two rescales and the end, about 3 GB each) and their restores
#: stay inside a few chip-minutes; every width is kept. One batch to a shard:
#: a rescale hands back only leases it has not trained, so no batch is
#: trained twice and both runs take the same number of steps.
RESCALE = dict(n_layers=12, remat=True, batch=8, shards=32,
               batches_per_shard=1, leg_steps=8, learning_rate=3e-4)

#: flash kernel against the dense oracle, bf16 inputs: largest difference
#: over the oracle's largest entry
KERNEL_TOL = 3e-2
#: a served token may differ from the float32 reference's argmax only where
#: the reference's logit for it is within this of its maximum (logit units)
NEAR_TIE = 0.05
#: |loss(elastic) - loss(static)| at the same optimizer step, in nats. The
#: handed-back leases go to the end of the queue, so after a rescale the two
#: runs see the same batches in a slightly different order: on the chip that
#: alone moves a step's loss by up to 0.016 (four-chip run, PR 21).
RESCALE_BAND = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, why) -> None:
    """The smoke's assertion: raises whatever the interpreter's flags."""
    if not ok:
        raise AssertionError(why)


# -- device --------------------------------------------------------------------


def device_phase(chips: int) -> dict:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"jax {jax.__version__}, "
        f"{shutil.disk_usage(HERE).free / 1e9:.1f} GB of disk free")
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX found platform "
            f"{device['platform']!r} ({device['kind']})")
    if device["count"] != chips:
        raise SystemExit(
            f"chip_smoke {'--chips 4 ' if chips == 4 else ''}needs exactly "
            f"{chips} chip(s); JAX found {device['count']}")
    return device


def use_compile_cache() -> dict:
    """Place JAX's persistent cache by the repo's one rule and count its
    hits and misses for the report."""
    import jax
    from edl_tpu.launcher.launch import jax_cache_dir

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", jax_cache_dir())
    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    return counts


# -- train ---------------------------------------------------------------------


def _lm(widths: dict, sizes: dict):
    from edl_tpu.models import transformer

    model_kw = dict(widths, n_layers=sizes["n_layers"], remat=sizes["remat"])
    return transformer.make_model(**model_kw), model_kw


def _shards(tag: str, sizes: dict, passes: int = 1):
    from edl_tpu.runtime.data import pass_tasks, shard_names

    # SyntheticShardSource seeds each shard from its name
    return pass_tasks(
        shard_names(f"chip-smoke-{tag}-seed{SEED}", sizes["shards"]), passes)


def _finite(losses) -> bool:
    return all(math.isfinite(x) for x in losses)


def check_kernel(shape) -> dict:
    """The Pallas kernel as this device runs it against the repo's dense
    oracle (`dense_attention`), bf16 and causal at the training shape:
    output, the three gradients, and the logsumexp the ring layer merges."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from edl_tpu.ops import flash_attention
    from edl_tpu.parallel.ring_attention import dense_attention

    rng = np.random.default_rng(SEED)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for _ in range(3))

    def sq(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    def dense_lse(q, k):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) \
            / math.sqrt(shape[-1])
        pos = jnp.arange(shape[1])
        return jax.nn.logsumexp(
            jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf), axis=-1)

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=True)
    got = (jax.jit(flash)(q, k, v),
           *jax.jit(jax.grad(sq(flash), argnums=(0, 1, 2)))(q, k, v),
           jax.jit(lambda q, k, v: flash_attention(
               q, k, v, causal=True, return_lse=True)[1])(q, k, v))
    want = (jax.jit(dense)(q, k, v),
            *jax.jit(jax.grad(sq(dense), argnums=(0, 1, 2)))(q, k, v),
            jax.jit(dense_lse)(q, k))
    errors = {}
    for name, a, b in zip(("out", "dq", "dk", "dv", "lse"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        check(a.shape == b.shape and np.isfinite(a).all(), name)
        errors[name] = float(np.abs(a - b).max() / np.abs(b).max())
    check(max(errors.values()) <= KERNEL_TOL,
          f"flash attention disagrees with the dense oracle: {errors}")
    return {k: round(e, 5) for k, e in errors.items()}


def train_phase(widths: dict, sizes: dict, workdir: str, devices) -> tuple:
    """Returns (report, what the serve phase takes over: the restored
    params, the model's kwargs, the step)."""
    import jax
    import numpy as np
    from edl_tpu.coordinator.inprocess import InProcessCoordinator
    from edl_tpu.parallel import MeshSpec, build_mesh
    from edl_tpu.runtime import (ElasticConfig, ElasticWorker,
                                 SyntheticShardSource)
    from edl_tpu.runtime.checkpoint import (Checkpointer, abstract_like,
                                            live_state_specs)
    from edl_tpu.runtime.train_loop import Trainer, TrainerConfig

    kernel_errors = check_kernel(sizes["kernel_shape"])
    model, model_kw = _lm(widths, sizes)
    tcfg = TrainerConfig(optimizer="adam",
                         learning_rate=sizes["learning_rate"], seed=SEED)
    mesh = build_mesh(MeshSpec({"data": len(devices)}), devices)

    # The step the worker is about to run, compiled ahead of it to be read:
    # is the kernel in it, and what does the compiler say it needs?
    trainer = Trainer(model, mesh, tcfg)
    state = trainer.init_state()
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(state.params))
    host_batch = model.synthetic_batch(np.random.default_rng(SEED),
                                       sizes["batch"])
    compile_seconds = trainer.warm_compile(
        state, {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in host_batch.items()})
    compiled = trainer._warm.fn
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    # what a restore needs of a state, kept without the 5 GB state itself
    abstract_state, state_specs = abstract_like(state), live_state_specs(state)
    del state, compiled
    kernel = "tpu_custom_call" in text
    interpreted = "flash_attention_interpreted" in text
    if devices[0].platform == "tpu":
        check(kernel and not interpreted,
              f"the compiled train step does not run the Pallas kernel "
              f"(tpu_custom_call: {kernel}, interpreter: {interpreted})")

    ckpt_dir = os.path.join(workdir, "ckpt")
    coord = InProcessCoordinator(task_lease_sec=300.0,
                                 heartbeat_ttl_sec=300.0)
    coord.add_tasks(_shards("train", sizes, sizes["passes"]))
    worker = ElasticWorker(
        model, coord.client("worker-0"),
        SyntheticShardSource(model, batch_size=sizes["batch"],
                             batches_per_shard=sizes["batches_per_shard"]),
        ElasticConfig(checkpoint_dir=ckpt_dir, checkpoint_interval=10**6,
                      trainer=tcfg),
        device_planner=lambda world: devices,
    )
    t0 = time.perf_counter()
    metrics = worker.run()
    train_seconds = time.perf_counter() - t0
    losses = worker.losses
    steps = sizes["shards"] * sizes["batches_per_shard"] * sizes["passes"]
    check(int(metrics["steps"]) == steps == len(losses), (metrics, losses))
    check(_finite(losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    # the one checkpoint the worker wrote, read back as a rescale would
    ckpt = Checkpointer(ckpt_dir)
    check(ckpt.latest_step() == steps, (ckpt.latest_step(), steps))
    restored = ckpt.restore(abstract_state, mesh, state_specs)
    ckpt.close()
    check(int(restored.step) == steps, (int(restored.step), steps))
    shutil.rmtree(ckpt_dir)

    stats = devices[0].memory_stats() or {}
    report = {
        "params": n_params, "widths": widths,
        "n_layers": sizes["n_layers"], "batch": sizes["batch"],
        "remat": sizes["remat"], "flash": model.config.flash,
        "kernel_in_step": kernel, "pallas_interpreter_in_step": interpreted,
        "kernel_vs_dense_rel_err": kernel_errors,
        "compile_seconds": round(compile_seconds, 2),
        "compiler_temp_bytes": mem.temp_size_in_bytes,
        "compiler_argument_bytes": mem.argument_size_in_bytes,
        "steps": steps, "state_step": int(restored.step),
        "first_loss": losses[0], "last_loss": losses[-1],
        "train_seconds": round(train_seconds, 2),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }
    return report, {"params": restored.params, "model_kw": model_kw,
                    "step": steps}


# -- serve ---------------------------------------------------------------------


def reference_logits(cfg, params, tokens):
    """Plain float32 forward of the decoder for ONE sequence: no kernel, no
    K/V cache, no bf16, every matmul at ``highest`` precision. tokens (S,)
    -> logits (S, V); row t is the distribution of token t + 1."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    S = tokens.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g

    def layer(x, bp):
        h = norm(x, bp["ln1"])
        qkv = jnp.einsum("sd,dthe->sthe", h, bp["wqkv"], precision=hi) \
            + bp["bqkv"]
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        s = jnp.einsum("she,the->hst", q, k, precision=hi) \
            / math.sqrt(cfg.head_dim)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = jnp.einsum("hst,the->she", w, v, precision=hi)
        x = x + jnp.einsum("she,hed->sd", a, bp["wo"], precision=hi) + bp["bo"]
        h = norm(x, bp["ln2"])
        f = jax.nn.gelu(
            jnp.einsum("sd,df->sf", h, bp["win"], precision=hi) + bp["bin"])
        x = x + jnp.einsum("sf,fd->sd", f, bp["wout"], precision=hi) \
            + bp["bout"]
        return x, None

    x = params["embed"][tokens] + params["pos"][:S]
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return jnp.einsum("sd,dv->sv", norm(x, params["lnf"]), params["head"],
                      precision=hi)


def _check_greedy(art, prompts, results, pad_to: int) -> dict:
    """Every served token against the reference, teacher-forced."""
    import jax
    import numpy as np

    ref = jax.jit(lambda p, t: reference_logits(art.model.config, p, t))
    exact = near = 0
    worst_gap = 0.0
    for prompt, result in zip(prompts, results):
        served = result["tokens"]
        seq = list(prompt) + served
        padded = np.zeros((-(-len(seq) // pad_to) * pad_to,), np.int32)
        padded[:len(seq)] = seq
        logits = np.asarray(ref(art.params, padded))
        check(np.isfinite(logits[:len(seq)]).all(),
              "reference logits not finite")
        for i, tok in enumerate(served):
            row = logits[len(prompt) - 1 + i]
            gap = float(row.max() - row[tok])
            if int(row.argmax()) == tok:
                exact += 1
            else:
                check(gap < NEAR_TIE,
                      f"prompt of {len(prompt)} tokens: served token {i} is "
                      f"{tok}, the float32 reference picks "
                      f"{int(row.argmax())} and rates the served one "
                      f"{gap:.4f} lower (band {NEAR_TIE})")
                near += 1
                worst_gap = max(worst_gap, gap)
    return {"tokens_checked": exact + near, "tokens_exact": exact,
            "tokens_near_tie": near, "worst_near_tie_gap": round(worst_gap, 5)}


def serve_phase(sizes: dict, workdir: str, handoff: dict) -> dict:
    import numpy as np
    from edl_tpu.runtime.export import (load_inference_model,
                                        save_inference_model)
    from edl_tpu.serving import LMServingConfig, LMServingReplica

    model_kw, step = handoff["model_kw"], handoff["step"]
    art_dir = os.path.join(workdir, "artifact")
    # pop: the trained copy leaves the device before the replica loads its own
    save_inference_model(art_dir, "transformer", handoff.pop("params"),
                         config=model_kw, step=step)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, model_kw["vocab_size"], size=n).tolist()
               for n in sizes["prompt_lens"]]
    n_new = sizes["max_new_tokens"]

    t0 = time.perf_counter()
    replica = LMServingReplica(LMServingConfig(
        model_dir=art_dir, batch_buckets=sizes["batch_buckets"],
        seq_buckets=sizes["seq_buckets"], kv_blocks=sizes["kv_blocks"],
        kv_block_tokens=sizes["kv_block_tokens"], port=0, name="chip-smoke",
        request_timeout_s=600.0,
    )).start()
    start_seconds = time.perf_counter() - t0
    try:
        cache_before = replica.jit_cache_size()

        def generate(prompt):
            req = urllib.request.Request(
                replica.url + "/generate",
                data=json.dumps({"prompt": prompt,
                                 "max_new_tokens": n_new}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=600) as resp:
                return json.loads(resp.read())

        t0 = time.perf_counter()
        # one stream alone (batch bucket 1), then the rest at once: they
        # join and leave the decode batch token by token
        results = [generate(prompts[0])]
        with ThreadPoolExecutor(max_workers=len(prompts)) as pool:
            results += list(pool.map(generate, prompts[1:]))
        traffic_seconds = time.perf_counter() - t0
        cache_after = replica.jit_cache_size()
        status = replica.status()
    finally:
        replica.stop()

    for prompt, result in zip(prompts, results):
        check(len(result["tokens"]) == n_new
              and result["finish_reason"] == "length"
              and result["prompt_tokens"] == len(prompt)
              and result["model_step"] == step, (len(prompt), result))
    check(cache_before == 0 and cache_after == 0,
          f"jit dispatch cache grew under traffic: {cache_before} -> "
          f"{cache_after}; a prefill or decode step went through jit, not "
          f"AOT")
    check(status["completed"] == len(prompts) and not status["rejected"],
          status)
    check(status["kv"]["used_blocks"] == 0, status["kv"])

    greedy = _check_greedy(load_inference_model(art_dir), prompts, results,
                           pad_to=sizes["seq_buckets"][0])
    shutil.rmtree(art_dir)
    return {
        "requests": len(prompts), "prompt_lens": list(sizes["prompt_lens"]),
        "tokens_each": n_new,
        "executables": 2 * len(sizes["batch_buckets"])
        * len(sizes["seq_buckets"]),
        "start_seconds": round(start_seconds, 2),
        "traffic_seconds": round(traffic_seconds, 2),
        "jit_cache_before": cache_before, "jit_cache_after": cache_after,
        "tokens_generated": status["tokens_generated"], **greedy,
    }


# -- rescale (four chips) ------------------------------------------------------


def _placement(state) -> dict:
    """Where the live state sits: device ids holding the biggest parameter
    and the biggest optimizer-moment leaf, and the shard shape of each."""
    import jax

    def biggest(tree):
        return max((x for x in jax.tree_util.tree_leaves(tree) if x.ndim),
                   key=lambda x: x.size)

    out = {}
    for name, leaf in (("param", biggest(state.params)),
                       ("moment", biggest(state.opt_state))):
        shards = leaf.addressable_shards
        out[name] = {"devices": sorted(s.device.id for s in shards),
                     "shape": list(leaf.shape),
                     "shard_shape": list(shards[0].data.shape)}
    return out


def _follow(peer, epoch: int, stop: threading.Event) -> None:
    """A registered second trainer's side of the rendezvous protocol: sync
    each epoch it observes, heartbeat in between (bench_rescale.py's
    joiner)."""
    while not stop.is_set():
        reply = peer.sync(epoch, timeout=5.0)
        if reply.get("ok"):
            break
        epoch = reply.get("epoch", epoch)
    while not stop.is_set():
        beat = peer.heartbeat()
        if beat.get("ok") and beat["epoch"] != epoch:
            epoch = beat["epoch"]
            peer.sync(epoch, timeout=5.0)
        time.sleep(0.1)


def _elastic_run(model, sizes: dict, workdir: str, devices, elastic: bool):
    """One ElasticWorker run over the same shards; ``elastic`` adds the
    4 -> 2 -> 4 membership change. Returns (worker, placements by step,
    restore sources)."""
    from edl_tpu.coordinator.inprocess import InProcessCoordinator
    from edl_tpu.obs.tracing import Tracer
    from edl_tpu.runtime import (ElasticConfig, ElasticWorker,
                                 SyntheticShardSource)
    from edl_tpu.runtime.train_loop import TrainerConfig

    half = devices[:len(devices) // 2]
    placements = {}
    tracer = Tracer(component="chip-smoke")
    coord = InProcessCoordinator(task_lease_sec=600.0,
                                 heartbeat_ttl_sec=600.0)
    coord.add_tasks(_shards("rescale", sizes))
    worker = ElasticWorker(
        model, coord.client("trainer-0"),
        SyntheticShardSource(model, batch_size=sizes["batch"],
                             batches_per_shard=sizes["batches_per_shard"]),
        ElasticConfig(
            checkpoint_dir=os.path.join(workdir, "ckpt"),
            checkpoint_interval=10**6, heartbeat_interval=0.0,
            rescale_barrier_timeout=120.0,
            trainer=TrainerConfig(
                optimizer="adam", learning_rate=sizes["learning_rate"],
                shard_opt_state=True, seed=SEED),
            step_callback=lambda step, state: placements.__setitem__(
                step, _placement(state)),
        ),
        # two trainers hold the whole host, one holds half of it
        device_planner=lambda world: devices if world >= 2 or not elastic
        else half,
        tracer=tracer,
    )
    stop = threading.Event()
    threads = []

    def join(name):
        peer = coord.client(name)
        epoch = peer.register()["epoch"]
        peer_stop = threading.Event()
        t = threading.Thread(target=_follow, args=(peer, epoch, peer_stop),
                             daemon=True)
        t.start()
        threads.append((t, peer_stop))
        return peer, t, peer_stop

    def wait_for(cond, what):
        deadline = time.monotonic() + 600.0
        while not cond():
            if stop.is_set():
                return False
            if time.monotonic() > deadline:
                raise RuntimeError(f"rescale phase stuck waiting for {what}")
            time.sleep(0.02)
        return True

    errors = []

    def control_plane(peer, peer_thread, peer_stop):
        try:
            leg = sizes["leg_steps"]
            if not wait_for(lambda: worker.steps_done >= leg, "leg 1"):
                return
            peer_stop.set()
            peer_thread.join(timeout=30)
            peer.leave()  # graceful leave: 4 -> 2 chips
            if not wait_for(lambda: len(worker.rescales) >= 1, "4 -> 2"):
                return
            base = worker.steps_done
            if not wait_for(lambda: worker.steps_done >= base + leg, "leg 2"):
                return
            join("trainer-2")  # re-join: 2 -> 4 chips
        except Exception as e:  # reported by the caller after run() returns
            errors.append(e)

    if elastic:
        # registered before the worker, so its first mesh is the whole host
        control = threading.Thread(target=control_plane,
                                   args=join("trainer-1"), daemon=True)
        control.start()
    try:
        worker.run()
    finally:
        stop.set()
        for t, evt in threads:
            evt.set()
        if elastic:
            control.join(timeout=30)
        for t, _ in threads:
            t.join(timeout=30)
    if errors:
        raise errors[0]
    sources = [s.attrs.get("source") for s in tracer.spans
               if s.name == "restore"]
    return worker, placements, sources


def rescale_phase(widths: dict, sizes: dict, workdir: str, devices) -> dict:
    n = len(devices)
    ids = sorted(d.id for d in devices)
    model, _ = _lm(widths, sizes)

    t0 = time.perf_counter()
    elastic_dir = os.path.join(workdir, "elastic")
    worker, placements, sources = _elastic_run(model, sizes, elastic_dir,
                                               devices, elastic=True)
    shutil.rmtree(elastic_dir)
    elastic_seconds = time.perf_counter() - t0
    t0 = time.perf_counter()
    static_dir = os.path.join(workdir, "static")
    static, static_placements, _ = _elastic_run(model, sizes, static_dir,
                                                devices, elastic=False)
    shutil.rmtree(static_dir)
    static_seconds = time.perf_counter() - t0

    worlds = [(r.from_world, r.to_world) for r in worker.rescales]
    layouts = [r.layout for r in worker.rescales]
    check(worlds == [(2, 1), (1, 2)], worker.rescales)
    check(layouts == [{"data": n // 2}, {"data": n}], layouts)
    check(not static.rescales, static.rescales)
    # each rescale restored the checkpoint (not a fresh init), and the
    # state's step counter went on from it: one distinct value a step
    check(sources == ["init", "blob", "blob"], sources)
    steps = sorted(placements)
    check(steps == list(range(1, len(worker.losses) + 1)), steps)
    check(_finite(worker.losses) and _finite(static.losses),
          (worker.losses, static.losses))

    # parameters on, and optimizer shards split over, every device of the
    # mesh of the moment: 4, then 2, then 4 again
    legs = []
    for lo, hi, want in zip(
            [1] + [r.at_step for r in worker.rescales],
            [r.at_step - 1 for r in worker.rescales] + [steps[-1]],
            [ids, ids[:n // 2], ids]):
        for step in range(lo, hi + 1):
            p = placements[step]
            check(p["param"]["devices"] == want, (step, p))
            check(p["param"]["shard_shape"] == p["param"]["shape"], (step, p))
            check(p["moment"]["devices"] == want, (step, p))
            full, shard = math.prod(p["moment"]["shape"]), \
                math.prod(p["moment"]["shard_shape"])
            check(shard * len(want) == full, (step, p))
        legs.append({"steps": [lo, hi], "devices": want,
                     "moment_shard_shape": placements[lo]["moment"]
                     ["shard_shape"]})
    for step, p in static_placements.items():
        check(p["moment"]["devices"] == ids, (step, p))

    check(len(worker.losses) == len(static.losses)
          == sizes["shards"] * sizes["batches_per_shard"],
          (len(worker.losses), len(static.losses)))
    first_rescale = worker.rescales[0].at_step
    deltas = [abs(a - b) for a, b in zip(worker.losses, static.losses)]
    worst = max(deltas[first_rescale - 1:])
    check(worst <= RESCALE_BAND,
          f"loss after the rescale left the static run's band: max |delta| "
          f"{worst:.4f} > {RESCALE_BAND} (elastic {worker.losses}, static "
          f"{static.losses})")
    return {
        "n_layers": sizes["n_layers"], "batch": sizes["batch"],
        "zero1": True,
        "rescales": [{"from_world": r.from_world, "to_world": r.to_world,
                      "at_step": r.at_step, "layout": r.layout,
                      "recovery_seconds": round(r.recovery_seconds, 2),
                      "compile_seconds": round(r.compile_seconds, 2)}
                     for r in worker.rescales],
        "restore_sources": sources, "legs": legs,
        "steps_elastic": len(worker.losses),
        "steps_static": len(static.losses),
        "max_abs_loss_delta_before_rescale":
            round(max(deltas[:first_rescale - 1]), 5),
        "max_abs_loss_delta_after_rescale": round(worst, 5),
        "band": RESCALE_BAND,
        "first_loss": worker.losses[0], "last_loss": worker.losses[-1],
        "static_last_loss": static.losses[-1],
        "elastic_seconds": round(elastic_seconds, 2),
        "static_seconds": round(static_seconds, 2),
    }


# -- main ----------------------------------------------------------------------


def run_phase(name: str, fn, *args):
    log(f"== {name}")
    try:
        out = fn(*args)
    except BaseException:
        print(f"chip_smoke: phase {name!r} FAILED", file=sys.stderr,
              flush=True)
        raise
    report = out[0] if isinstance(out, tuple) else out
    log(f"{name}: {json.dumps(report)}")
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the cross-chip rescale phase and its static "
             "comparison, on a four-chip host")
    args = parser.parse_args()

    device = run_phase("device", device_phase, args.chips)
    import jax

    cache = use_compile_cache()
    shutil.rmtree(WORKDIR, ignore_errors=True)
    os.makedirs(WORKDIR)
    try:
        if args.chips == 4:
            run_phase("rescale", rescale_phase, WIDTHS, RESCALE, WORKDIR,
                      jax.devices())
        else:
            _, handoff = run_phase("train", train_phase, WIDTHS, TRAIN,
                                   WORKDIR, jax.devices())
            run_phase("serve", serve_phase, SERVE, WORKDIR, handoff)
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    log(f"compile cache: {json.dumps(cache)}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
