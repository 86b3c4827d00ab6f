"""Runner ``serve_closed``: LM serving under a closed loop of callers.

The path of ``chip_smoke.serve_phase`` (PR 21): parameters from the seed ->
``save_inference_model`` -> ``LMServingReplica.start()`` with ``port=0`` ->
``POST /generate`` over HTTP. ``clients`` threads each send their next request
of the seeded list when their last reply has arrived. A directed warm-up runs
every batch bucket at every sequence bucket once; then the loop starts, and the
window opens when ``ramp_requests`` replies have come back, so that it sees the
loop in its steady state: requests in flight at its opening count where they
complete inside it, those in flight at its close do not.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time
import urllib.request


def post(url: str, prompt, max_new_tokens: int) -> dict:
    req = urllib.request.Request(
        url + "/generate",
        data=json.dumps({"prompt": prompt,
                         "max_new_tokens": max_new_tokens}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())


def reply_ok(request: dict, reply: dict) -> bool:
    return (len(reply.get("tokens", ())) == request["max_new_tokens"]
            and reply.get("finish_reason") == "length"
            and reply.get("prompt_tokens") == len(request["prompt"]))


def run(cell, compiles):
    import jax
    from edl_tpu.models import transformer
    from edl_tpu.obs.tracing import Tracer
    from edl_tpu.parallel import MeshSpec, build_mesh
    from edl_tpu.runtime.export import (load_inference_model,
                                        save_inference_model)
    from edl_tpu.serving import LMServingConfig, LMServingReplica

    import generate
    import reference
    from cell import Outcome
    from stats import percentile

    w, traffic, log = cell.workload, cell.traffic, cell.log
    if traffic["loop"] != "closed":
        raise ValueError("runner serve_closed needs a closed-loop traffic mix")
    device = cell.devices[0]
    vocab = cell.model_kwargs["vocab_size"]

    # weights: on the device from the seed in one jitted call, in the type
    # the artifact stores (float32), then to the directory the replica loads
    t = time.perf_counter()
    model = transformer.make_model(**cell.model_kwargs)
    mesh = build_mesh(MeshSpec({"data": 1}), [device])
    key = jax.random.PRNGKey(cell.seed % (2**31 - 1))
    params = jax.jit(lambda k: model.init(k, mesh))(key)
    art_dir = os.path.join(cell.workdir, "artifact")
    save_inference_model(art_dir, "transformer", params,
                         config=cell.model_kwargs, step=0)
    del params
    t_weights = time.perf_counter() - t

    t = time.perf_counter()
    tracer = Tracer(component="benchmark", window=500_000)
    replica = LMServingReplica(LMServingConfig(
        model_dir=art_dir, batch_buckets=tuple(w["batch_buckets"]),
        seq_buckets=tuple(w["seq_buckets"]), kv_blocks=w["kv_blocks"],
        kv_block_tokens=w["kv_block_tokens"], port=0, name=cell.name,
        request_timeout_s=600.0), tracer=tracer).start()
    t_start = time.perf_counter() - t
    requests = generate.request_list(traffic, cell.seed, vocab)
    block = generate.base_block(traffic)
    log(f"one block of {len(block)} requests: prompt lengths "
        f"{generate.histogram([p for p, _ in block], [0, 32, 64, 128])}, "
        f"output budgets "
        f"{generate.histogram([o for _, o in block], [0, 16, 32, 64])}, "
        f"{sum(p + o <= w['seq_buckets'][0] for p, o in block)} fit the "
        f"smallest capacity")
    try:
        cache_before = replica.jit_cache_size()

        # directed warm-up: every batch bucket at every sequence bucket
        t = time.perf_counter()
        warm_rng = random.Random(cell.seed)
        lo = 0
        for seq in w["seq_buckets"]:
            plen = lo + max((seq - lo) // 4, 1)
            for b in w["batch_buckets"]:
                group = [{"prompt": [warm_rng.randrange(1, vocab)
                                     for _ in range(plen)],
                          "max_new_tokens": 4} for _ in range(b)]
                threads = [threading.Thread(
                    target=lambda r=r: post(replica.url, r["prompt"], 4))
                    for r in group]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
            lo = seq
        t_warm = time.perf_counter() - t

        # the closed loop
        lock = threading.Lock()
        s = {"next": 0, "done": 0, "open": None, "close": None,
             "wall_open": None, "stop": False}
        records = []  # (index, t_send, t_done, reply or None)

        def client() -> None:
            while True:
                with lock:
                    if s["stop"]:
                        return
                    i = s["next"]
                    s["next"] += 1
                request = requests[i % len(requests)]
                t_send = time.perf_counter()
                try:
                    reply = post(replica.url, request["prompt"],
                                 request["max_new_tokens"])
                except Exception as e:  # counted as failed, never dropped
                    reply = {"error": repr(e)}
                t_done = time.perf_counter()
                with lock:
                    records.append((i, t_send, t_done, reply))
                    s["done"] += 1
                    if s["open"] is None and s["done"] >= traffic["ramp_requests"]:
                        s["open"], s["wall_open"] = t_done, time.time()
                        s["close"] = t_done + cell.seconds
                    if s["close"] is not None and t_done >= s["close"]:
                        s["stop"] = True

        threads = [threading.Thread(target=client, name=f"bench-client-{i}")
                   for i in range(traffic["clients"])]
        for th in threads:
            th.start()
        while s["open"] is None:
            time.sleep(0.01)
        setup_s = s["open"] - cell.t0
        compiles_at_open = compiles["hits"] + compiles["misses"]
        if cell.trace:
            time.sleep(w["trace_after_s"])
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(cell.trace_dir, profiler_options=options)
            time.sleep(w["traced_seconds"])
            jax.profiler.stop_trace()
        time.sleep(max(s["close"] - time.perf_counter(), 0.0))
        wall_close = s["wall_open"] + cell.seconds
        compiles_in_window = (compiles["hits"] + compiles["misses"]
                              - compiles_at_open)
        peak = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
        with lock:
            s["stop"] = True
        for th in threads:  # requests in flight at the close run to their end
            th.join()
        t_drained = time.perf_counter()
        cache_after = replica.jit_cache_size()
        status = replica.status()
    finally:
        replica.stop()
    del replica

    # -- the window --------------------------------------------------------------
    inside = [r for r in records if s["open"] < r[2] <= s["close"]]
    good = [r for r in inside
            if reply_ok(requests[r[0] % len(requests)], r[3])]
    tokens = sum(len(r[3]["tokens"]) for r in good)
    if not good:
        raise RuntimeError(f"no request completed in the window: "
                           f"{[r[3] for r in inside][:3]}")
    per_token_ms = [(r[2] - r[1]) / len(r[3]["tokens"]) * 1e3 for r in good]
    rate = tokens / cell.seconds
    spans = [sp for sp in tracer.spans
             if sp.start >= s["wall_open"] and sp.end <= wall_close]
    log(f"serve: weights {t_weights:.2f} s, replica start {t_start:.2f} s, "
        f"warm-up {t_warm:.2f} s; {len(records)} requests sent, "
        f"{len(inside)} ended in the window ({len(good)} good, {tokens} "
        f"tokens), drain after it {t_drained - s['close']:.2f} s")
    log(f"latency per token: p50 {percentile(per_token_ms, 50):.2f} ms, "
        f"p90 {percentile(per_token_ms, 90):.2f}, max {max(per_token_ms):.2f}")
    log(f"status: {json.dumps(status)}; peak in use at the window's close "
        f"{peak} B")

    # -- checks --------------------------------------------------------------------
    checks = {
        "replies_ok": len(good) == len(inside),
        "no_compile_in_window": compiles_in_window == 0,
        "jit_cache_empty": cache_before == 0 and cache_after == 0,
        "pool_empty": status["kv"]["used_blocks"] == 0,
        "none_rejected": not status["rejected"],
    }
    art = load_inference_model(art_dir)
    chosen = sorted(good)[:w["checked_requests"]]
    greedy = reference.check_greedy(
        art.model.config, art.params,
        [requests[r[0] % len(requests)]["prompt"] for r in chosen],
        [r[3]["tokens"] for r in chosen], pad_to=w["seq_buckets"][0])
    checks["greedy_is_reference"] = greedy["ok"]
    log(f"greedy check: {json.dumps(greedy)}")
    log(f"checks: {checks}")

    return Outcome(
        correct=all(checks.values()), attempted=len(inside),
        failed=len(inside) - len(good),
        end_to_end={"setup_s": setup_s, "serve_tokens_per_s": rate,
                    "latency_per_token_p90_ms": percentile(per_token_ms, 90)},
        memory_peak_bytes=peak,
        values={"tokens_per_s": rate, "requests": float(len(good)),
                "latency_per_token_p90_ms": percentile(per_token_ms, 90)},
        spans=spans)
