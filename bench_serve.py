"""Serving-tier bench: continuous batching vs none, bucket configs, and
rescale-under-traffic — regenerates BENCH_SERVE.json.

Each batching arm runs two phases against one replica:

- **open-loop latency** — requests arrive on a Poisson schedule at a rate
  below single-replica capacity and do NOT slow down when the server lags
  (closed-loop generators hide overload by self-throttling); p50/p99 are
  honest service latencies, not backlog artifacts.
- **burst throughput** — all requests submitted at once; wall-clock to
  drain the queue gives saturated QPS (and QPS/chip). This is where
  continuous batching pays: the same request count collapses into ~N/32
  device dispatches instead of N.

Arms:

- ``batching_on``  — the full bucket ladder + coalesce window.
- ``batching_off`` — bucket ladder (1,), zero coalesce delay: every request
  is its own batch (the naive frontend this package replaces).
- one ``batching_on`` run per bucket configuration (the bucket table).
- ``rescale_under_traffic`` — a 2-replica pool behind the real
  :class:`~edl_tpu.serving.router.Router` (shallowest-queue affinity);
  mid-load a third replica joins (AOT-compiles, then takes traffic) and
  one replica drains out. Every accepted request must resolve: the
  zero-dropped-requests number IS the result.

LM arms (the decode-native tier, same chips):

- ``lm_serving`` — one LMServingReplica, three phases: open-loop Poisson
  *stream* arrivals for honest p50/p99 PER-TOKEN latency (scraped from
  the replica's own `edl_lm_token_latency_seconds` histogram — the bench
  dogfoods the autoscaler's signal path); a continuous-batching burst
  (all streams at once, per-token join/leave); and the same workload
  gang-scheduled in static waves (a wave admits together and the next
  waits for the slowest stream — the pre-continuous-batching baseline).
  Continuous must beat static on tokens/s at equal chips: the paired
  delta is the result. KV-block occupancy and peak are reported from the
  block pool's own stats.
- ``lm_rescale_under_decode`` — a 2-replica LM pool behind the Router;
  mid-decode a pre-compiled third replica joins and one replica is
  removed, its live streams evicted and migrated (prefix-stitched).
  ``dropped_streams`` must be 0 and every stream's token count exact.

CPU-sim caveat (same discipline as the sibling benches): numbers are
generated on the CPU backend with virtual devices, so absolute latency is
meaningless next to a real TPU pod — the comparisons (batching on/off,
bucket shapes, drop counts under rescale) are the portable part.
QPS/chip divides by `jax.device_count()` per the MLPerf-style per-chip
accounting the TPU-pod papers report.
"""

from __future__ import annotations

import os

# Which platform JAX uses is the environment's choice (`make bench-serve` sets
# JAX_PLATFORMS=cpu); this only gives a CPU run its eight virtual devices.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import json
import tempfile
import threading
import time
from typing import Dict, List

import numpy as np

OUT = os.path.join(os.path.dirname(__file__), "BENCH_SERVE.json")

RATE_QPS = 120.0  # below single-replica CPU-sim capacity (~300 QPS)
N_REQUESTS = 360
BURST_REQUESTS = 512
BUCKET_CONFIGS = ((1, 8, 32), (1, 4, 16), (8, 32))

# LM tier: a small transformer the CPU backend decodes in milliseconds —
# per-chip absolute numbers are sim-only, the paired comparisons portable.
LM_MODEL_KW = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
                   d_ff=64, seq_len=64, flash=False)
LM_BATCH_BUCKETS = (1, 4, 8)
LM_SEQ_BUCKETS = (16, 32)
LM_KV_BLOCKS = 256
LM_KV_BLOCK_TOKENS = 8
LM_N_STREAMS = 48          # continuous-vs-static burst size
LM_OPEN_STREAMS = 24       # open-loop per-token-latency phase
LM_STREAM_RATE = 6.0       # Poisson stream arrivals/s, below capacity
LM_RESCALE_STREAMS = 32
LM_RESCALE_NEW_TOKENS = 40


def _export_artifact(directory: str, scale: float = 1.0, step: int = 100):
    import jax

    from edl_tpu.models import fit_a_line
    from edl_tpu.runtime.export import _serving_mesh, save_inference_model

    model = fit_a_line.MODEL
    mesh = _serving_mesh(model)
    params = model.init(jax.random.PRNGKey(0), mesh)
    if scale != 1.0:
        params = jax.tree_util.tree_map(lambda x: x * scale, params)
    save_inference_model(directory, "fit_a_line", params, step=step,
                         versioned=True)


def _requests(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    features = [{"x": rng.standard_normal(13).astype(np.float32)}
                for _ in range(n)]
    # exponential inter-arrivals -> Poisson arrivals at RATE_QPS
    gaps = rng.exponential(1.0 / RATE_QPS, size=n)
    return features, np.cumsum(gaps)


def _percentiles(latencies: List[float]) -> Dict[str, float]:
    arr = np.asarray(latencies)
    return {
        "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 3),
        "mean_ms": round(float(arr.mean()) * 1e3, 3),
    }


def _open_loop(submit, n: int, seed: int = 0):
    """Fire ``n`` requests on the open-loop schedule; returns
    ([(future, record)], submit_errors). Completion time is stamped by a
    done-callback AT resolution — measuring at collection time would
    charge early requests for the whole submission window."""
    features, arrivals = _requests(n, seed)
    t0 = time.monotonic()
    futures, errors = [], 0
    for feat, due in zip(features, arrivals):
        delay = t0 + due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            t_submit = time.monotonic()
            fut = submit(feat)
            record = {"t_submit": t_submit, "t_done": None}
            fut.add_done_callback(
                lambda f, r=record: r.__setitem__("t_done", time.monotonic())
            )
            futures.append((fut, record))
        except Exception:  # edl: noqa[EDL005] overload rejections are a measured outcome of the open-loop arm, reported as submit errors in the results
            errors += 1
    return futures, errors


def run_arm(name: str, buckets, max_delay_s: float) -> Dict:
    import jax

    from edl_tpu.serving import ServingConfig, ServingReplica

    with tempfile.TemporaryDirectory() as td:
        _export_artifact(td)
        replica = ServingReplica(ServingConfig(
            model_dir=td, buckets=buckets, max_batch_delay_s=max_delay_s,
            queue_capacity=4096, name=f"bench-{name}",
        )).start()
        try:
            # phase 1: open-loop latency below capacity
            futures, submit_errors = _open_loop(replica.submit, N_REQUESTS)
            latencies = []
            failed = 0
            for fut, record in futures:
                try:
                    fut.result(timeout=60)
                    latencies.append(record["t_done"] - record["t_submit"])
                except Exception:  # edl: noqa[EDL005] per-request failures are a measured outcome, reported as the arm's failed count
                    failed += 1
            # phase 2: burst throughput — everything enqueued at once
            feats, _ = _requests(BURST_REQUESTS, seed=2)
            t_burst = time.monotonic()
            burst = [replica.submit(f) for f in feats]
            for fut in burst:
                fut.result(timeout=120)
            burst_wall = time.monotonic() - t_burst
            status = replica.status()
        finally:
            replica.stop()
    qps = BURST_REQUESTS / burst_wall if burst_wall > 0 else 0.0
    chips = jax.device_count()
    return {
        "buckets": list(buckets),
        "max_batch_delay_ms": max_delay_s * 1e3,
        "open_loop": {
            "requests": N_REQUESTS,
            "offered_qps": RATE_QPS,
            "completed": len(latencies),
            "failed": failed + submit_errors,
            "latency": _percentiles(latencies),
        },
        "burst": {
            "requests": BURST_REQUESTS,
            "wall_seconds": round(burst_wall, 3),
            "qps": round(qps, 1),
            "qps_per_chip": round(qps / chips, 2),
        },
        "bucket_hits": status["bucket_hits"],
        "batches": sum(status["bucket_hits"].values()),
        "mean_batch_size": round(
            status["completed"] / max(1, sum(status["bucket_hits"].values())), 2
        ),
    }


def run_rescale_arm() -> Dict:
    from edl_tpu.serving import Router, ServingConfig, ServingReplica

    buckets = (1, 8, 32)
    with tempfile.TemporaryDirectory() as td:
        _export_artifact(td)
        made = []

        def make(i):
            replica = ServingReplica(ServingConfig(
                model_dir=td, buckets=buckets, max_batch_delay_s=0.005,
                queue_capacity=4096, name=f"bench-rescale-{i}",
            )).start()
            made.append(replica)
            return replica

        # the real control-plane Router (shallowest-queue affinity +
        # overload failover), not the round-robin stand-in it replaced
        pool = Router([make(0), make(1)], name="bench-rescale")
        timeline = []

        def rescale_script():
            # grow mid-traffic: the new replica AOT-compiles its buckets
            # BEFORE joining the pool (the warm-join discipline)
            time.sleep(0.4)
            pool.add(make(2))
            timeline.append("t+0.4s grow 2->3 (replica pre-compiled)")
            # shrink mid-traffic: remove from routing, then drain — every
            # request already accepted by the leaving replica completes
            time.sleep(0.4)
            leaving = pool.remove("bench-rescale-0")
            timeline.append("t+0.8s shrink 3->2 (drained, zero aborts)")
            leaving.stop(drain=True)

        script = threading.Thread(target=rescale_script)
        script.start()
        t_start = time.monotonic()
        futures, submit_errors = _open_loop(pool.submit, N_REQUESTS, seed=1)
        latencies, dropped = [], 0
        for fut, record in futures:
            try:
                fut.result(timeout=60)
                latencies.append(record["t_done"] - record["t_submit"])
            except Exception:  # edl: noqa[EDL005] a dropped in-flight request is THE metric of the rescale arm (must be zero); counted, and non-zero fails the bench exit code
                dropped += 1
        wall = time.monotonic() - t_start
        script.join()
        completed_per_replica = {}
        for replica in made:
            status = replica.status()
            completed_per_replica[status["name"]] = status["completed"]
            replica.stop()
    return {
        "buckets": list(buckets),
        "requests": N_REQUESTS,
        "accepted": len(futures),
        "submit_rejections": submit_errors,
        "completed": len(latencies),
        "dropped_in_flight": dropped,
        "timeline": timeline,
        "completed_per_replica": completed_per_replica,
        "achieved_qps": round(len(latencies) / wall, 1) if wall else 0.0,
        "latency": _percentiles(latencies),
    }


# -- the LM tier ---------------------------------------------------------------


def _export_lm_artifact(directory: str) -> None:
    import jax

    from edl_tpu.models import transformer
    from edl_tpu.runtime.export import _serving_mesh, save_inference_model

    model = transformer.make_model(**LM_MODEL_KW)
    mesh = _serving_mesh(model)
    params = model.init(jax.random.PRNGKey(0), mesh)
    save_inference_model(directory, "transformer", params,
                         config=LM_MODEL_KW, step=100)


def _lm_workload(n: int, seed: int = 0):
    """(prompt, max_new_tokens) pairs with varied prompt lengths AND
    varied budgets — length variance is exactly what static batching pays
    for (every wave waits for its slowest stream)."""
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(1, 60, size=int(rng.integers(3, 9))),
         int(rng.integers(4, 21)))
        for _ in range(n)
    ]


def run_lm_arm() -> Dict:
    import jax

    from edl_tpu.serving import LMServingConfig, LMServingReplica
    from edl_tpu.serving.autoscale import histogram_quantile, scrape_lm_signal

    with tempfile.TemporaryDirectory() as td:
        _export_lm_artifact(td)
        replica = LMServingReplica(LMServingConfig(
            model_dir=td, batch_buckets=LM_BATCH_BUCKETS,
            seq_buckets=LM_SEQ_BUCKETS, kv_blocks=LM_KV_BLOCKS,
            kv_block_tokens=LM_KV_BLOCK_TOKENS, port=0, name="bench-lm",
        )).start()
        try:
            # phase 1: open-loop Poisson STREAM arrivals below capacity;
            # per-token p50/p99 scraped from the replica's own histogram
            # (the same family the LM autoscaler scales on)
            rng = np.random.default_rng(3)
            arrivals = np.cumsum(
                rng.exponential(1.0 / LM_STREAM_RATE, size=LM_OPEN_STREAMS)
            )
            t0 = time.monotonic()
            handles = []
            for i, due in enumerate(arrivals):
                delay = t0 + due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                handles.append(replica.submit(
                    rng.integers(1, 60, size=3 + i % 6), max_new_tokens=8,
                ))
            for h in handles:
                h.result(timeout=120)
            sig = scrape_lm_signal(replica.url)
            p50 = histogram_quantile(sig.token_latency_buckets, 0.5)
            p99 = histogram_quantile(sig.token_latency_buckets, 0.99)
            open_loop = {
                "streams": LM_OPEN_STREAMS,
                "offered_streams_per_s": LM_STREAM_RATE,
                "tokens": int(sig.token_count),
                "token_latency": {
                    "p50_ms": round(p50 * 1e3, 3) if p50 is not None else None,
                    "p99_ms": round(p99 * 1e3, 3) if p99 is not None else None,
                },
            }

            # phase 2: continuous batching — every stream admitted at
            # once, membership changes per token
            work = _lm_workload(LM_N_STREAMS, seed=4)
            t_burst = time.monotonic()
            handles = [replica.submit(p, max_new_tokens=m) for p, m in work]
            occupancy_peak_window = replica.status()["kv"]["occupancy"]
            cont_tokens = sum(len(h.result(timeout=300)["tokens"])
                              for h in handles)
            cont_wall = time.monotonic() - t_burst

            # phase 3: the SAME workload gang-scheduled in static waves —
            # a wave admits together and the next waits for its slowest
            # stream (the pre-continuous-batching baseline)
            wave = LM_BATCH_BUCKETS[-1]
            t_static = time.monotonic()
            static_tokens = 0
            for i in range(0, len(work), wave):
                hs = [replica.submit(p, max_new_tokens=m)
                      for p, m in work[i:i + wave]]
                static_tokens += sum(len(h.result(timeout=300)["tokens"])
                                     for h in hs)
            static_wall = time.monotonic() - t_static
            kv = replica.status()["kv"]
        finally:
            replica.stop()
    chips = jax.device_count()
    cont_tps = cont_tokens / cont_wall if cont_wall > 0 else 0.0
    static_tps = static_tokens / static_wall if static_wall > 0 else 0.0
    return {
        "model": {k: LM_MODEL_KW[k]
                  for k in ("d_model", "n_layers", "n_heads", "seq_len")},
        "batch_buckets": list(LM_BATCH_BUCKETS),
        "seq_buckets": list(LM_SEQ_BUCKETS),
        "open_loop": open_loop,
        "continuous": {
            "streams": LM_N_STREAMS,
            "tokens": cont_tokens,
            "wall_seconds": round(cont_wall, 3),
            "tokens_per_s": round(cont_tps, 1),
            "tokens_per_s_per_chip": round(cont_tps / chips, 2),
        },
        "static_waves": {
            "streams": LM_N_STREAMS,
            "wave_size": wave,
            "tokens": static_tokens,
            "wall_seconds": round(static_wall, 3),
            "tokens_per_s": round(static_tps, 1),
            "tokens_per_s_per_chip": round(static_tps / chips, 2),
        },
        "continuous_speedup": round(cont_tps / static_tps, 2)
        if static_tps else None,
        "kv": {
            "n_blocks": kv["n_blocks"],
            "block_tokens": kv["block_tokens"],
            "peak_blocks_used": kv["peak_blocks_used"],
            "peak_occupancy": round(
                kv["peak_blocks_used"] / kv["n_blocks"], 4
            ),
            "burst_occupancy": occupancy_peak_window,
        },
    }


def run_lm_rescale_arm() -> Dict:
    from edl_tpu.serving import LMServingConfig, LMServingReplica, Router

    # the 64-token capacity bucket keeps streams decoding long enough
    # that the pool provably changes size mid-decode
    seq_buckets = (16, 64)
    with tempfile.TemporaryDirectory() as td:
        _export_lm_artifact(td)

        def make_lm(i):
            return LMServingReplica(LMServingConfig(
                model_dir=td, batch_buckets=LM_BATCH_BUCKETS,
                seq_buckets=seq_buckets, kv_blocks=LM_KV_BLOCKS,
                kv_block_tokens=LM_KV_BLOCK_TOKENS, name=f"bench-lm-{i}",
            )).start()

        # the joining replica compiles BEFORE the traffic starts: rescale
        # measures membership change, not compile time (warm-join)
        rep_a, rep_b, rep_c = make_lm(0), make_lm(1), make_lm(2)
        router = Router([rep_a, rep_b], name="bench-lm-rescale")
        rng = np.random.default_rng(5)
        t_start = time.monotonic()
        handles = [
            router.generate_async(rng.integers(1, 60, size=int(n)),
                                  max_new_tokens=LM_RESCALE_NEW_TOKENS)
            for n in rng.integers(3, 9, size=LM_RESCALE_STREAMS)
        ]
        timeline = []
        time.sleep(0.15)
        router.add(rep_c)
        timeline.append("t+0.15s grow 2->3 (replica pre-compiled)")
        time.sleep(0.15)
        removed = router.remove(rep_a.config.name)
        timeline.append(
            "t+0.30s shrink 3->2 (streams evicted + migrated mid-decode)"
        )
        removed.stop()
        results = [h.result(timeout=300) for h in handles]
        wall = time.monotonic() - t_start
        stats = router.stats()
        per_replica = {r.config.name: r.status()["completed"]
                       for r in (rep_a, rep_b, rep_c)}
        for r in (rep_b, rep_c):
            r.stop()
    tokens = sum(len(r["tokens"]) for r in results)
    exact = all(len(r["tokens"]) == LM_RESCALE_NEW_TOKENS for r in results)
    return {
        "streams": LM_RESCALE_STREAMS,
        "max_new_tokens": LM_RESCALE_NEW_TOKENS,
        "timeline": timeline,
        "dropped_streams": stats["dropped_streams"],
        "migrations": stats["migrations"],
        "migrated_tokens": stats["migrated_tokens"],
        "tokens_generated": tokens,
        "exact_token_accounting": exact,
        "completed_per_replica": per_replica,
        "tokens_per_s": round(tokens / wall, 1) if wall else 0.0,
    }


def main() -> int:
    import jax

    results = {
        "bench": "serving tier: continuous batching + rescale-under-traffic",
        "env": {
            "backend": jax.default_backend(),
            "devices": jax.device_count(),
            "note": ("CPU-sim: absolute latencies are not TPU numbers; "
                     "batching-on/off deltas, bucket shapes and drop "
                     "counts are the portable comparisons"),
        },
        "offered_load_qps": RATE_QPS,
        "arms": {},
        "bucket_table": [],
    }
    print(f"== batching on (buckets {BUCKET_CONFIGS[0]}) ==")
    on = run_arm("on", BUCKET_CONFIGS[0], 0.005)
    print(json.dumps({**on["open_loop"]["latency"], **on["burst"]}))
    results["arms"]["batching_on"] = on
    print("== batching off (bucket ladder (1,), no coalesce) ==")
    off = run_arm("off", (1,), 0.0)
    print(json.dumps({**off["open_loop"]["latency"], **off["burst"]}))
    results["arms"]["batching_off"] = off
    for buckets in BUCKET_CONFIGS:
        print(f"== bucket config {buckets} ==")
        arm = run_arm(f"buckets-{'-'.join(map(str, buckets))}", buckets, 0.005)
        results["bucket_table"].append(arm)
    print("== rescale under traffic ==")
    rescale = run_rescale_arm()
    print(json.dumps({k: rescale[k] for k in
                      ("accepted", "completed", "dropped_in_flight")}))
    results["arms"]["rescale_under_traffic"] = rescale
    print("== LM serving: continuous vs static batching ==")
    lm = run_lm_arm()
    print(json.dumps({
        "continuous_tokens_per_s": lm["continuous"]["tokens_per_s"],
        "static_tokens_per_s": lm["static_waves"]["tokens_per_s"],
        "speedup": lm["continuous_speedup"],
        "token_p99_ms": lm["open_loop"]["token_latency"]["p99_ms"],
    }))
    results["arms"]["lm_serving"] = lm
    print("== LM rescale under decode ==")
    lm_rescale = run_lm_rescale_arm()
    print(json.dumps({k: lm_rescale[k] for k in
                      ("dropped_streams", "migrations",
                       "exact_token_accounting")}))
    results["arms"]["lm_rescale_under_decode"] = lm_rescale
    with open(OUT, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"wrote {OUT}")
    failures = []
    if rescale["dropped_in_flight"] != 0:
        failures.append("batch rescale dropped in-flight requests")
    if lm["continuous"]["tokens_per_s"] <= lm["static_waves"]["tokens_per_s"]:
        failures.append("continuous batching did not beat static waves")
    if lm_rescale["dropped_streams"] != 0:
        failures.append("LM rescale dropped streams")
    if not lm_rescale["exact_token_accounting"]:
        failures.append("LM rescale token accounting inexact")
    for f in failures:
        print(f"FAILED: {f}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
