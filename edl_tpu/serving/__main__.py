"""The serve smokes: ``python -m edl_tpu.serving`` (``make serve-smoke``)
and ``python -m edl_tpu.serving lm`` (``make serve-lm-smoke``).

Boots the serving tier end to end the way a pod would see it: export a
real artifact (versioned layout, atomic ``LATEST``), start a
:class:`ServingReplica` with its HTTP frontend, push requests through
``POST /predict`` over real sockets, then scrape `/metrics` and assert

- the p99-bearing latency family and the queue-depth family are present
  (the two signals the autoscaler scales the tier on),
- per-bucket dispatch and model-step families are exported,
- the AOT contract held: every bucket executable was compiled before the
  first request and the jit dispatch cache is still empty,
- a model-version swap landed mid-traffic with zero dropped requests.

The ``lm`` mode does the same for the LM tier: export a small transformer,
boot an :class:`LMServingReplica`, decode a prompt batch through ``POST
/generate`` concurrently (continuous batching with per-token membership),
then assert zero dropped streams, exact token accounting, the LM metric
families, a fully-recycled KV block pool, and the empty-dispatch-cache
AOT contract across BOTH phase executables.

Exit 0 only when all of it holds — the deploy gates for the serving
path, chained into ``make verify``.
"""

from __future__ import annotations

import os
import sys

#: a scrape missing any of these means the serving telemetry regressed —
#: the first two are the autoscaler's inputs.
REQUIRED_FAMILIES = (
    "edl_serve_request_latency_seconds",
    "edl_serve_queue_depth",
    "edl_serve_requests_total",
    "edl_serve_batches_total",
    "edl_serve_model_step",
    "edl_serve_model_swaps_total",
)

#: the LM tier's telemetry contract — the first two are the LM
#: autoscaler's inputs, the KV families the router's affinity source.
REQUIRED_LM_FAMILIES = (
    "edl_lm_token_latency_seconds",
    "edl_lm_kv_occupancy",
    "edl_lm_tokens_total",
    "edl_lm_kv_blocks_free",
    "edl_lm_prefill_batch_size",
    "edl_lm_decode_batch_size",
    "edl_lm_decode_steps_total",
)

N_REQUESTS = 48
N_STREAMS = 12
MAX_NEW_TOKENS = 8


def _eight_host_devices() -> None:
    # Which platform JAX uses is the environment's choice (`make
    # serve-smoke` sets JAX_PLATFORMS=cpu); this only gives a CPU run its
    # eight virtual devices, and must happen BEFORE jax is imported.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def main_lm() -> int:
    _eight_host_devices()

    import json
    import tempfile
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import numpy as np

    from edl_tpu.models import transformer
    from edl_tpu.obs.http import scrape_metrics
    from edl_tpu.obs.metrics import parse_prometheus
    from edl_tpu.runtime.export import _serving_mesh, save_inference_model
    from edl_tpu.serving import LMServingConfig, LMServingReplica

    model_kw = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
                    d_ff=64, seq_len=64, flash=False)
    model = transformer.make_model(**model_kw)
    mesh = _serving_mesh(model)
    params = model.init(jax.random.PRNGKey(0), mesh)

    with tempfile.TemporaryDirectory() as td:
        art_dir = os.path.join(td, "artifact")
        save_inference_model(art_dir, "transformer", params,
                             config=model_kw, step=100)
        replica = LMServingReplica(LMServingConfig(
            model_dir=art_dir, batch_buckets=(1, 4), seq_buckets=(16, 32),
            kv_blocks=32, kv_block_tokens=8, port=0, name="smoke-lm",
        )).start()
        try:
            cache0 = replica.jit_cache_size()
            rng = np.random.default_rng(0)

            def one_stream(i: int):
                body = json.dumps({
                    "prompt": rng.integers(1, 60, size=3 + i % 9).tolist(),
                    "max_new_tokens": MAX_NEW_TOKENS,
                }).encode()
                req = urllib.request.Request(
                    replica.url + "/generate", data=body,
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=30) as resp:
                    return json.loads(resp.read())

            # concurrent submission: streams join and leave the decode
            # batch at step boundaries, not request boundaries
            with ThreadPoolExecutor(max_workers=6) as pool:
                results = list(pool.map(one_stream, range(N_STREAMS)))
            status = replica.status()
            text = scrape_metrics(replica.url)
            families = parse_prometheus(text)
        finally:
            replica.stop()

    failures = []
    short = [r for r in results
             if len(r["tokens"]) != MAX_NEW_TOKENS
             or r["finish_reason"] != "length"]
    if short:
        failures.append(f"{len(short)}/{N_STREAMS} streams returned wrong "
                        f"token counts: {short[:2]}")
    missing = [f for f in REQUIRED_LM_FAMILIES if f not in families]
    if missing:
        failures.append(f"missing LM metric families: {missing}")
    cache_now = replica.jit_cache_size()
    if cache0 not in (0, None) or cache_now not in (0, None):
        failures.append(
            f"jit dispatch cache not empty (start={cache0}, end={cache_now})"
            " — a prefill/decode executable was dispatched through jit, "
            "not AOT"
        )
    if status["completed"] != N_STREAMS or status["rejected"]:
        failures.append(f"dropped/rejected streams: {status}")
    kv = status["kv"]
    if kv["used_blocks"] != 0 or kv["free_blocks"] != kv["n_blocks"]:
        failures.append(f"KV block pool leaked: {kv}")
    expected = N_STREAMS * MAX_NEW_TOKENS
    if status["tokens_generated"] != expected:
        failures.append(f"token accounting off: generated "
                        f"{status['tokens_generated']}, expected {expected}")

    if failures:
        print("serve-lm-smoke FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print(
        f"serve-lm-smoke OK: {N_STREAMS} streams x {MAX_NEW_TOKENS} tokens "
        f"over HTTP /generate, 0 dropped, KV pool fully recycled "
        f"(peak {kv['peak_blocks_used']}/{kv['n_blocks']} blocks), "
        f"jit dispatch cache empty across prefill+decode, "
        f"{len(REQUIRED_LM_FAMILIES)} required families present"
    )
    return 0


def main() -> int:
    _eight_host_devices()

    import json
    import tempfile
    import time
    import urllib.request

    import jax
    import numpy as np

    from edl_tpu.models import fit_a_line
    from edl_tpu.obs.http import scrape_metrics
    from edl_tpu.obs.metrics import parse_prometheus
    from edl_tpu.runtime.export import _serving_mesh, save_inference_model
    from edl_tpu.serving import ServingConfig, ServingReplica

    model = fit_a_line.MODEL
    mesh = _serving_mesh(model)
    params = model.init(jax.random.PRNGKey(0), mesh)

    with tempfile.TemporaryDirectory() as td:
        art_dir = os.path.join(td, "artifact")
        save_inference_model(art_dir, "fit_a_line", params, step=100,
                             versioned=True)
        replica = ServingReplica(ServingConfig(
            model_dir=art_dir, buckets=(1, 4, 16), max_batch_delay_s=0.002,
            port=0, version_poll_s=0.05, name="smoke-serve",
        )).start()
        try:
            cache0 = replica.jit_cache_size()
            rng = np.random.default_rng(0)
            ok = 0
            for i in range(N_REQUESTS):
                body = json.dumps({"features": {
                    "x": rng.standard_normal(13).tolist()
                }}).encode()
                req = urllib.request.Request(
                    replica.url + "/predict", data=body,
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=10) as resp:
                    reply = json.loads(resp.read())
                if np.isfinite(np.asarray(reply["outputs"])).all():
                    ok += 1
                if i == N_REQUESTS // 2:
                    # rolling swap mid-traffic: publish a newer artifact and
                    # keep the requests flowing
                    save_inference_model(
                        art_dir, "fit_a_line",
                        jax.tree_util.tree_map(lambda x: x * 1.5, params),
                        step=200, versioned=True,
                    )
            deadline = time.monotonic() + 5
            while (replica.status()["swaps"] < 1
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            status = replica.status()
            text = scrape_metrics(replica.url)
            families = parse_prometheus(text)
        finally:
            replica.stop()

    failures = []
    if ok != N_REQUESTS:
        failures.append(f"{N_REQUESTS - ok}/{N_REQUESTS} requests failed")
    missing = [f for f in REQUIRED_FAMILIES if f not in families]
    if missing:
        failures.append(f"missing metric families: {missing}")
    cache_now = replica.jit_cache_size()
    if cache0 not in (0, None) or cache_now not in (0, None):
        failures.append(
            f"jit dispatch cache not empty (start={cache0}, end={cache_now})"
            " — a bucket executable was dispatched through jit, not AOT"
        )
    if status["swaps"] < 1 or status["model_step"] != 200:
        failures.append(f"model swap did not land: {status}")
    if status["completed"] != N_REQUESTS or status["errors"]:
        failures.append(f"dropped/errored requests: {status}")
    buckets_hit = sum(status["bucket_hits"].values())
    if buckets_hit <= 0:
        failures.append("no batches dispatched")

    if failures:
        print("serve-smoke FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print(
        f"serve-smoke OK: {ok} requests over HTTP, "
        f"bucket hits {status['bucket_hits']}, "
        f"{status['swaps']} rolling swap(s) to step {status['model_step']}, "
        f"jit dispatch cache empty, "
        f"{len(REQUIRED_FAMILIES)} required families present"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main_lm() if "lm" in sys.argv[1:] else main())
