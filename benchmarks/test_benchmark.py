"""The benchmark's own tests, on the CPU at toy widths.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_benchmark.py -q

They live here and not under ``tests/`` because the PR that defines the
benchmark may add files only under ``benchmarks/`` (PERF.md, Open questions:
a later PR moves them). No test describes a TPU topology.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for path in (HERE, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

import generate  # noqa: E402
import run  # noqa: E402
from cell import ReadContext  # noqa: E402
import trace_reduce as tr  # noqa: E402


def bench() -> dict:
    return run.load_json(REPO, "BENCHMARK.json")


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """A temporary copy of ``benchmarks/`` with a toy configuration, two
    traffic mixes and two cells dropped in as NEW files: nothing edited."""
    root = tmp_path_factory.mktemp("toybench")
    bdir = str(root / "benchmarks")
    shutil.copytree(HERE, bdir,
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    before = {f: os.path.getmtime(os.path.join(d, f))
              for d, _, fs in os.walk(bdir) for f in fs}
    b = bench()

    def put(path, obj):
        assert not os.path.exists(os.path.join(bdir, path))
        with open(os.path.join(bdir, path), "w") as f:
            json.dump(obj, f)

    medium = run.load_json(bdir, "configs", "gpt2-medium.json")
    put("configs/toy.json", {
        "source": "none", "vocab_size": 256, "n_positions": 64, "n_embd": 64,
        "n_layer": 2, "n_head": 4, "n_inner": 128,
        "maps_to": medium["maps_to"]})
    put("traffic/toy_batches.json", dict(
        run.load_json(bdir, "traffic", "fixed_b32_s1024.json"),
        batch=4, seq_len=64))
    put("traffic/toy_chat.json", dict(
        run.load_json(bdir, "traffic", "chat_closed8.json"),
        prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.8,
                    "min": 4, "max": 40},
        output_len={"dist": "lognormal", "median": 8, "sigma": 0.6,
                    "min": 3, "max": 24}))
    put("workloads/toy_train.json",
        run.load_json(bdir, "workloads", "train_gpt2m_1chip.json"))
    put("workloads/toy_serve.json", dict(
        run.load_json(bdir, "workloads", "serve_gpt2l_chat_1chip.json"),
        seq_buckets=[32, 64], kv_blocks=64))
    put("layer_metrics/decode_step_ms_p90.toy.json", {
        "reader": "span_stat", "args": {"span": "lm_decode_step",
                                        "stat_name": "p90", "scale": 1e3}})
    b["configs"].append({"name": "toy", "source": "none", "reduced": [],
                         "file": "benchmarks/configs/toy.json", "why": "toy"})
    b["workloads"] += [
        {"name": "toy_train", "config": "toy", "chips": 1,
         "traffic": "toy_batches", "why": "toy"},
        {"name": "toy_serve", "config": "toy", "chips": 1,
         "traffic": "toy_chat", "why": "toy"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:  # today's metrics are the training cell's
            m["workloads"].append("toy_train")
    # a serving cell brings its metrics as entries; their readers' data files
    # are in the directory already (PERF.md, Open questions, item 0)
    serve = {"better": "lower", "workloads": ["toy_serve"]}
    b["end_to_end"] += [
        dict(serve, name="serve_tokens_per_s", unit="tokens/s", bound=0.05,
             better="higher", source="host_clock"),
        dict(serve, name="latency_per_token_p90_ms", unit="ms/token",
             bound=0.05, source="host_clock")]
    b["per_layer"] += [
        dict(serve, name=name, unit=unit, source="program_span", layer=layer,
             moves="serve_tokens_per_s")
        for name, unit, layer in (
            ("decode_cycle_ms_p50.serve", "ms", "LM engine"),
            ("decode_batch_mean.serve", "streams", "LM engine"),
            ("decode_step_ms_p50.serve", "ms", "decode program"),
            ("prefill_ms_p50.serve", "ms", "prefill program"),
            ("device_idle_pct.serve", "%", "device"),
            ("decode_step_ms_p90.toy", "ms", "decode program"))]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    run.validate(b, bdir)
    after = {f: os.path.getmtime(os.path.join(d, f))
             for d, _, fs in os.walk(bdir) for f in fs}
    assert all(after[f] == t for f, t in before.items())
    return b, bdir


def test_intervals_overlap_gap_empty():
    ev = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 2.0, 0.5)]
    assert tr.union_seconds(ev) == pytest.approx(2.0)
    assert tr.span_seconds(ev) == pytest.approx(2.5)
    assert tr.idle_gaps(ev) == [("unattributed, then c", pytest.approx(0.5))]
    assert tr.union_seconds([]) == 0.0 and tr.span_seconds([]) == 0.0
    assert tr.idle_gaps([]) == [] and tr.self_seconds([]) == {}
    assert tr.matching_seconds(ev, "a|c") == pytest.approx(1.5)


def test_self_time_takes_children_out_of_the_parent():
    ev = [("while", 0.0, 1.0), ("fusion", 0.1, 0.2), ("kernel", 0.4, 0.3),
          ("fusion", 1.5, 0.5)]
    own = tr.self_seconds(ev)
    assert own == {"while": pytest.approx(0.5), "fusion": pytest.approx(0.7),
                   "kernel": pytest.approx(0.3)}
    assert sum(own.values()) == pytest.approx(tr.union_seconds(ev))


def test_reduction_of_a_tiny_xspace():
    from jax.profiler import ProfileData

    text = """
    planes { id: 1 name: "/device:TPU:0"
      event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
      event_metadata { key: 2 value { id: 2
        name: '%branch_0_fun.3 = bf16[8]{0} custom-call(bf16[8]{0} %p), custom_call_target="tpu_custom_call"' } }
      lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
        events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
      lines { id: 2 name: "Steps" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } } }
    planes { id: 2 name: "/host:CPU"
      event_metadata { key: 1 value { id: 1 name: "python" } }
      lines { id: 1 name: "main" events { metadata_id: 1 duration_ps: 5 } } }
    """
    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    trace = tr.from_profile(profile)
    assert list(trace.devices) == ["/device:TPU:0"]
    assert trace.busy_s == pytest.approx(3e-6)
    assert trace.window_s == pytest.approx(4e-6)
    assert list(trace.custom_calls) == ["branch_0_fun.3[tpu_custom_call]"]
    assert tr.matching_seconds(trace.devices["/device:TPU:0"],
                               r"\[tpu_custom_call\]") == pytest.approx(1e-6)
    assert trace.breakdown()["idle_gaps"][0][1] == pytest.approx(1e-6)
    assert trace.seen["/host:CPU"] == {"main": 1}


def test_benchmark_json_and_data_files_are_valid():
    b = bench()
    run.validate(b, HERE)
    for d, _, files in os.walk(HERE):
        if ".work" in d or "__pycache__" in d:
            continue
        for f in files:
            assert run.NAME.match(f) or f.startswith("__"), f
            if f.endswith(".json"):
                run.load_json(d, f)
    for c in b["configs"]:
        cfg = run.load_json(REPO, c["file"])
        assert c["source"] == cfg["source"] and c["reduced"] == cfg["reduced"]
        assert set(run.model_kwargs(cfg)) == {
            "vocab_size", "d_model", "n_layers", "n_heads", "d_ff", "seq_len"}


def test_a_bad_moves_is_refused(toy):
    b = bench()
    b["per_layer"][0]["moves"] = "setup_s_typo"
    with pytest.raises(ValueError, match="moves unknown"):
        run.validate(b, HERE)
    b, bdir = toy
    b = json.loads(json.dumps(b))
    b["per_layer"][0]["moves"] = "serve_tokens_per_s"  # a train-cell metric
    with pytest.raises(ValueError, match="does not report"):
        run.validate(b, bdir)


def test_every_seed_gets_the_same_sizes_in_another_order():
    t = run.load_json(HERE, "traffic", "chat_closed8.json")
    a, b = (generate.request_list(t, seed, 50257) for seed in (1, 2**31 + 7))
    sizes = lambda rs: [(len(r["prompt"]), r["max_new_tokens"]) for r in rs]
    n = t["block"]
    assert sizes(a) != sizes(b)
    for k in range(0, 4 * n, n):  # block by block, the same multiset
        assert sorted(sizes(a)[k:k + n]) == sorted(sizes(b)[k:k + n])
    assert sizes(a) == sizes(generate.request_list(t, 1, 50257))
    block = generate.base_block(t)
    assert all(8 <= p <= 192 and 8 <= o <= 64 and p + o <= 256
               for p, o in block)


def test_reference_agrees_with_the_prefill_step():
    import jax
    import numpy as np
    from edl_tpu.models import transformer
    from edl_tpu.parallel import MeshSpec, build_mesh

    import reference

    model = transformer.make_model(vocab_size=256, d_model=64, n_heads=4,
                                   d_ff=128, seq_len=64, n_layers=2)
    mesh = build_mesh(MeshSpec({"data": 1}), jax.devices()[:1])
    params = model.init(jax.random.PRNGKey(0), mesh)
    tokens = np.random.default_rng(0).integers(1, 256, (1, 32)).astype(np.int32)
    nxt, _, _ = transformer.make_prefill_step(model.config)(
        params, tokens, np.array([32], np.int32))
    row = np.asarray(reference.reference_logits(
        model.config, params, tokens[0]))[31]
    assert row.max() - row[int(nxt[0])] < reference.NEAR_TIE


def test_run_py_exits_non_zero_without_a_tpu():
    got = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "train_gpt2m_1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert got.returncode != 0
    assert "needs a TPU" in got.stderr and '"correct"' not in got.stdout


@pytest.mark.parametrize("cell", ["toy_train", "toy_serve"])
def test_runner_end_to_end_at_toy_widths(toy, cell):
    import jax

    b, bdir = toy
    out = run.run_cell(b, bdir, cell, 2**31 + 12345, 2.0, False,
                       jax.devices()[:1])
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in b["end_to_end"]
                                   if run.reports(m, cell)}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert not os.path.exists(os.path.join(bdir, ".work", cell))


def test_a_new_metric_is_read_from_spans_by_its_data_file(toy):
    from types import SimpleNamespace as Span

    b, bdir = toy
    how = run.load_json(bdir, "layer_metrics", "decode_step_ms_p90.toy.json")
    reader = run.load_module(bdir, "readers", how["reader"])
    spans = [Span(name="lm_decode_step", start=float(i), end=i + 0.01 * i,
                  attrs={}) for i in range(1, 12)]
    ctx = ReadContext(spans=spans, values={}, trace=None, device={},
                          chips=1, model_kwargs={})
    assert reader.read(ctx, **how["args"]) == pytest.approx(100.0)
    assert reader.read(ReadContext([], {}, None, {}, 1, {}),
                       **how["args"]) is None
