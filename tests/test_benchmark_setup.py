"""The benchmark's worker set-up metrics, on the CPU: reader `span_sum` on
hand-built spans, the five `layer_metrics/setup_*.train.json` files and
their `BENCHMARK.json` entries, and the five read from the spans of a small
`ElasticWorker` run and from a program that records none of them. No chip,
no timing."""

import os
import sys
from types import SimpleNamespace

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
for path in (BENCH, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

import run  # noqa: E402
from cell import ReadContext  # noqa: E402

SETUP = ("setup_worker_s.train", "setup_init_state_s.train",
         "setup_trace_lower_s.train", "setup_xla_compile_s.train",
         "setup_step_compiles.train")
CELLS = ["train_gpt2m_1chip", "train_nemotron3nano_1chip",
         "train_keyevl2_1chip", "train_smallthinker21b_1chip"]


def span(name, start, end, parent=None):
    return SimpleNamespace(name=name, start=start, end=end, parent=parent)


def context(spans):
    return ReadContext(spans=spans, values={}, trace=None, device={},
                       chips=1, model_kwargs={})


def read(metric, ctx):
    how = run.load_json(BENCH, "layer_metrics", f"{metric}.json")
    reader = run.load_module(BENCH, "readers", how["reader"])
    return reader.read(ctx, **how.get("args", {}))


@pytest.fixture
def span_sum():
    return run.load_module(BENCH, "readers", "span_sum").read


def test_span_sum_by_name_by_parent_and_as_a_count(span_sum):
    dispatch = [span("step_dispatch", 10.0, 20.0), span("step_dispatch",
                                                        20.0, 21.0)]
    init = span("init_state", 1.0, 4.0)
    spans = dispatch + [
        init,
        span("jax_trace", 1.0, 1.5, init), span("jax_lower", 1.5, 2.0, init),
        span("xla_compile", 2.0, 3.0, init),
        span("jax_trace", 10.0, 12.0, dispatch[0]),
        span("jax_lower", 12.0, 15.0, dispatch[0]),
        span("xla_compile", 15.0, 19.0, dispatch[0]),
        span("xla_compile", 20.0, 20.5, dispatch[1]),
        span("restore", 0.0, 1.0)]  # recorded with no parent attribute
    del spans[-1].parent
    ctx = context(spans)
    assert span_sum(ctx, ["init_state"]) == 3.0
    assert span_sum(ctx, ["jax_trace", "jax_lower"]) == 6.0
    assert span_sum(ctx, ["xla_compile"]) == 5.5
    assert span_sum(ctx, ["xla_compile"], parent="step_dispatch") == 4.5
    assert span_sum(ctx, ["xla_compile"], parent="step_dispatch",
                    count=True) == 2
    assert span_sum(ctx, ["xla_compile"], parent="init_state",
                    count=True, scale=10.0) == 10.0
    assert span_sum(ctx, ["restore"]) == 1.0
    # nothing to read: no such span, or none under that parent
    assert span_sum(ctx, ["worker_setup"]) is None
    assert span_sum(ctx, ["xla_compile"], parent="place") is None
    assert span_sum(ctx, ["restore"], parent="place", count=True) is None


def test_the_five_files_and_entries_are_additions_and_valid():
    bench = run.load_json(REPO, "BENCHMARK.json")
    run.validate(bench, BENCH)
    mine = bench["per_layer"][-len(SETUP):]
    assert tuple(m["name"] for m in mine) == SETUP
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["better"], m["layer"], m["moves"], m["workloads"]) == (
            "lower", "worker set-up", "setup_s", CELLS)
        assert (m["unit"], m["source"]) == (
            ("count", "program_counter")
            if m["name"] == "setup_step_compiles.train"
            else ("s", "program_span"))
        how = run.load_json(BENCH, "layer_metrics", f"{m['name']}.json")
        assert how["reader"] == "span_sum" and how["reads"]
    # every metric before them moves throughput: no other moves setup_s
    assert all(m["moves"] == "train_tokens_per_s"
               for m in bench["per_layer"][:-len(SETUP)])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_validate_refuses_a_setup_metric_of_an_unknown_cell():
    bench = run.load_json(REPO, "BENCHMARK.json")
    bench["per_layer"][-1]["workloads"].append("train_nowhere_1chip")
    with pytest.raises(ValueError, match="unknown cell"):
        run.validate(bench, BENCH)


def test_the_five_read_a_worker_run_and_nothing_without_its_spans(tmp_path):
    from edl_tpu.coordinator import InProcessCoordinator
    from edl_tpu.models import fit_a_line
    from edl_tpu.obs.tracing import Tracer
    from edl_tpu.runtime import TrainerConfig
    from edl_tpu.runtime.data import SyntheticShardSource, shard_names
    from edl_tpu.runtime.elastic import ElasticConfig, ElasticWorker

    model = fit_a_line.MODEL
    coord = InProcessCoordinator(task_lease_sec=600.0,
                                 heartbeat_ttl_sec=600.0)
    coord.add_tasks(shard_names("train", 3))
    tracer = Tracer(component="benchmark")
    ElasticWorker(
        model, coord.client("worker-0"),
        SyntheticShardSource(model, batch_size=8, batches_per_shard=1),
        ElasticConfig(checkpoint_dir=str(tmp_path / "ckpt"),
                      checkpoint_interval=10**9,
                      trainer=TrainerConfig(optimizer="adam")),
        device_planner=lambda world: jax.devices()[:1], tracer=tracer).run()
    got = {m: read(m, context(list(tracer.spans))) for m in SETUP}
    assert all(v is not None for v in got.values()), got
    assert 0 < got["setup_init_state_s.train"] \
        <= got["setup_worker_s.train"]
    # a fresh state is placed where the step returns it: one program
    assert got["setup_step_compiles.train"] == 1
    assert got["setup_trace_lower_s.train"] > 0
    assert got["setup_xla_compile_s.train"] > 0
    # a program that records none of these spans (an older one): every
    # metric is left out of the line
    older = [s for s in tracer.spans if s.name not in (
        "worker_setup", "init_state", "final_checkpoint", "jax_trace",
        "jax_lower", "xla_compile")]
    assert older and all(read(m, context(older)) is None for m in SETUP)
