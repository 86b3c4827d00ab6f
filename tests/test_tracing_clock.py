"""One clock, named phases (ISSUE 25): `Tracer` spans mirror themselves into
the profiler's trace and know their parent; the elastic worker's step and the
LM engine's turn are split into spans; the compiled train step carries the
phase and kernel names a device trace is split by."""

import re
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from edl_tpu.coordinator import InProcessCoordinator
from edl_tpu.models import fit_a_line, transformer
from edl_tpu.obs import tracing
from edl_tpu.obs.tracing import Tracer
from edl_tpu.parallel import MeshSpec, build_mesh
from edl_tpu.runtime import TrainerConfig
from edl_tpu.runtime.data import SyntheticShardSource, shard_names
from edl_tpu.runtime.elastic import ElasticConfig, ElasticWorker
from edl_tpu.runtime.train_loop import Trainer


class FakeAnnotations:
    """An annotation factory that writes down what it is asked to do."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        factory = self

        class Annotation:
            def __enter__(self):
                factory.log.append(("enter", name))

            def __exit__(self, *exc):
                factory.log.append(("exit", name, exc[0]))

        return Annotation()


# -- Tracer.span -----------------------------------------------------------------


def test_span_opens_and_closes_its_annotation():
    fake = FakeAnnotations()
    tracer = Tracer(annotation=fake)
    with tracer.span("outer", step=1):
        with tracer.span("inner"):
            pass
    assert fake.log == [("enter", "outer"), ("enter", "inner"),
                        ("exit", "inner", None), ("exit", "outer", None)]
    # recorded intervals are over when they are known: not mirrored
    tracer.record("drain", 1.0, 2.0)
    tracer.event("decided")
    assert len(fake.log) == 4
    assert [s.name for s in tracer.spans] == ["inner", "outer", "drain",
                                              "decided"]


def test_span_parent_is_the_enclosing_span_of_its_own_thread():
    tracer = Tracer(annotation=FakeAnnotations())
    ready, release = threading.Event(), threading.Event()

    def pump():
        with tracer.span("lease"):
            ready.set()
            release.wait(10)

    thread = threading.Thread(target=pump)
    with tracer.span("worker_step") as step:
        thread.start()
        assert ready.wait(10)
        with tracer.span("input_wait") as wait:  # while "lease" is open
            pass
        with tracer.span("loss_sync") as sync:
            pass
        release.set()
        thread.join(10)
    assert not thread.is_alive()
    (lease,) = tracer.find(name="lease")
    assert wait.parent is step and sync.parent is step
    assert lease.parent is None and step.parent is None
    assert wait.to_dict()["parent"] == "worker_step"
    assert "parent" not in step.to_dict()
    assert tracer.record("restore", 1.0, 2.0).parent is None


def test_span_records_on_an_exception_and_closes_the_annotation():
    fake = FakeAnnotations()
    tracer = Tracer(annotation=fake)
    with pytest.raises(KeyError):
        with tracer.span("place", task="t0"):
            raise KeyError("boom")
    (span,) = tracer.spans
    assert span.attrs == {"task": "t0", "error": "KeyError"}
    assert fake.log[-1] == ("exit", "place", KeyError)
    with tracer.span("after") as after:  # the thread's stack was unwound
        pass
    assert after.parent is None


def test_span_yields_itself_and_a_dropped_span_stays_out_of_the_ring():
    tracer = Tracer(annotation=FakeAnnotations())
    with tracer.span("lease") as lease:
        lease.attrs["task"] = "part-0"
    with tracer.span("read_shard") as read:
        read.keep = False  # the look past the end of the shard
    assert [s.name for s in tracer.spans] == ["lease"]
    assert tracer.spans[0].attrs == {"task": "part-0"}
    assert lease.seconds > 0 and lease.end == lease.start + lease.seconds


def test_span_length_is_not_the_wall_clocks(monkeypatch):
    """A step of the wall clock inside a span moves neither its length nor
    its sign: the length is measured on perf_counter."""
    wall = iter([1000.0, 990.0])  # the clock is set back ten seconds
    monkeypatch.setattr(tracing.time, "time", lambda: next(wall))
    tracer = Tracer(annotation=FakeAnnotations())
    with tracer.span("loss_sync") as span:
        pass
    assert span.start == 1000.0 and 0 < span.seconds < 1.0


def test_span_without_jax_records_to_the_ring_alone(monkeypatch):
    """The controller's case: no JAX in the process, no factory handed in."""
    monkeypatch.delitem(sys.modules, "jax")
    assert tracing.profiler_annotation() is None
    tracer = Tracer(component="controller")
    with tracer.span("actuate", job="j1") as span:
        pass
    assert tracer.spans == [span] and span.component == "controller"


def test_obs_imports_without_jax_and_the_mirror_is_jaxs_where_jax_is():
    code = ("import sys, edl_tpu.obs, edl_tpu.obs.tracing as t; "
            "assert 'jax' not in sys.modules; "
            "assert t.profiler_annotation() is None")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
    assert tracing.profiler_annotation() is jax.profiler.TraceAnnotation


def test_empty_span_costs_microseconds_with_no_profiler_session():
    """The step path pays about a dozen of these a step, always (the
    mirror has no switch). Generous, so it is steady on a loaded CPU."""
    tracer = Tracer()  # the real mirror: JAX is imported here
    n = 10_000
    t0 = time.perf_counter()
    for i in range(n):
        with tracer.span("worker_step", step=i):
            pass
    each_us = (time.perf_counter() - t0) / n * 1e6
    assert len(tracer.spans) == n
    assert each_us < 20.0, f"{each_us:.1f} us a span"


def test_spans_reach_a_captured_profile_on_a_host_line(tmp_path):
    """With a session active the mirrored span is an event of the trace."""
    from jax.profiler import ProfileData
    import glob

    tracer = Tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracer.span("worker_step", step=3):
            with tracer.span("loss_sync"):
                jax.numpy.ones((8, 8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("worker_step", "loss_sync"):
                        found[e.name] = (e.start_ns, e.duration_ns)
    assert set(found) == {"worker_step", "loss_sync"}
    (s0, d0), (s1, d1) = found["worker_step"], found["loss_sync"]
    assert s0 <= s1 and s1 + d1 <= s0 + d0  # nested on the profiler's clock


# -- the elastic worker's step --------------------------------------------------


@pytest.mark.parametrize("depth", [2, 0], ids=["pipelined", "synchronous"])
def test_worker_emits_each_step_span_once_a_step(tmp_path, depth):
    model = fit_a_line.MODEL
    coord = InProcessCoordinator(task_lease_sec=60.0)
    tasks = shard_names("train", 3)
    coord.add_tasks(tasks)
    tracer = Tracer(component="test")
    seen = []
    worker = ElasticWorker(
        model, coord.client("w0"),
        SyntheticShardSource(model, batch_size=8, batches_per_shard=1),
        ElasticConfig(checkpoint_dir=str(tmp_path / "ck"),
                      checkpoint_interval=10**9, pipeline_depth=depth,
                      trainer=TrainerConfig(optimizer="sgd"),
                      step_callback=lambda step, state: seen.append(step)),
        device_planner=lambda world: jax.devices()[:1], tracer=tracer)
    summary = worker.run()
    assert summary["steps"] == 3.0 and seen == [1, 2, 3]

    def by(name):
        return tracer.find(name=name)

    steps = by("worker_step")
    assert [s.attrs["step"] for s in steps] == [1, 2, 3]
    for name in ("step_dispatch", "loss_sync", "step_callback"):
        spans = by(name)
        assert [s.attrs["step"] for s in spans] == [1, 2, 3], name
        assert [s.parent for s in spans] == steps, name
    waits = by("input_wait")
    assert [s.parent for s in waits] == steps  # none for the end of input
    for step in steps:
        children = [s for s in tracer.spans if s.parent is step]
        assert sum(c.seconds for c in children) <= step.seconds
    # the pump's side: one lease, one read and one placement a shard, then
    # the leases that found the queue empty
    leased = [s.attrs["task"] for s in by("lease")]
    assert leased[:3] == tasks and set(leased[3:]) == {None}
    assert [s.attrs["task"] for s in by("read_shard")] == tasks
    places = by("place")
    assert [s.attrs["task"] for s in places] == tasks
    assert all(s.parent is None for s in by("lease") + places) \
        or depth == 0  # synchronous: the pump is the worker's own thread
    if depth == 0:
        assert [s.parent for s in places] == waits


def test_place_span_feeds_the_step_profiler(tmp_path):
    """One timing of the placement: `StepProfiler`'s place series is the
    `place` span's length."""
    from edl_tpu.tools import StepProfiler

    model = fit_a_line.MODEL
    coord = InProcessCoordinator(task_lease_sec=60.0)
    coord.add_tasks(shard_names("train", 2))
    tracer, profiler = Tracer(), StepProfiler(warmup=0)
    ElasticWorker(
        model, coord.client("w0"),
        SyntheticShardSource(model, batch_size=8, batches_per_shard=1),
        ElasticConfig(checkpoint_dir=str(tmp_path / "ck"),
                      checkpoint_interval=10**9,
                      trainer=TrainerConfig(optimizer="sgd")),
        device_planner=lambda world: jax.devices()[:1], tracer=tracer,
        profiler=profiler).run()
    assert [r.place_seconds for r in profiler.records] == \
        [s.seconds for s in tracer.find(name="place")]


# -- the LM engine's turn ---------------------------------------------------------

LM_KW = dict(vocab_size=61, d_model=16, n_layers=2, n_heads=2, d_ff=32,
             seq_len=64, flash=False)


def test_lm_stage_precedes_every_decode_step_and_prefill_is_per_chunk(
        tmp_path):
    from edl_tpu.obs.metrics import MetricsRegistry
    from edl_tpu.runtime.export import _serving_mesh, save_inference_model
    from edl_tpu.serving import LMServingConfig, LMServingReplica

    model = transformer.make_model(**LM_KW)
    params = model.init(jax.random.PRNGKey(0), _serving_mesh(model))
    save_inference_model(str(tmp_path), "transformer", params, config=LM_KW,
                         step=1)
    tracer = Tracer(component="test")
    replica = LMServingReplica(
        LMServingConfig(model_dir=str(tmp_path), batch_buckets=(1, 2),
                        seq_buckets=(16,), kv_blocks=16, kv_block_tokens=8,
                        name="lm-spans"),
        registry=MetricsRegistry(), tracer=tracer).start()
    try:
        prompt = np.asarray([2, 4, 6], dtype=np.int32)
        handles = [replica.submit(prompt, max_new_tokens=3)
                   for _ in range(2)]
        for h in handles:
            assert len(h.result(timeout=60)["tokens"]) == 3
    finally:
        replica.stop()
    prefills = tracer.find(name="lm_prefill")
    assert sum(s.attrs["batch_size"] for s in prefills) == 2  # streams
    assert len(prefills) <= 2 and all("stream" not in s.attrs
                                      for s in prefills)
    steps = tracer.find(name="lm_decode_step")
    stages = tracer.find(name="lm_stage")
    assert len(steps) == len(stages) >= 2
    turn = [s.name for s in sorted(stages + steps, key=lambda s: s.start)]
    assert turn == ["lm_stage", "lm_decode_step"] * len(steps)
    for stage, step in zip(stages, steps):
        assert stage.end <= step.start + 1e-3
        assert {k: stage.attrs[k] for k in ("batch_size", "seq_bucket")} \
            == {k: step.attrs[k] for k in ("batch_size", "seq_bucket")}


# -- names inside the compiled step -----------------------------------------------


@pytest.fixture(scope="module")
def lowered_step_text():
    model = transformer.make_model(vocab_size=61, d_model=32, n_layers=2,
                                   n_heads=2, d_ff=64, seq_len=128,
                                   remat=True)
    mesh = build_mesh(MeshSpec({"data": 1}), jax.devices()[:1])
    trainer = Trainer(model, mesh, TrainerConfig(optimizer="adam"))
    state = trainer.init_state()
    batch = trainer.place_batch(
        model.synthetic_batch(np.random.default_rng(0), 2))
    return trainer._jit_step.lower(state, batch).as_text(debug_info=True)


@pytest.mark.parametrize("scope", [
    "fwd_bwd", "optimizer", "attn_core", "attn_proj", "mlp",
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
def test_lowered_step_names_its_phases_and_kernels(lowered_step_text, scope):
    """Metadata only: each scope is a component of some operation's name
    in the step lowered for the CPU (with debug info). The kernels sit
    under `attn_core`, the recomputed forward kernel under the marker JAX
    gives rematerialised work, which is how a device trace tells them."""
    names = set(re.findall(r'"([^"\n]*)"', lowered_step_text))
    under = [n for n in names if scope in n.split("/")]
    assert under, f"no operation is named under {scope!r}"
    if scope.startswith("flash_"):
        assert all("attn_core" in n.split("/") for n in under)
    if scope == "flash_fwd":
        assert any("rematted_computation" in n.split("/") for n in under)
        assert any("rematted_computation" not in n.split("/") for n in under)
    if scope in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert all("checkpoint" in n.split("/") for n in under)
