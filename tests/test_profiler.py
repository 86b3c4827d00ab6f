"""Profiler tests: step accounting, summaries, trace capture, loop hookup."""

import glob
import io
import json
import math
import time

import numpy as np

from edl_tpu.models import fit_a_line
from edl_tpu.parallel import local_mesh
from edl_tpu.runtime import Trainer, TrainerConfig
from edl_tpu.obs.tracing import Tracer
from edl_tpu.tools import StepProfiler, annotate_step, device_memory_stats, trace


def test_step_profiler_records_and_summarizes():
    p = StepProfiler(warmup=1)
    p.start()
    for i in range(5):
        time.sleep(0.002)
        p.step(samples=32, loss=1.0 / (i + 1))
    assert len(p.records) == 5
    s = p.summary()
    assert s["steps"] == 5.0
    assert s["steady_steps"] == 4.0  # warmup step excluded
    assert s["samples_per_sec"] > 0
    assert s["step_time_p50_s"] <= s["step_time_p95_s"] <= s["step_time_max_s"]
    # warmup record still present for trace alignment
    assert p.records[0].step == 0


def test_step_profiler_sink_emits_jsonl():
    sink = io.StringIO()
    p = StepProfiler(sink=sink)
    p.start()
    p.step(samples=8, loss=0.5)
    p.step(samples=8)
    lines = [json.loads(l) for l in sink.getvalue().splitlines()]
    assert len(lines) == 2
    assert lines[0]["samples"] == 8
    assert lines[0]["loss"] == 0.5
    assert "loss" not in lines[1]


def test_step_profiler_window_bounds_memory():
    p = StepProfiler(warmup=0, window=10)
    p.start()
    for _ in range(50):
        p.step(samples=1)
    assert len(p.records) == 10
    assert p.summary()["steps"] == 50.0


def test_window_eviction_does_not_misclassify_steady():
    """Warmup is a per-record flag, not a list position: after the warmup
    record is evicted by the window, no steady record is dropped."""
    p = StepProfiler(warmup=1, window=5)
    p.start()
    for _ in range(20):
        p.step(samples=1)
    assert len(p.steady) == 5  # all surviving records are steady
    assert p.summary()["steady_steps"] == 5.0


def test_mark_warmup_flags_recompile_steps():
    p = StepProfiler(warmup=0)
    p.start()
    p.step(samples=1)
    p.mark_warmup()  # e.g. mesh rebuilt after rescale
    p.step(samples=1)
    p.step(samples=1)
    flags = [r.warmup for r in p.records]
    assert flags == [False, True, False]
    assert p.summary()["steady_steps"] == 2.0


def test_wrap_iterator_times_consumer():
    p = StepProfiler(warmup=0)
    data = [{"x": np.zeros((4, 2))} for _ in range(3)]
    out = list(p.wrap(iter(data)))
    assert len(out) == 3
    assert [r.samples for r in p.records] == [4, 4, 4]


def test_empty_profiler_summary():
    """Zero-step and warmup-only summaries: same keys as the populated case,
    every value a finite zero — never a ZeroDivisionError, inf, or NaN (a
    rescale can interrupt a worker before its first steady step, and the
    flush must still aggregate)."""
    keys = ("steps", "steady_steps", "samples_per_sec", "step_time_mean_s",
            "step_time_p50_s", "step_time_p95_s", "step_time_max_s")

    s = StepProfiler().summary()
    for k in keys:
        assert s[k] == 0.0 and math.isfinite(s[k]), (k, s)

    # warmup-only: records exist but none are steady — the old inf/NaN trap.
    p = StepProfiler(warmup=5)
    p.start()
    p.step(samples=8)
    p.step(samples=8)
    s = p.summary()
    assert s["steps"] == 2.0
    assert s["steady_steps"] == 0.0
    for k in keys:
        assert math.isfinite(s[k]), (k, s)
    assert s["samples_per_sec"] == 0.0


def test_trainer_run_with_profiler():
    mesh = local_mesh()
    trainer = Trainer(fit_a_line.MODEL, mesh, TrainerConfig(optimizer="sgd", learning_rate=0.1))
    state = trainer.init_state()
    rng = np.random.default_rng(0)
    prof = StepProfiler(warmup=1)

    def batches(n):
        for _ in range(n):
            yield fit_a_line.MODEL.synthetic_batch(rng, 64)

    state, metrics = trainer.run(state, batches(6), profiler=prof)
    assert len(prof.records) == 6
    s = prof.summary()
    assert s["steady_steps"] == 5.0
    # aggregate throughput in the same ballpark as the loop's own accounting
    assert s["samples_per_sec"] > 0


def test_collective_series_and_data_plane_summary():
    """The data-plane estimate rides the step records (`collective_ms` in the
    sink line) and the summary surfaces `grad_bytes_per_step` +
    `collective_time_est_mean_s` once `data_plane` is attached."""
    sink = io.StringIO()
    p = StepProfiler(warmup=0, sink=sink)
    p.data_plane = {"grad_bytes_per_step": 1024.0, "bytes_per_step": 1536.0}
    p.start()
    p.step(samples=8, collective_seconds=0.002)
    p.step(samples=8, collective_seconds=0.004)
    p.step(samples=8)  # estimate omitted — must not poison the mean
    lines = [json.loads(l) for l in sink.getvalue().splitlines()]
    assert lines[0]["collective_ms"] == 2.0
    assert lines[1]["collective_ms"] == 4.0
    assert "collective_ms" not in lines[2]
    s = p.summary()
    assert s["collective_time_est_mean_s"] == (0.002 + 0.004) / 2
    assert s["grad_bytes_per_step"] == 1024.0
    assert s["data_plane_bytes_per_step"] == 1536.0
    # without a data plane, the byte keys stay absent
    bare = StepProfiler(warmup=0)
    bare.start()
    bare.step(samples=8)
    assert "grad_bytes_per_step" not in bare.summary()
    assert "collective_time_est_mean_s" not in bare.summary()


def test_trainer_run_fills_data_plane():
    """Trainer.run wires its analytic data plane into the profiler: every
    step record carries the estimate and the summary reports bytes."""
    mesh = local_mesh()
    trainer = Trainer(
        fit_a_line.MODEL, mesh, TrainerConfig(optimizer="sgd", learning_rate=0.1)
    )
    state = trainer.init_state()
    rng = np.random.default_rng(0)
    prof = StepProfiler(warmup=0)

    def batches(n):
        for _ in range(n):
            yield fit_a_line.MODEL.synthetic_batch(rng, 64)

    _, metrics = trainer.run(state, batches(3), profiler=prof)
    assert prof.data_plane is not None
    assert prof.data_plane["grad_sync"] == trainer.grad_sync
    assert all(r.collective_seconds is not None for r in prof.records)
    assert prof.summary()["grad_bytes_per_step"] == metrics["grad_bytes_per_step"]


def test_annotations_are_usable_contexts():
    """The one path into the profiler's trace: the worker's step marker
    around `Tracer` spans, which mirror themselves as annotations (what a
    captured trace then holds is `test_tracing_clock.py`'s to check)."""
    tracer = Tracer()
    with annotate_step(3):
        with tracer.span("edl/test-span", step=3) as span:
            pass
    assert tracer.spans == [span] and span.attrs == {"step": 3}


def test_trace_captures_to_logdir(tmp_path):
    import jax.numpy as jnp

    logdir = str(tmp_path / "trace")
    with trace(logdir):
        (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
    produced = glob.glob(logdir + "/**/*", recursive=True)
    assert produced, "profiler trace produced no files"


def test_device_memory_stats_shape():
    stats = device_memory_stats()
    # CPU backend usually exposes nothing; if it does, values are ints.
    for per_dev in stats.values():
        for v in per_dev.values():
            assert isinstance(v, int)


def test_summary_reports_mfu_when_model_given():
    from edl_tpu.models import fit_a_line
    from edl_tpu.tools.profiler import StepProfiler

    prof = StepProfiler(warmup=0, model=fit_a_line.MODEL)
    prof.start()
    for _ in range(3):
        prof.step(64)
    s = prof.summary()
    assert s["tflops_per_sec"] > 0
    # per-sample flops x rate consistency: mfu_fields rounds to 3 decimals
    # but never rounds a positive achieved rate down to 0 (CPU-sim figures
    # for tiny models sit below a milli-TFLOP)
    expected = fit_a_line.MODEL.flops_per_step(1) * s["samples_per_sec"] / 1e12
    assert s["tflops_per_sec"] == (round(expected, 3) or expected)
    # CPU backend: no peak table entry, so no mfu key
    assert "mfu" not in s

    bare = StepProfiler(warmup=0)
    bare.start()
    bare.step(64)
    assert "tflops_per_sec" not in bare.summary()
