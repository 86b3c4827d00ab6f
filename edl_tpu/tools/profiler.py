"""Step-time and trace instrumentation for the training runtime.

The reference's observability is logs only — log15 levels (`cmd/edl/edl.go:26-28`),
`GLOG_v` on pods, and pass-elapsed prints in examples
(`example/ctr/ctr/train.py:176`). SURVEY §5 flags that as the bar to clear:
this module gives the TPU framework first-class step timing and XLA traces.

Three pieces:

- :class:`StepProfiler` — host-side per-step accounting (wall time, samples,
  rolling throughput, percentiles). Pure data structure; feed it from any
  loop via :meth:`StepProfiler.step` or wrap an iterator.
- :func:`trace` — context manager around ``jax.profiler`` that captures an
  XLA/TPU trace (TensorBoard-loadable) for the enclosed steps.
- :func:`annotate_step` — the step marker the elastic worker puts around one
  loop iteration, so a captured trace groups its events by the worker's step
  number. The hot loop's phases reach the trace as spans of
  ``edl_tpu.obs.tracing.Tracer``, which mirrors each into the profiler.

Device memory introspection (:func:`device_memory_stats`) reports per-device
HBM in-use/limit where the backend exposes it (TPU does; CPU returns {}).
"""

from __future__ import annotations

import contextlib
import json
import math
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, TextIO

import jax

from edl_tpu.obs.metrics import get_registry

__all__ = [
    "StepProfiler",
    "StepRecord",
    "trace",
    "annotate_step",
    "device_memory_stats",
]


@dataclass
class StepRecord:
    """One step's host-side observation."""

    step: int
    seconds: float
    samples: int
    loss: Optional[float] = None
    #: excluded from steady-state summaries (jit compile / post-rescale).
    warmup: bool = False
    #: host-side batch placement time (wire encode + H2D shard placement)
    #: attributed to this step. In the synchronous loop it is part of
    #: ``seconds``; in the pipelined loop it ran on the pump thread and
    #: overlapped an earlier step's device compute.
    place_seconds: Optional[float] = None
    #: analytic bandwidth-model ESTIMATE of this step's data-plane
    #: collective time (`Trainer.data_plane` — bytes-on-wire closed form
    #: over per-tier bandwidths), not a measurement: it exposes the
    #: bytes-vs-time structure next to the measured ``seconds``.
    collective_seconds: Optional[float] = None

    def to_dict(self) -> dict:
        d = {"step": self.step, "seconds": round(self.seconds, 6), "samples": self.samples}
        if self.loss is not None and not math.isnan(self.loss):
            d["loss"] = self.loss
        if self.warmup:
            d["warmup"] = True
        if self.place_seconds is not None:
            d["place_ms"] = round(self.place_seconds * 1e3, 3)
        if self.collective_seconds is not None:
            d["collective_ms"] = round(self.collective_seconds * 1e3, 3)
        return d


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return float("nan")
    idx = q * (len(sorted_vals) - 1)
    lo = int(math.floor(idx))
    hi = int(math.ceil(idx))
    if lo == hi:
        return sorted_vals[lo]
    frac = idx - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


class StepProfiler:
    """Accumulates per-step wall times and derives throughput statistics.

    Skips the first ``warmup`` steps in summaries (they include jit compile,
    20-40 s on TPU) but still records them, so traces line up with records.
    A bounded window keeps memory constant on long runs.
    """

    def __init__(self, warmup: int = 1, window: int = 10_000,
                 sink: Optional[TextIO] = None, model: Optional[Any] = None,
                 n_chips: Optional[int] = None):
        self.warmup = warmup
        self.window = window
        self.sink = sink
        #: optional zoo model: when it declares analytic ``flops_per_step``
        #: (models.base convention) the summary also reports achieved
        #: TFLOP/s per chip and MFU against the live chip's peak.
        self.model = model
        #: None = unset (Trainer.run fills it from its mesh); an explicit
        #: value — including 1 for whole-job figures — is never overwritten.
        self.n_chips = n_chips
        #: None = unset; Trainer.run fills it with its `data_plane` dict so
        #: the summary can report ``grad_bytes_per_step`` next to the
        #: measured step times without re-deriving the byte model here.
        self.data_plane: Optional[Dict[str, Any]] = None
        self.records: List[StepRecord] = []
        self._count = 0
        self._mark: Optional[float] = None
        self._pending_warmup = 0
        # Registry mirrors of the per-step series: JSONL sinks carry the
        # full history, /metrics carries the live distribution. Get-or-create
        # means every profiler in the process feeds the same families.
        registry = get_registry()
        self._m_step_time = registry.histogram(
            "edl_step_time_seconds",
            "training step wall time, by phase (steady vs warmup/recompile)",
            labelnames=("phase",),
        )
        self._m_samples = registry.counter(
            "edl_step_samples_total", "training examples consumed",
        )
        self._m_place_time = registry.histogram(
            "edl_place_time_seconds",
            "host-side batch placement time (wire decode + H2D sharding)",
        )
        self._m_collective_est = registry.gauge(
            "edl_collective_time_estimate_seconds",
            "analytic data-plane collective-time estimate for the current "
            "mesh/layout (a model, not a measurement)",
        )

    # -- feeding ---------------------------------------------------------------

    def start(self) -> None:
        """Mark the start of a step (optional; ``step`` falls back to the
        previous step's end)."""
        self._mark = time.perf_counter()

    def mark_warmup(self, n: int = 1) -> None:
        """Flag the next ``n`` steps as warmup — call when the upcoming step
        will recompile (mesh rebuild after an elastic rescale)."""
        self._pending_warmup += n

    def step(self, samples: int, loss: Optional[float] = None,
             place_seconds: Optional[float] = None,
             collective_seconds: Optional[float] = None) -> StepRecord:
        """Record one completed step of ``samples`` examples.

        ``place_seconds`` — this batch's host placement time, recorded as
        its own series so the place/step split survives into jsonl sinks
        and summaries (the pipelined loop's placement happens off the
        dispatch thread, invisible to ``seconds``).

        ``collective_seconds`` — the analytic data-plane collective
        estimate for this step (`Trainer.data_plane`); a model series, not
        a measurement, kept per-record so jsonl sinks line it up against
        the measured ``seconds``."""
        now = time.perf_counter()
        start = self._mark if self._mark is not None else now
        is_warmup = self._count < self.warmup or self._pending_warmup > 0
        if self._pending_warmup > 0:
            self._pending_warmup -= 1
        rec = StepRecord(step=self._count, seconds=now - start,
                         samples=samples, loss=loss, warmup=is_warmup,
                         place_seconds=place_seconds,
                         collective_seconds=collective_seconds)
        self._count += 1
        self._mark = now
        self._m_step_time.observe(rec.seconds,
                                  phase="warmup" if is_warmup else "steady")
        self._m_samples.inc(samples)
        if place_seconds is not None:
            self._m_place_time.observe(place_seconds)
        if collective_seconds is not None:
            self._m_collective_est.set(collective_seconds)
        self.records.append(rec)
        if len(self.records) > self.window:
            del self.records[: len(self.records) - self.window]
        if self.sink is not None:
            self.sink.write(json.dumps(rec.to_dict()) + "\n")
            self.sink.flush()
        return rec

    def wrap(self, batches: Iterator[Dict[str, Any]],
             batch_size_of=lambda b: len(next(iter(b.values())))) -> Iterator[Dict[str, Any]]:
        """Yield from ``batches`` while timing each consumer iteration."""
        self.start()
        for batch in batches:
            yield batch
            self.step(batch_size_of(batch))

    # -- summaries -------------------------------------------------------------

    @property
    def steady(self) -> List[StepRecord]:
        return [r for r in self.records if not r.warmup]

    def summary(self) -> Dict[str, float]:
        steady = self.steady
        if not steady:
            # Well-defined empty summary: same keys as the populated one,
            # all finite zeros — a zero-step run (rescale before the first
            # steady step, a crashed worker's flush) must aggregate cleanly,
            # never throw or emit NaN percentiles downstream.
            return {
                "steps": float(self._count),
                "steady_steps": 0.0,
                "samples_per_sec": 0.0,
                "step_time_mean_s": 0.0,
                "step_time_p50_s": 0.0,
                "step_time_p95_s": 0.0,
                "step_time_max_s": 0.0,
            }
        times = sorted(r.seconds for r in steady)
        total = sum(times)
        samples = sum(r.samples for r in steady)
        out = {
            "steps": float(self._count),
            "steady_steps": float(len(steady)),
            # total == 0 can only happen with clamped/mocked clocks; report
            # 0 throughput rather than inf (inf is not JSON-representable).
            "samples_per_sec": samples / total if total > 0 else 0.0,
            "step_time_mean_s": total / len(steady),
            "step_time_p50_s": _percentile(times, 0.5),
            "step_time_p95_s": _percentile(times, 0.95),
            "step_time_max_s": times[-1],
        }
        places = sorted(r.place_seconds for r in steady
                        if r.place_seconds is not None)
        if places:
            out["place_time_mean_s"] = sum(places) / len(places)
            out["place_time_p50_s"] = _percentile(places, 0.5)
        colls = [r.collective_seconds for r in steady
                 if r.collective_seconds is not None]
        if colls:
            # an estimate series (see StepRecord.collective_seconds) —
            # constant within a mesh/layout, so mean is the whole story
            out["collective_time_est_mean_s"] = sum(colls) / len(colls)
        if self.data_plane is not None:
            out["grad_bytes_per_step"] = float(
                self.data_plane["grad_bytes_per_step"]
            )
            out["data_plane_bytes_per_step"] = float(
                self.data_plane["bytes_per_step"]
            )
        if getattr(self.model, "flops_per_step", None) is not None \
                and total > 0 and samples:
            from edl_tpu.tools.mfu import mfu_fields

            # One accounting implementation (mfu.mfu_fields — the benches'):
            # analytic FLOPs are linear in batch size (tested invariant), so
            # batch_size=1 at the steady samples/s rate gives the achieved
            # figure. Only the non-null fields join the summary.
            acct = mfu_fields(self.model, 1, samples / total,
                              n_chips=self.n_chips or 1,
                              device=jax.devices()[0])
            if acct.get("tflops_per_sec") is not None:
                out["tflops_per_sec"] = acct["tflops_per_sec"]
            if acct.get("mfu") is not None:
                out["mfu"] = acct["mfu"]
        return out


# -- XLA trace capture ---------------------------------------------------------


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a TensorBoard-loadable device trace of the enclosed block.

    Thin guard over ``jax.profiler.trace``: a backend without profiler
    support (or a profiler already running) degrades to a no-op instead of
    failing the training run. Profiler errors surface at ``__enter__``/
    ``__exit__`` — both are guarded; errors from the traced block itself
    propagate untouched.
    """
    cm = None
    try:
        cm = jax.profiler.trace(logdir)
        cm.__enter__()
    except Exception:  # pragma: no cover  # edl: noqa[EDL005] degrade to no-op: a backend without profiler support must not kill training
        cm = None
    try:
        yield
    finally:
        if cm is not None:
            try:
                cm.__exit__(None, None, None)
            except Exception:  # pragma: no cover  # edl: noqa[EDL005] trace teardown is best-effort; errors from the traced block propagate separately
                pass


def annotate_step(step: int):
    """Step marker that lets a trace viewer group events per training step.
    A flag check while no profiler session runs."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


# -- device memory -------------------------------------------------------------


def device_memory_stats() -> Dict[str, Dict[str, int]]:
    """Per-device memory stats where the backend exposes them (TPU HBM).

    Returns {device_id: {bytes_in_use, bytes_limit, ...}}; empty entries are
    dropped so CPU test runs see {}.
    """
    out: Dict[str, Dict[str, int]] = {}
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:  # edl: noqa[EDL005] backends without memory_stats (CPU tests) report {}; that absence is the signal
            stats = None
        if stats:
            out[str(d.id)] = {k: int(v) for k, v in stats.items()
                              if isinstance(v, (int, float))}
    return out
