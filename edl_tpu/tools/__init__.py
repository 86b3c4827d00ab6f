"""Operational tooling: metrics collector, step profiler, CLI surfaces."""

from edl_tpu.tools.collector import ClusterSample, Collector
from edl_tpu.tools.profiler import (
    StepProfiler,
    StepRecord,
    annotate_step,
    device_memory_stats,
    trace,
)

__all__ = [
    "ClusterSample",
    "Collector",
    "StepProfiler",
    "StepRecord",
    "annotate_step",
    "device_memory_stats",
    "trace",
]
