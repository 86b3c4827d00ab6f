"""What a runner is given and gives back, and what a reader may read."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional


@dataclass
class Cell:
    """One run of one cell, as a runner sees it."""

    name: str
    chips: int
    config: dict            # the configuration's file
    model_kwargs: dict      # its sizes under the program's names
    traffic: dict           # the traffic mix's file
    workload: dict          # the cell's file: runner and its settings
    seed: int
    seconds: float
    trace: bool
    devices: list
    workdir: str            # scratch inside the checkout, removed at the end
    trace_dir: str
    t0: float               # process start, time.perf_counter()
    log: Callable[[str], None]


@dataclass
class Outcome:
    """What a runner measured. ``end_to_end`` is keyed by metric name and
    holds ``setup_s``; ``values`` and ``spans`` feed the per-layer readers."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    memory_peak_bytes: int
    values: Dict[str, float] = field(default_factory=dict)
    spans: List[Any] = field(default_factory=list)


@dataclass
class ReadContext:
    """What a per-layer reader may read."""

    spans: List[Any]
    values: Dict[str, float]
    trace: Optional[Any]    # trace_reduce.DeviceTrace
    device: dict
    chips: int
    model_kwargs: dict
