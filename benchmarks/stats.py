"""The statistics a metric may name, in one place."""

from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    return float(np.percentile(values, q))


def stat(values: Sequence[float], which: str):
    """``mean`` or ``p<q>``; None of nothing."""
    if not values:
        return None
    if which == "mean":
        return float(np.mean(values))
    if which.startswith("p"):
        return percentile(values, float(which[1:]))
    raise ValueError(f"unknown statistic {which!r}")
