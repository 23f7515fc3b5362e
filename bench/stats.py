"""Percentiles for the benchmark's latency metrics.

Copied from the program's percentile arithmetic so that the yardstick
cannot move with it: linear interpolation (numpy's default), ranks on the
0-100 scale, ``None`` for an empty sample.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(xs: Sequence[float], q: float) -> float | None:
    """Linear-interpolated percentile of ``xs`` (``q`` in 0..100)."""
    xs = [x for x in xs if x is not None]
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q))


def median(xs: Sequence[float]) -> float | None:
    return percentile(xs, 50.0)
