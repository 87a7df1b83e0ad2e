"""Tails over all samples."""

from __future__ import annotations

import numpy as np


def percentile(samples, q: float) -> float | None:
    """The ``q``-th percentile of every sample (linear interpolation);
    None when there are none."""
    xs = np.asarray(list(samples), np.float64)
    if xs.size == 0:
        return None
    return float(np.percentile(xs, q))

