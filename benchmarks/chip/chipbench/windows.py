"""Which requests a run's tails are taken over, and their token gaps."""

from __future__ import annotations

import numpy as np


def window_requests(run):
    """Requests due inside the window."""
    t0, t1 = run.window
    return [r for r in run.requests
            if r.req.segment == "window" and t0 <= r.due < t1]


def window_gaps(run) -> tuple[np.ndarray, np.ndarray]:
    """Every gap between successive tokens of a request due in the window
    (ms), and whether a step that carried prefill tokens ended inside it."""
    ends = np.array([te for ts, te, *_ in run.steps], np.float64)
    order = np.argsort(ends)
    ends = ends[order]
    chunk = np.array([s[2] > 0 for s in run.steps], np.int64)[order]
    before = np.concatenate([[0], np.cumsum(chunk)])   # chunk steps ending <= t
    gaps, carried = [], []
    for r in window_requests(run):
        t = np.asarray(r.times, np.float64)
        if t.size < 2:
            continue
        k = np.searchsorted(ends, t, side="right")
        gaps.append(np.diff(t) * 1e3)
        carried.append(np.diff(before[k]) > 0)
    if not gaps:
        return np.zeros((0,)), np.zeros((0,), bool)
    return np.concatenate(gaps), np.concatenate(carried)


def prefill_gap_share(run) -> float | None:
    """Share of the window's token gaps that carried a prefill step."""
    gaps, carried = window_gaps(run)
    return float(carried.mean()) if gaps.size else None
