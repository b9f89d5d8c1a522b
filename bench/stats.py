"""Arithmetic the metric readers share.  ``percentile`` is the
program's ``LatencyRecorder`` arithmetic (linear interpolation between
order statistics), copied so that no change to the program moves it."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def covered_s(spans, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the union of ``spans`` covers."""
    total, cursor = 0.0, lo
    for s, e in sorted(spans):
        s, e = max(s, cursor), min(e, hi)
        if e > s:
            total += e - s
            cursor = e
    return total


def mean_ms(spans) -> float | None:
    if not spans:
        return None
    return 1e3 * float(np.mean([e - s for s, e in spans]))


def roofline_pct(run) -> float | None:
    """Least time of the step's bytes at the HBM peak, over the device time
    of the window's program executions, in percent."""
    t = run.trace
    if t is None or not run.step_bytes or not t.execution_s or run.peaks is None:
        return None
    return 100.0 * run.step_bytes / run.peaks["hbm_bytes_per_s"] / t.execution_s
