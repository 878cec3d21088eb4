"""The benchmark's metric arithmetic, kept with the benchmark so that every
run computes a number the same way."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of all ``values``, linearly interpolated
    between order statistics (numpy's default method)."""
    v = np.asarray(values, dtype=np.float64)
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(v, q))


def latencies_s(requests, give_up_s: float) -> np.ndarray:
    """Seconds from each request's due time to its answer, over every request
    due in the window; times are seconds since the window opened. A request
    that failed or was never answered counts as missing every limit: it reads
    as answered at ``give_up_s``, when the benchmark stopped waiting."""
    return np.array(
        [(r["done"] if r.get("ok") else give_up_s) - r["due"] for r in requests],
        dtype=np.float64,
    )


def rate(count: int, seconds: float) -> float:
    """Work per second: all the work over all the window's time."""
    return float(count) / float(seconds)


def mean(values):
    """Arithmetic mean, or ``None`` where there is nothing to average."""
    v = np.asarray(values, dtype=np.float64)
    return float(v.mean()) if v.size else None
