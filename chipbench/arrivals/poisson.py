"""Arrivals ``poisson``: open loop at ``rate_per_s``.

``round(rate * seconds)`` requests, due at the times of a Poisson process
conditioned on that many arrivals in the window (exponential gaps scaled to
fill it). Each is sent at its due time whether or not earlier ones have been
answered.
"""

import numpy as np


def due_times(mix: dict, seconds: float, rng) -> np.ndarray:
    K = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    gaps = rng.exponential(1.0, size=K + 1)
    return np.cumsum(gaps * (seconds / gaps.sum()))[:K]


def drive(window, mix: dict, due_s) -> None:
    for i, due in enumerate(due_s):
        window.wait_until(float(due))
        window.send(i)
