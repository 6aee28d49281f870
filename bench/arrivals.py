"""Seeded request streams for the serving drivers.

``poisson`` is a copy of the system's Poisson arrival generator
(exponential gaps drawn in chunks until past the horizon, so the stream
is exact and untruncated).  Every request then draws its sensor, its row
count and where in the sensor's test window its rows start.
"""
from __future__ import annotations

import numpy as np


def poisson(rng: np.random.Generator, rate_hz: float, duration_s: float) -> np.ndarray:
    """Sorted arrival times in [0, duration_s) of a Poisson process."""
    if rate_hz <= 0 or duration_s <= 0:
        raise ValueError("rate_hz and duration_s must be positive")
    gaps, total = [], 0.0
    while total < duration_s:
        chunk = rng.exponential(1.0 / rate_hz, size=max(64, int(rate_hz)))
        gaps.append(chunk)
        total += float(chunk.sum())
    times = np.cumsum(np.concatenate(gaps))
    return times[times < duration_s]


def requests(rng: np.random.Generator, n: int, sensors: int, rows: tuple[int, int],
             window: int) -> dict[str, np.ndarray]:
    """``n`` requests: sensor id, row count (uniform in ``rows``, both ends
    included) and the first row within the sensor's ``window`` rows."""
    lo, hi = rows
    count = rng.integers(lo, hi + 1, size=n)
    return {
        "sensor": rng.integers(0, sensors, size=n),
        "rows": count,
        "start": rng.integers(0, window - count + 1),
    }
