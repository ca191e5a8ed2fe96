"""Window arithmetic: per-turn latency from the send schedule.

A turn's latency runs from its scheduled send time to its result, so a
stall shows in every turn due during it.  Every turn due in the window
counts; one that failed or never answered reads ``inf`` and so sits at
the top of the tail.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def latencies(due: np.ndarray, done: np.ndarray) -> np.ndarray:
    """Seconds from due to done per turn; ``done`` is NaN for a turn
    that failed or never answered, which reads ``inf``."""
    lat = done - due
    return np.where(np.isnan(lat), np.inf, lat)


def percentile(lat: np.ndarray, q: float) -> float:
    """The q-th percentile as an order statistic (nearest rank), so an
    unanswered turn's ``inf`` is read as such and never interpolated."""
    if lat.size == 0:
        return float("nan")
    s = np.sort(lat)
    rank = int(np.ceil(q / 100.0 * s.size)) - 1
    return float(s[min(max(rank, 0), s.size - 1)])


def summary(due: np.ndarray, done: np.ndarray, seconds: float
            ) -> Dict[str, float]:
    """End-to-end numbers of one window.  ``due`` and ``done`` are
    seconds from the window's opening; ``turns_per_s`` counts turns
    answered before the window closed."""
    lat = latencies(due, done)
    completed = int(np.sum(done <= seconds))   # NaN compares False
    return {
        "turn_p50_ms": percentile(lat, 50) * 1e3,
        "turn_p95_ms": percentile(lat, 95) * 1e3,
        "turns_per_s": completed / seconds,
        "unanswered": int(np.sum(~np.isfinite(lat))),
    }
