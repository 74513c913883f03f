"""Open-loop arrival schedules and schedule-to-answer timing.

The arithmetic follows the open-loop load loop of
``benchmarks/table20_overload.py``: arrivals sit on an absolute schedule
that does not wait for the server, and a request is timed from when it
was due, so a late generator counts as latency instead of hiding it.
"""
from __future__ import annotations

import numpy as np

# The gaps of every Poisson schedule come from this fixed draw; a run's
# seed only reorders them, so every seed offers the same set of gaps.
GAP_SEED = 20_250_806


def poisson_due(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window start) of a Poisson process at
    ``rate``, inside ``[0, seconds)``."""
    n = int(np.ceil(rate * seconds * 1.25)) + 16
    gaps = np.random.default_rng(GAP_SEED).exponential(1.0 / rate, size=n)
    gaps = np.random.default_rng(seed).permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]


def periodic_due(rate: float, seconds: float) -> np.ndarray:
    """Due times of a fixed-rate process at ``rate`` inside
    ``[0, seconds)``."""
    return np.arange(0.0, seconds, 1.0 / rate)


def lateness_ms(due: np.ndarray, sent: np.ndarray) -> np.ndarray:
    """How late each request was sent after it was due."""
    return (sent - due) * 1e3
