"""Open-loop arrival processes (deterministic, seeded).

Copied from ``repro.workload.arrivals`` (``poisson_arrivals``,
``bursty_arrivals``) so that the yardstick does not move with the program.
The program's ``arrival_ticks`` is left out on purpose: the benchmark drives
arrivals on the wall clock, not on the scheduler's tick grid.
"""
from __future__ import annotations

import numpy as np


def poisson_arrivals(rate: float, n: int, seed: int = 0) -> np.ndarray:
    """``n`` arrival times of a homogeneous Poisson process with ``rate``
    arrivals per unit time (i.i.d. exponential inter-arrival gaps)."""
    assert rate > 0 and n >= 0
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n)
    return np.cumsum(gaps)


def bursty_arrivals(rate: float, n: int, seed: int = 0, *,
                    burst_factor: float = 8.0,
                    mean_burst: int = 8,
                    mean_calm: int = 24) -> np.ndarray:
    """Two-state Markov-modulated Poisson arrivals with overall mean
    ``rate``: calm and burst states whose rates differ by ``burst_factor``,
    with geometric dwell lengths (in arrivals) of means ``mean_burst`` /
    ``mean_calm``; the long-run rate stays ``rate``."""
    assert rate > 0 and n >= 0 and burst_factor > 1.0
    rng = np.random.default_rng(seed)
    f_burst = mean_burst / (mean_burst + mean_calm)
    r_calm = rate * (f_burst / burst_factor + (1.0 - f_burst))
    r_burst = burst_factor * r_calm
    gaps = np.empty(n)
    i = 0
    in_burst = False
    while i < n:
        dwell = 1 + rng.geometric(1.0 / (mean_burst if in_burst
                                         else mean_calm))
        k = min(dwell, n - i)
        r = r_burst if in_burst else r_calm
        gaps[i:i + k] = rng.exponential(1.0 / r, size=k)
        i += k
        in_burst = not in_burst
    return np.cumsum(gaps)
