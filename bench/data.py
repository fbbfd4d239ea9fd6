"""The open-loop arrival times of a run, made from its seed. The
database vectors and the query pool come from the deployment's data
generator (``datasets/<data>.py``, see ``spec``).
"""
from __future__ import annotations

import numpy as np


def poisson_arrivals(rate: float, seconds: float, seed) -> np.ndarray:
    """Arrival offsets in [0, seconds) of a Poisson stream at ``rate``
    per second. The gaps are one fixed draw for the rate and length,
    put in an order drawn from ``seed``: every seed offers the same
    number of requests over the same span, with its bursts elsewhere."""
    fixed = np.random.default_rng([int(rate * 1000), int(seconds * 1000)])
    n = int(rate * seconds * 1.2) + 64
    gaps = fixed.exponential(1.0 / rate, n)
    while gaps.sum() < seconds:
        gaps = np.concatenate([gaps, fixed.exponential(1.0 / rate, n)])
    gaps = gaps[:int(np.searchsorted(np.cumsum(gaps), seconds))]
    return np.cumsum(np.random.default_rng(seed).permutation(gaps))
