"""Inputs of a run, made from its seed: the database vectors, the query
pool and the open-loop arrival times.

``make_sift_like`` and ``make_queries`` are copied from
``src/repro/data/vectors.py`` (same arithmetic, same draws for an
integer seed), so that the yardstick's inputs do not move when the
program's own generator is edited. ``make_queries`` also takes a seed
sequence, which keeps the query stream apart from the data stream of
the same run seed.
"""
from __future__ import annotations

import numpy as np


def make_sift_like(n: int, dim: int = 128, *, n_clusters: int = 64,
                   intrinsic: int = 16, noise: float = 0.04,
                   seed=0) -> np.ndarray:
    """[n, dim] float32, SIFT-like: clustered, low intrinsic dimension,
    non-negative, magnitudes in SIFT's typical 0..220 range."""
    rng = np.random.default_rng(seed)
    basis = rng.standard_normal((intrinsic, dim)) / np.sqrt(intrinsic)
    centers = rng.standard_normal((n_clusters, intrinsic)) * 2.2
    assign = rng.integers(0, n_clusters, size=n)
    z = centers[assign] + rng.standard_normal((n, intrinsic))
    x = z @ basis + noise * rng.standard_normal((n, dim))
    # non-negativity via offset + clip (folding would destroy the
    # low-rank structure the PCA filter relies on)
    x = np.clip(x * 20.0 + 80.0, 0.0, None)
    return x.astype(np.float32)


def make_queries(x: np.ndarray, n_queries: int, *, seed=1,
                 jitter: float = 0.05) -> np.ndarray:
    """Queries near the data manifold: perturbed database points."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(x), size=n_queries)
    q = x[idx] + jitter * x.std() * rng.standard_normal((n_queries,
                                                         x.shape[1]))
    return np.abs(q).astype(np.float32)


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
