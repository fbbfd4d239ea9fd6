"""SIFT-like descriptors and their queries.

``make_sift_like`` and ``make_queries`` are copied from
``src/repro/data/vectors.py`` (same arithmetic, same draws for an
integer seed), so that the yardstick's inputs do not move when the
program's own generator is edited. ``make_queries`` also takes a seed
sequence, which keeps the query stream apart from the data stream of
the same run seed.

The deployment's file gives ``n_points``, ``dim``, ``n_clusters``,
``intrinsic``, ``noise`` and ``query_jitter``.
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


def make(cfg: dict, seed) -> np.ndarray:
    """The deployment's database vectors for run seed ``seed``."""
    return make_sift_like(
        int(cfg["n_points"]), int(cfg["dim"]),
        n_clusters=int(cfg["n_clusters"]), intrinsic=int(cfg["intrinsic"]),
        noise=float(cfg["noise"]), seed=seed)


def queries(cfg: dict, x: np.ndarray, n: int, seed_seq) -> np.ndarray:
    """``n`` queries over ``x`` drawn from the seed sequence
    ``seed_seq``."""
    return make_queries(x, n, seed=seed_seq,
                        jitter=float(cfg["query_jitter"]))
