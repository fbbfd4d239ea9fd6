"""PCA transform for pHNSW Step 1 (paper Fig. 1(c)): project the database
from dim -> d_low, preserving maximum variance.

Fit is exact (eigendecomposition of the covariance; numpy, done once at
index-build time on the host). ``transform`` is the host projection;
``transform_jnp`` / ``project_jnp`` are its device twin, at f32. The
transform keeps distances approximately: ||P(x) - P(q)||^2 <= ||x -
q||^2 (orthonormal rows), so low-dim distances underestimate true
distances — the property the filter relies on."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp


def project_jnp(x, mean, components):
    """(x - mean) @ components on the device at f32: a TPU's default
    matmul precision is one bf16 pass, so the projection asks for
    ``Precision.HIGHEST``."""
    return jnp.matmul(x - mean, components,
                      precision=jax.lax.Precision.HIGHEST)


@dataclass
class PCA:
    mean: np.ndarray        # [D]
    components: np.ndarray  # [D, d_low]  (orthonormal columns)
    explained: np.ndarray   # [d_low] fraction of variance per component
    # device-array cache for device_arrays: wrapping mean/components with
    # jnp.asarray on every call re-pays a host->device transfer per
    # query batch; the projection matrices are frozen after fit, so they
    # are uploaded once and reused (excluded from ==/repr)
    _mean_jnp: Optional[jnp.ndarray] = field(
        default=None, init=False, repr=False, compare=False)
    _components_jnp: Optional[jnp.ndarray] = field(
        default=None, init=False, repr=False, compare=False)

    @property
    def d_low(self) -> int:
        return self.components.shape[1]

    def transform(self, x):
        return (x - self.mean) @ self.components

    def device_arrays(self):
        """(mean, components) as device arrays, uploaded once."""
        if self._mean_jnp is None:
            self._mean_jnp = jnp.asarray(self.mean)
            self._components_jnp = jnp.asarray(self.components)
        return self._mean_jnp, self._components_jnp

    def transform_jnp(self, x):
        return project_jnp(x, *self.device_arrays())

    def inverse(self, z):
        return z @ self.components.T + self.mean


def fit_pca(x: np.ndarray, d_low: int) -> PCA:
    """x: [N, D] float; exact PCA via covariance eigendecomposition."""
    x = np.asarray(x, np.float64)
    mean = x.mean(axis=0)
    xc = x - mean
    cov = xc.T @ xc / max(len(x) - 1, 1)
    w, v = np.linalg.eigh(cov)            # ascending
    order = np.argsort(w)[::-1][:d_low]
    comps = v[:, order]
    explained = w[order] / max(w.sum(), 1e-12)
    return PCA(mean.astype(np.float32), comps.astype(np.float32),
               explained.astype(np.float32))
