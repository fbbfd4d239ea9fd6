"""Pluggable filter stage for the pHNSW traversal pipeline.

The paper's core idea is a *filter stage*: a cheap per-neighbor score
(PCA-projected distance) prunes candidates before expensive high-dim
re-ranking. This module makes that stage a first-class component with
three interchangeable implementations behind one contract:

  * ``PCAFilter``  — the paper's dense low-dim projection (Dist.L).
  * ``PQFilter``   — Flash [15]-style product quantization: uint8 codes
    scored by an on-device ADC gather-accumulate kernel.
  * ``IdentityFilter`` — filter bypass: every neighbor goes straight to
    Dist.H (the HNSW-Std behavior, kept as a measured baseline).

A ``FilterSpec`` owns (DESIGN.md § Filter-stage contract):

  * its **build-time payload** (``encode``): the per-vector rows stored
    in ``PackedDB.low`` and inlined per-neighbor in layout (3)
    (``PackedLayer.packed_low``) — dense f32/bf16 low-dim rows for PCA,
    uint8 codes for PQ, a zero-width array for identity;
  * its **per-query preparation**: PCA projection of the query vs.
    construction of the [S, 256] ADC lookup table (identity needs
    none). ``prepare_jnp`` is what serving runs: one jitted device
    program per kind (``_prep``), the filter's constants
    (``device_consts``) passed as arguments. ``prepare`` is the host
    oracle, for ``search_ref``, the build and the mutable index;
  * its **device expand kernel** (``expand``): the fused
    Dist.L+mask+threshold+kSort.L kernel for PCA, the fused ADC kernel
    for PQ (the engine bypasses the kernel entirely for identity);
  * its **cost-model pricing**: ``bytes_per_vec`` (layout-(3) inline
    payload bytes, the dominant sequential-burst stream) and
    ``cost_dims`` (per-point filter-distance pipeline depth).

``search_ref`` uses ``dists`` (the host numpy oracle) so the reference
and batched engines share one filter definition per kind.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import PHNSWConfig
from repro.core.pca import PCA, fit_pca, project_jnp
from repro.core.pq import (PQCodebook, adc_table_batch,
                           adc_tables_from_centroids, encode_pq,
                           train_pq)
from repro.kernels import ops


@functools.partial(jax.jit, static_argnums=0)
def _prepare_jit(prep, consts, q):
    """The device query prep of every filter kind. ``prep`` (a kind's
    ``_prep``) is static, so a kind compiles once per batch width; the
    constants are arguments, so a replaced filter of the same shapes
    reuses the program and cannot serve stale constants."""
    return prep(consts, q)


def prepare_cache_size() -> int:
    """Compiled-program count of the device query prep."""
    return _prepare_jit._cache_size()


class FilterSpec:
    """Contract shared by the three filter kinds. ``kind`` is the
    static string that keys the compiled search program (a structural
    property: each kind compiles a different expand pipeline)."""

    kind: str = "?"

    # --- build-time payload -------------------------------------------------
    def encode(self, x: np.ndarray) -> np.ndarray:
        """x [N, D] -> payload rows [N, P] (host array; P may be 0)."""
        raise NotImplementedError

    @property
    def payload_dtype(self) -> np.dtype:
        raise NotImplementedError

    @property
    def bytes_per_vec(self) -> int:
        """Layout-(3) inline payload bytes per vector (DRAM pricing)."""
        raise NotImplementedError

    @property
    def cost_dims(self) -> int:
        """Per-point filter-distance pipeline depth for the processor
        cost model (d_low for PCA, n_sub table lookups for PQ)."""
        raise NotImplementedError

    # --- per-query preparation ----------------------------------------------
    def prepare(self, q: np.ndarray) -> np.ndarray:
        """q [B, D] -> host per-query filter data (f32): the host
        oracle of ``prepare_jnp``."""
        raise NotImplementedError

    def device_consts(self) -> tuple:
        """The prep's constants as device arrays, uploaded once."""
        return ()

    @staticmethod
    def _prep(consts, q):
        """``prepare`` in jnp, from ``device_consts``."""
        raise NotImplementedError

    def prepare_jnp(self, q):
        """Device-side ``prepare``, what serving runs: q [B, D] (device
        or host) -> [B, ...] f32 on the device, one compiled program
        per kind and batch width."""
        return _prepare_jit(type(self)._prep, self.device_consts(), q)

    # --- host distance oracle (search_ref) ----------------------------------
    def dists(self, qprep_row: np.ndarray, payload: np.ndarray
              ) -> np.ndarray:
        """One query's filter distances: qprep_row = prepare(q)[i],
        payload [M, P] -> [M] f32."""
        raise NotImplementedError

    # --- device expand kernel (search_jax) ----------------------------------
    def expand(self, nb_payload, qprep, valid, th, k: int):
        """The fused expansion filter stage for this kind (see
        ``ops.fused_expand`` / ``ops.pq_adc_expand``)."""
        raise NotImplementedError


@dataclass
class PCAFilter(FilterSpec):
    """The paper's filter: dense projection to d_low dims."""
    pca: PCA
    low_dtype: str = "float32"   # device storage dtype of the payload

    kind = "pca"

    def encode(self, x):
        return self.pca.transform(x).astype(np.float32)

    @property
    def payload_dtype(self):
        return np.dtype(np.float32)

    @property
    def bytes_per_vec(self):
        return self.pca.d_low * jnp.dtype(self.low_dtype).itemsize

    @property
    def cost_dims(self):
        return self.pca.d_low

    def prepare(self, q):
        return self.pca.transform(q).astype(np.float32)

    def device_consts(self):
        return self.pca.device_arrays()

    @staticmethod
    def _prep(consts, q):
        return project_jnp(q.astype(jnp.float32), *consts)

    def dists(self, qprep_row, payload):
        d = payload.astype(np.float32) - qprep_row
        return np.einsum("ij,ij->i", d, d)

    def expand(self, nb_payload, qprep, valid, th, k):
        return ops.fused_expand(nb_payload, qprep, valid, th, k)


@dataclass
class PQFilter(FilterSpec):
    """Flash-style PQ filter: n_sub uint8 codes per vector, scored with
    per-query ADC lookup tables."""
    cb: PQCodebook
    _cents_jnp: Optional[jnp.ndarray] = field(
        default=None, init=False, repr=False, compare=False)

    kind = "pq"

    def encode(self, x):
        return encode_pq(self.cb, x)

    @property
    def payload_dtype(self):
        return np.dtype(np.uint8)

    @property
    def bytes_per_vec(self):
        return self.cb.bytes_per_vec

    @property
    def cost_dims(self):
        return self.cb.n_sub

    def prepare(self, q):
        return adc_table_batch(self.cb, q)

    def device_consts(self):
        # codebook uploaded once (same caching story as PCA.device_arrays)
        if self._cents_jnp is None:
            self._cents_jnp = jnp.asarray(self.cb.centroids)
        return (self._cents_jnp,)

    @staticmethod
    def _prep(consts, q):
        cents, = consts
        return adc_tables_from_centroids(cents, q, jnp)

    def dists(self, qprep_row, payload):
        S = qprep_row.shape[0]
        return qprep_row[np.arange(S)[None, :],
                         payload.astype(np.int64)].sum(1)

    def expand(self, nb_payload, qprep, valid, th, k):
        return ops.pq_adc_expand(nb_payload, qprep, valid, th, k)


@dataclass
class CascadeFilter(FilterSpec):
    """Multi-stage cascade (AQR-HNSW-style, see PAPERS.md): traverse on
    cheap PQ codes, promote the surviving ``promote_mult * ef``
    candidates through a PCA mid-stage score once per layer-0 exit (not
    per step), and defer Dist.H to ONE final batched pass of
    ``rerank_mult * k`` survivors — PQ-class bytes/vec on the hot
    stream at PCA-class recall.

    Two build-time payloads:

      * **inline** (``encode``): uint8 PQ codes — the layout-(3)
        per-neighbor stream the traversal touches every step;
      * **side-car** (``encode_mid``): f32 PCA rows, stored OFF the hot
        stream (``PackedDB.low2``) and gathered once per query at the
        promote stage.

    Per-query prep is ONE flat f32 row ``[n_sub*256 + d_low]`` — the
    ADC tables flattened, then the PCA-projected query. The engine
    slices it statically on ``n_sub`` (= the inline payload width); a
    single array keeps the slot-state scatter and the shard_map specs
    rank-generic.
    """
    cb: PQCodebook
    pca: PCA
    _cents_jnp: Optional[jnp.ndarray] = field(
        default=None, init=False, repr=False, compare=False)

    kind = "cascade"

    # --- inline payload: PQ codes (what the traversal streams) --------------
    def encode(self, x):
        return encode_pq(self.cb, x)

    @property
    def payload_dtype(self):
        return np.dtype(np.uint8)

    @property
    def bytes_per_vec(self):
        return self.cb.bytes_per_vec       # inline codes only

    @property
    def cost_dims(self):
        return self.cb.n_sub               # in-loop ADC depth

    # --- side-car payload: PCA rows (the promote stage) ---------------------
    def encode_mid(self, x):
        return self.pca.transform(x).astype(np.float32)

    @property
    def mid_bytes_per_vec(self):
        return self.pca.d_low * 4          # f32 side-car rows

    @property
    def mid_cost_dims(self):
        return self.pca.d_low

    # --- per-query preparation: flat concat (luts | projected query) --------
    def prepare(self, q):
        luts = adc_table_batch(self.cb, q)
        qp = self.pca.transform(q).astype(np.float32)
        return np.concatenate([luts.reshape(len(q), -1), qp], axis=1)

    def device_consts(self):
        if self._cents_jnp is None:
            self._cents_jnp = jnp.asarray(self.cb.centroids)
        return (self._cents_jnp,) + self.pca.device_arrays()

    @staticmethod
    def _prep(consts, q):
        cents, mean, components = consts
        luts = adc_tables_from_centroids(cents, q, jnp)
        qp = project_jnp(q.astype(jnp.float32), mean, components)
        return jnp.concatenate([luts.reshape(q.shape[0], -1), qp],
                               axis=1)

    # --- host oracles --------------------------------------------------------
    def dists(self, qprep_row, payload):
        S = self.cb.n_sub
        lut = qprep_row[:S * 256].reshape(S, 256)
        return lut[np.arange(S)[None, :],
                   payload.astype(np.int64)].sum(1)

    def mid_dists(self, qprep_row, payload_mid):
        """Promote-stage distances: PCA rows vs the projected query."""
        qp = qprep_row[self.cb.n_sub * 256:]
        d = payload_mid.astype(np.float32) - qp
        return np.einsum("ij,ij->i", d, d)

    def expand(self, nb_payload, qprep, valid, th, k):
        S = self.cb.n_sub
        lut = qprep[:, :S * 256].reshape(qprep.shape[0], S, 256)
        return ops.pq_adc_expand(nb_payload, lut, valid, th, k)


@dataclass
class IdentityFilter(FilterSpec):
    """Filter bypass: no payload, no per-query prep, no expand kernel.
    The engine skips the C_pca stage entirely and ranks every valid
    neighbor in high dim — HNSW-Std as a pluggable baseline. Its
    'filter distance' IS the high-dim distance, so deferred re-ranking
    degenerates to per-step behavior (with a wider final list)."""
    dim: int = 0                 # high dim, for cost_dims

    kind = "none"

    def encode(self, x):
        return np.zeros((len(x), 0), np.float32)

    @property
    def payload_dtype(self):
        return np.dtype(np.float32)

    @property
    def bytes_per_vec(self):
        return 0

    @property
    def cost_dims(self):
        return self.dim

    def prepare(self, q):
        return q.astype(np.float32)[:, :0]     # [B, 0] — unused

    @staticmethod
    def _prep(consts, q):
        return q.astype(jnp.float32)[:, :0]

    def dists(self, qprep_row, payload):
        raise RuntimeError("identity filter has no filter distances; "
                           "the engine ranks in high dim directly")

    def expand(self, nb_payload, qprep, valid, th, k):
        raise RuntimeError("identity filter bypasses the expand kernel")


def make_filter(cfg: PHNSWConfig, x: np.ndarray, *,
                pca: Optional[PCA] = None, seed: int = 0,
                levels: Optional[np.ndarray] = None) -> FilterSpec:
    """Fit the filter selected by ``cfg.filter_kind`` on the dataset.
    A pre-fit ``pca`` is adopted (avoids double fits when callers
    already hold one). ``levels`` (optional, [n] per-point HNSW level
    assignment) trains PQ codebooks density-aware: points are weighted
    by graph-layer occupancy (``level + 1`` — the number of layers the
    node appears on, hence how often the traversal streams its codes)."""

    def _train_cb():
        # seeded RANDOM subsample, not a prefix: the sharded build
        # shares one codebook across shards partitioned contiguously
        # from x, so a prefix sample would train on the first shard(s)
        # only and skew cross-shard ADC comparability
        weights = None if levels is None else \
            np.asarray(levels, np.float64) + 1.0
        n_train = min(len(x), 20_000)
        if n_train == len(x):
            xt, wt = x, weights
        else:
            perm = np.random.default_rng(seed).permutation(
                len(x))[:n_train]
            xt = x[perm]
            wt = None if weights is None else weights[perm]
        return train_pq(xt, cfg.pq_n_sub,
                        iters=cfg.pq_train_iters, seed=seed, weights=wt)

    if cfg.filter_kind == "pca":
        return PCAFilter(pca or fit_pca(x, cfg.d_low),
                         low_dtype=cfg.low_dtype)
    if cfg.filter_kind == "pq":
        return PQFilter(_train_cb())
    if cfg.filter_kind == "cascade":
        return CascadeFilter(_train_cb(), pca or fit_pca(x, cfg.d_low))
    if cfg.filter_kind == "none":
        return IdentityFilter(dim=x.shape[1])
    raise ValueError(f"unknown filter kind {cfg.filter_kind!r}")
