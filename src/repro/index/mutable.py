"""Mutable pHNSW index: online upserts, tombstone deletes, compaction,
snapshot/restore — a living index on top of the packed layout-(3)
representation (DESIGN.md § Mutable index).

The paper builds its database once (C phase) and only accelerates
search; HNSW itself, though, is natively incremental (Malkov & Yashunin
Alg. 1 *is* the insert procedure). This module makes the device-resident
``PackedDB`` mutable without ever giving up the fixed-shape compiled
search program:

* **Capacity padding.** All buffers are allocated at a power-of-two
  capacity (``>= cfg.min_capacity``). Inserts fill pre-allocated slots;
  only when capacity is exhausted do the buffers double (one recompile
  per doubling, O(log N) ever). Pad slots have no adjacency (never
  traversed) and are additionally marked in the tombstone bitmap (never
  returned).
* **Batched insert.** Inserts run through the WAVE pipeline shared
  with the bulk builder (DESIGN.md § Construction pipeline): a new
  vector's ef_construction neighborhood is found ON DEVICE by the same
  fused S-phase kernels the serving path uses
  (``search_jax.probe_neighborhoods``), one probe per insert
  sub-batch, always padded to a fixed probe width; the host then links
  the whole batch at once with the vectorized diversity heuristic
  (``core/build.link_wave`` — an intra-wave distance block supplies
  batch peers the pre-batch snapshot cannot see), followed by an
  incremental layout-(3) refresh of exactly the adjacency rows that
  changed.
* **Tombstone deletes.** Deletes flip a bit in a word-packed bitmap that
  ships with the ``PackedDB``; deleted nodes keep routing traffic
  (traversed) but are excluded from results (never returned). Same
  shapes, same compiled program.
* **Compaction.** When tombstone density crosses
  ``cfg.compact_tombstone_frac``, the graph is repaired (each live
  node's dead neighbors are replaced by live 2-hop candidates under the
  diversity heuristic), ids are remapped dense, buffers reallocated at
  the shrunk capacity, and a PCA-drift report says whether the frozen
  projection still captures the live distribution.
* **Snapshot/restore.** The whole index (vectors, adjacency, levels,
  tombstones, PCA) round-trips through one ``.npz``.

Every mutation publishes a NEW ``PackedDB`` value under a bumped
``epoch`` — readers holding the previous epoch keep a consistent frozen
view (functional arrays), and serving swaps atomically.
"""
from __future__ import annotations

import zlib
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import PHNSWConfig
from repro.distributed.faults import SnapshotCorruptError
from repro.constants import INF
from repro.core.build import link_wave, pad_rows_pow2, pairwise_sq
from repro.core.filters import (CascadeFilter, FilterSpec,
                                IdentityFilter, PCAFilter, PQFilter,
                                make_filter)
from repro.core.graph import (HNSWGraph, _select_heuristic, build_hnsw,
                              sample_levels)
from repro.core.pca import PCA, fit_pca
from repro.core.pq import PQCodebook
from repro.core.search_jax import (PackedDB, PackedLayer, pack_bitmap,
                                   probe_neighborhoods, search_batched)


def _as_filter(f, cfg: PHNSWConfig) -> FilterSpec:
    """Adopt a bare ``PCA`` (the seed API) as a ``PCAFilter``."""
    if isinstance(f, PCA):
        return PCAFilter(f, low_dtype=cfg.low_dtype)
    return f


def _next_pow2(n: int, floor: int) -> int:
    """Smallest power of two >= max(n, floor, 32). The floor itself is
    rounded up to a power of two — a non-pow2 ``cfg.min_capacity`` must
    not break the capacity invariant (doubling preserves any stray
    factor, and the bitmap packing needs 32 | cap)."""
    cap = 32
    while cap < max(int(floor), n):
        cap *= 2
    return cap


# --------------------------------------------------------------------------
# snapshot integrity envelope (shared by MutableIndex and the sharded
# stacked snapshot; the safety rail under replica snapshot shipping)
# --------------------------------------------------------------------------

# bump on any change to the snapshot array schema; loads of a different
# version raise SnapshotCorruptError instead of mis-deserializing
SNAPSHOT_VERSION = 1


def snapshot_checksum(arrays: Dict[str, np.ndarray]) -> int:
    """Order-independent crc32 over every array's name, dtype, shape,
    and bytes (the ``checksum`` entry itself excluded)."""
    crc = 0
    for k in sorted(arrays):
        if k == "checksum":
            continue
        v = np.asarray(arrays[k])
        meta = f"{k}|{v.dtype.str}|{v.shape}".encode()
        crc = zlib.crc32(v.tobytes(), zlib.crc32(meta, crc))
    return crc & 0xFFFFFFFF


def write_snapshot(path, arrays: Dict[str, np.ndarray]) -> None:
    """One compressed npz with the integrity envelope
    (``format_version`` + content ``checksum``) stamped in. Honors an
    installed ``FaultPlan``'s truncate-snapshot event (post-write) so
    corruption-detection tests exercise the REAL file path."""
    from repro.distributed import faults as _faults
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    arrays = dict(arrays)
    arrays["format_version"] = np.int64(SNAPSHOT_VERSION)
    arrays["checksum"] = np.uint32(snapshot_checksum(arrays))
    np.savez_compressed(path, **arrays)
    plan = _faults.active()
    if plan is not None:
        plan.snapshot_hook(path)


def read_snapshot(path) -> Dict[str, np.ndarray]:
    """Load + verify an npz written by ``write_snapshot``. Raises the
    typed ``SnapshotCorruptError`` on an unreadable/truncated file, a
    missing envelope, a format-version mismatch, or a content checksum
    mismatch — never garbage-deserializes."""
    try:
        with np.load(path, allow_pickle=False) as z:
            arrays = {k: np.asarray(z[k]) for k in z.files}
    except OSError as e:
        raise SnapshotCorruptError(
            f"snapshot {path} is unreadable/truncated: {e}") from None
    except Exception as e:   # zlib/zip errors on partial members, etc.
        raise SnapshotCorruptError(
            f"snapshot {path} is unreadable/truncated "
            f"(failed to deserialize): {e}") from None
    if "format_version" not in arrays or "checksum" not in arrays:
        raise SnapshotCorruptError(
            f"snapshot {path} has no integrity envelope (pre-versioned "
            f"or foreign npz)")
    ver = int(arrays.pop("format_version"))
    if ver != SNAPSHOT_VERSION:
        raise SnapshotCorruptError(
            f"snapshot {path}: format version {ver} != supported "
            f"{SNAPSHOT_VERSION}")
    want = int(arrays.pop("checksum"))
    got = snapshot_checksum(
        {**arrays, "format_version": np.int64(ver)})
    if got != want:
        raise SnapshotCorruptError(
            f"snapshot {path}: checksum mismatch "
            f"(stored {want:#010x}, computed {got:#010x})")
    return arrays


# the engine's _tombstone_bit word layout has exactly one packer
# (core/search_jax.pack_bitmap); keep the historical local name
_pack_bitmap = pack_bitmap


# O(log N)-distinct-shape dirty-row padding, shared with the wave
# builder's incremental snapshot refresh (historical local name)
_pad_rows_pow2 = pad_rows_pow2


# The on-device neighborhood probe is the wave pipeline's device half,
# hoisted to core/search_jax.py (PR-5) — the wave builder and this
# module share ONE compiled program family (and one jit cache counter,
# which the zero-recompile tests read under the historical name).
_probe_jit = probe_neighborhoods


class MutableIndex:
    """Mutable pHNSW index over capacity-padded device buffers.

    Host-side numpy mirrors hold the authoritative graph; the device
    holds the packed layout-(3) snapshot published as ``self.db`` (a
    ``PackedDB``) under a monotonically increasing ``self.epoch``.
    """

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    def __init__(self, cfg: PHNSWConfig, pca: PCA, x: np.ndarray,
                 x_low: np.ndarray, levels: np.ndarray,
                 adj: Sequence[np.ndarray], entry: int,
                 deleted: Optional[np.ndarray] = None, *, seed: int = 0,
                 epoch: int = 0, device=None):
        """Build from UNPADDED arrays ([n] rows); pads to capacity and
        publishes. ``pca`` may be a bare ``PCA`` (the seed API) or any
        ``FilterSpec``; ``x_low`` is that filter's payload rows.
        ``device`` holds the published buffers (default device when
        None) — a sharded index puts each shard on its own.
        Prefer the ``from_graph`` / ``build`` / ``load`` classmethods."""
        n = len(x)
        cap = _next_pow2(n, cfg.min_capacity)
        self.cfg = cfg
        self.device = device
        self.filt = _as_filter(pca, cfg)
        # PCA convenience handle (drift checks, seed callers): the
        # PCAFilter's projection, or the cascade's mid-stage projection;
        # None for the other filter kinds
        self.pca = getattr(self.filt, "pca", None)
        self.n, self.cap = n, cap
        self.entry = int(entry)
        self.epoch = epoch
        self.rng = np.random.default_rng(seed)
        D, dl = x.shape[1], x_low.shape[1]
        self.x = np.zeros((cap, D), np.float32)
        self.x[:n] = x
        # host mirror of the filter payload (dtype is the filter's:
        # f32 low-dim rows for PCA, uint8 codes for PQ, width 0 for
        # identity); the name survives from the PCA-only engine
        self.x_low = np.zeros((cap, dl), self.filt.payload_dtype)
        self.x_low[:n] = x_low
        # the cascade's mid-stage side-car (PCA rows scored by the
        # promote pass) — recomputed from x, so compaction/restore need
        # no extra plumbing; None for single-stage filters
        self.x_mid: Optional[np.ndarray] = None
        if hasattr(self.filt, "encode_mid"):
            xm = self.filt.encode_mid(x)
            self.x_mid = np.zeros((cap, xm.shape[1]), np.float32)
            self.x_mid[:n] = xm
        self.levels = np.full(cap, -1, np.int64)
        self.levels[:n] = levels
        # tombstones: real deletions in [:n]; pad slots are born deleted
        self.deleted = np.ones(cap, bool)
        self.deleted[:n] = deleted[:n] if deleted is not None else False
        self.n_deleted = int(self.deleted[:n].sum())
        self.adj: List[np.ndarray] = []
        for l in range(cfg.n_layers):
            a = np.full((cap, cfg.degree(l)), -1, np.int32)
            if l < len(adj):
                a[:n] = adj[l][:n]
            self.adj.append(a)
        self.top = max(int(self.levels[:n].max()), 0)
        # old-id -> new-id map of the most recent compaction (None until
        # one happens); compaction renumbers the public id space
        self.last_remap: Optional[np.ndarray] = None
        # (layer, cap) -> empty device layer, for device_layers()
        self._empty_layers: Dict = {}
        self._publish_full()

    @classmethod
    def from_graph(cls, g: HNSWGraph, pca, *, seed: int = 0, device=None
                   ) -> "MutableIndex":
        """Adopt a one-shot ``build_hnsw`` graph as the mutable seed.
        ``pca``: a fitted ``PCA`` or any ``FilterSpec``."""
        filt = _as_filter(pca, g.cfg)
        x_low = filt.encode(g.x)
        return cls(g.cfg, filt, g.x, x_low, g.levels, g.layers, g.entry,
                   seed=seed, device=device)

    @classmethod
    def build(cls, x: np.ndarray, cfg: PHNSWConfig, *, seed: int = 0
              ) -> "MutableIndex":
        """Fit the configured filter + host-build the seed graph +
        adopt it."""
        filt = make_filter(cfg, x, seed=seed)
        g = build_hnsw(x, cfg, seed=seed)
        return cls.from_graph(g, filt, seed=seed + 1)

    # ------------------------------------------------------------------
    # device publication (epoch-versioned, functional)
    # ------------------------------------------------------------------

    def _packed_rows(self, layer: int, rows: np.ndarray) -> np.ndarray:
        """Layout-(3) inline-vector refresh for a set of adjacency rows:
        re-gather each row's neighbor low-dim vectors."""
        a = self.adj[layer][rows]                      # [R, M]
        safe = np.where(a >= 0, a, 0)
        packed = self.x_low[safe]                      # [R, M, dl]
        packed[a < 0] = 0.0
        return packed

    @property
    def _dev_payload_dtype(self):
        """Device storage dtype of the filter payload: cfg.low_dtype
        for PCA (the bf16 layout-(3) option), the payload's own dtype
        (uint8 codes / zero-width f32) otherwise."""
        if self.filt.kind == "pca":
            return jnp.dtype(self.cfg.low_dtype)
        return jnp.dtype(self.x_low.dtype)

    def device_layers(self, n_pub: int):
        """The published device layers padded with cached EMPTY layers
        (all -1 adjacency, zero payload) up to ``n_pub`` >= top+1 —
        shard stacking (index/sharded.py) needs uniform layer counts
        across shards whose top layers differ. An empty layer is inert:
        the entry has no neighbors there, so its while_loop exits after
        one popped-and-dropped iteration. Returns (adj list, packed
        list)."""
        adj, packed = list(self._dev_adj), list(self._dev_packed)
        for l in range(len(adj), n_pub):
            key = (l, self.cap)
            if key not in self._empty_layers:
                M = self.cfg.degree(l)
                pl = self._dev_low.shape[1]
                self._empty_layers[key] = (
                    self._put(np.full((self.cap, M), -1, np.int32)),
                    self._put(np.zeros((self.cap, M, pl),
                                       self._dev_payload_dtype)))
            a, p = self._empty_layers[key]
            adj.append(a)
            packed.append(p)
        return adj, packed

    def _put(self, a: np.ndarray) -> jax.Array:
        """Upload a host buffer to this index's device."""
        return jax.device_put(a, self.device)

    def _publish_full(self) -> None:
        """Rebuild every device buffer (init / growth / compaction /
        top-layer change — anything that changes shapes or layer count)."""
        dt = self._dev_payload_dtype
        n_pub = self.top + 1
        all_rows = np.arange(self.cap)
        self._dev_adj = [self._put(self.adj[l]) for l in range(n_pub)]
        self._dev_packed = [self._put(self._packed_rows(l, all_rows)
                                      .astype(dt))
                            for l in range(n_pub)]
        self._dev_low = self._put(self.x_low.astype(dt))
        self._dev_high = self._put(self.x)
        self._dev_deleted = self._put(_pack_bitmap(self.deleted))
        self._dev_low2 = None if self.x_mid is None \
            else self._put(self.x_mid)
        self._swap()

    def _publish_incremental(self, dirty: List[set], new_ids: np.ndarray,
                             deleted_ids: Optional[np.ndarray] = None
                             ) -> None:
        """Refresh only what changed: new vector rows, dirty adjacency
        rows (+ their inline packed payload), and exactly the tombstone
        words whose bits flipped (``new_ids`` clear their pad-slot bits;
        ``deleted_ids`` set theirs). Payload refresh is filter-generic:
        whatever rows the active filter owns (low-dim vectors, PQ
        codes) are re-gathered for the dirty adjacency rows."""
        dt = self._dev_payload_dtype
        if len(new_ids):
            rows = _pad_rows_pow2(np.asarray(new_ids))
            self._dev_high = self._dev_high.at[rows].set(
                jnp.asarray(self.x[rows]))
            self._dev_low = self._dev_low.at[rows].set(
                jnp.asarray(self.x_low[rows], dt))
            if self._dev_low2 is not None:
                self._dev_low2 = self._dev_low2.at[rows].set(
                    jnp.asarray(self.x_mid[rows]))
        for l in range(self.top + 1):
            if not dirty[l]:
                continue
            rows = _pad_rows_pow2(np.fromiter(sorted(dirty[l]), np.int64,
                                              len(dirty[l])))
            self._dev_adj[l] = self._dev_adj[l].at[rows].set(
                jnp.asarray(self.adj[l][rows]))
            self._dev_packed[l] = self._dev_packed[l].at[rows].set(
                jnp.asarray(self._packed_rows(l, rows), dt))
        changed = np.concatenate(
            [np.asarray(new_ids, np.int64),
             np.asarray(deleted_ids, np.int64)
             if deleted_ids is not None else np.empty(0, np.int64)])
        if len(changed):
            words = _pad_rows_pow2(np.unique(changed // 32))
            w_host = np.stack([
                _pack_bitmap(self.deleted[w * 32:(w + 1) * 32])[0]
                for w in words])
            self._dev_deleted = self._dev_deleted.at[words].set(
                jnp.asarray(w_host))
        self._swap()

    def _swap(self) -> None:
        """Atomically publish a new epoch's PackedDB (plain attribute
        assignment; previous epochs stay valid frozen views)."""
        layers = [PackedLayer(adj=a, packed_low=p)
                  for a, p in zip(self._dev_adj, self._dev_packed)]
        self.epoch += 1
        self._db = PackedDB(layers=layers, low=self._dev_low,
                            high=self._dev_high, entry=self.entry,
                            cfg=self.cfg, deleted=self._dev_deleted,
                            low2=self._dev_low2,
                            filter_kind=self.filt.kind)

    @property
    def db(self) -> PackedDB:
        """The current epoch's device snapshot."""
        return self._db

    @property
    def n_live(self) -> int:
        return self.n - self.n_deleted

    @property
    def tombstone_frac(self) -> float:
        return self.n_deleted / max(self.n, 1)

    def live_ids(self) -> np.ndarray:
        """Ids of live (allocated, non-tombstoned) nodes, ascending —
        the id space results are drawn from."""
        return np.nonzero(~self.deleted[:self.n])[0]

    def live_ground_truth(self, q: np.ndarray, at: int) -> np.ndarray:
        """Exact top-``at`` neighbors of each query over the LIVE set,
        as mutable-index ids ([len(q), at]) — the yardstick every
        recall-under-churn measurement shares."""
        from repro.data.vectors import brute_force_topk
        live = self.live_ids()
        return live[brute_force_topk(self.x[live], q, at)]

    # ------------------------------------------------------------------
    # upsert
    # ------------------------------------------------------------------

    def upsert(self, xs: np.ndarray,
               ids: Optional[np.ndarray] = None) -> np.ndarray:
        """Insert vectors; with ``ids`` given, tombstone those ids first
        (replace semantics). Returns the new internal ids."""
        if ids is not None:
            self.delete(ids, auto_compact=False)
        xs = np.asarray(xs, np.float32)
        out = []
        bb = self.cfg.insert_batch
        for i in range(0, len(xs), bb):
            out.append(self._insert_batch(xs[i:i + bb]))
        return np.concatenate(out) if out else np.empty(0, np.int64)

    def reserve(self, capacity: int) -> None:
        """Pre-grow buffers to ``capacity`` (rounded up to a power of
        two): pay the one growth recompile now, before traffic, instead
        of mid-upsert."""
        if capacity > self.cap:
            self._grow(capacity)
            self._publish_full()

    def _grow(self, need: int) -> None:
        new_cap = _next_pow2(need, self.cap * 2)
        pad = new_cap - self.cap
        self.x = np.concatenate(
            [self.x, np.zeros((pad, self.x.shape[1]), np.float32)])
        self.x_low = np.concatenate(
            [self.x_low, np.zeros((pad, self.x_low.shape[1]),
                                  self.x_low.dtype)])
        if self.x_mid is not None:
            self.x_mid = np.concatenate(
                [self.x_mid, np.zeros((pad, self.x_mid.shape[1]),
                                      np.float32)])
        self.levels = np.concatenate(
            [self.levels, np.full(pad, -1, np.int64)])
        self.deleted = np.concatenate([self.deleted, np.ones(pad, bool)])
        self.adj = [np.concatenate(
            [a, np.full((pad, a.shape[1]), -1, np.int32)])
            for a in self.adj]
        self.cap = new_cap

    def _insert_batch(self, xb: np.ndarray) -> np.ndarray:
        b = len(xb)
        grew = False
        if self.n + b > self.cap:
            self._grow(self.n + b)
            grew = True
        ids = np.arange(self.n, self.n + b)
        lvls = sample_levels(b, self.cfg, self.rng)
        xl = self.filt.encode(xb)

        # --- on-device neighborhood probe (pre-batch snapshot; padded
        # to the fixed probe width so the compiled program is reused) ---
        bb = self.cfg.insert_batch
        qx = xb
        if b < bb:
            qx = np.concatenate(
                [qx, np.broadcast_to(self.x[self.entry], (bb - b,
                                                          qx.shape[1]))])
        qprep = self.filt.prepare(qx)
        fd, fi = _probe_jit(self._db, jnp.asarray(qx),
                            jnp.asarray(qprep),
                            self.cfg.ef_construction,
                            self.cfg.ef_construction_k)
        # [Lpub, bb, efc] -> drop the pad lanes of an underfull batch
        fd = np.asarray(fd)[:, :b]
        fi = np.asarray(fi)[:, :b]

        # --- host state for the batch (before linking, so intra-wave
        # peers are visible as candidates) ---
        self.x[ids] = xb
        self.x_low[ids] = xl
        if self.x_mid is not None:
            self.x_mid[ids] = self.filt.encode_mid(xb)
        self.levels[ids] = lvls
        self.deleted[ids] = False
        self.n += b

        # --- vectorized wave linking (core/build.py): batched
        # diversity selection + bidirectional linking over the whole
        # batch; the intra-wave distance block supplies batch peers the
        # pre-batch probe snapshot cannot see ---
        block = pairwise_sq(xb, xb)
        np.fill_diagonal(block, INF)
        changed = link_wave(self.x, self.adj, ids, self.levels,
                            fd, fi, block, self.cfg)
        dirty: List[set] = [set(map(int, d)) for d in changed]
        wmax = int(lvls.max())
        top_changed = wmax > self.top
        if top_changed:
            self.top = wmax
            self.entry = int(ids[int(np.argmax(lvls == wmax))])

        if grew or top_changed:
            self._publish_full()
        else:
            self._publish_incremental(dirty, ids)
        return ids

    # ------------------------------------------------------------------
    # delete / compaction
    # ------------------------------------------------------------------

    def delete(self, ids: np.ndarray, *, auto_compact: bool = True) -> int:
        """Tombstone ids (idempotent; out-of-range ids — e.g. stale
        after a compaction shrank the id space — are ignored). The nodes
        keep routing traffic but never appear in results. Returns the
        number newly deleted; triggers compaction past
        ``cfg.compact_tombstone_frac``."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        ids = ids[(ids >= 0) & (ids < self.n)]
        ids = np.unique(ids[~self.deleted[ids]])
        if len(ids) == 0:
            return 0
        self.deleted[ids] = True
        self.n_deleted += len(ids)
        self._publish_incremental([set() for _ in self.adj],
                                  np.empty(0, np.int64),
                                  deleted_ids=ids)
        if auto_compact and \
                self.tombstone_frac >= self.cfg.compact_tombstone_frac:
            self.compact()
        return len(ids)

    def compact(self) -> dict:
        """Physically drop tombstoned nodes: splice live 2-hop candidates
        over dead neighbors (diversity heuristic), remap ids dense,
        reallocate at the shrunk power-of-two capacity, and re-publish.

        COMPACTION RENUMBERS THE ID SPACE: ids handed out before it are
        stale afterward. The report's ``"remap"`` array (old id -> new
        id, -1 for dropped) — also kept as ``self.last_remap`` — lets
        callers re-resolve any ids they hold; `delete()` ignores stale
        out-of-range ids rather than crashing.

        Returns a report including the remap and the PCA-drift check."""
        n_before, frac_before = self.n, self.tombstone_frac
        live = ~self.deleted[:self.n]
        n_live = int(live.sum())
        if n_live == 0:
            raise ValueError("compact() on a fully-deleted index")
        drift = self.pca_drift()

        # --- graph repair: replace dead neighbors with live 2-hop ---
        for l in range(self.top + 1):
            A = self.adj[l]
            deg = A.shape[1]
            has_dead = np.zeros(self.n, bool)
            valid = A[:self.n] >= 0
            safe = np.where(valid, A[:self.n], 0)
            has_dead[live] = (valid & self.deleted[safe])[live].any(axis=1)
            for i in np.nonzero(has_dead)[0]:
                nb = A[i][A[i] >= 0]
                keep = [int(e) for e in nb if not self.deleted[e]]
                cand = set(keep)
                for e in nb:
                    if self.deleted[e]:
                        for f in A[e][A[e] >= 0]:
                            f = int(f)
                            if f != i and not self.deleted[f]:
                                cand.add(f)
                if not cand:
                    A[i, :] = -1
                    continue
                cl = np.fromiter(cand, np.int64, len(cand))
                ds = np.sum((self.x[cl] - self.x[i]) ** 2, axis=1)
                ordered = sorted(zip(ds.tolist(), cl.tolist()))
                sel = _select_heuristic(self.x, ordered, deg)
                A[i, :] = -1
                A[i, :len(sel)] = sel

        # --- dense remap + reallocation ---
        remap = np.full(self.n, -1, np.int64)
        remap[live] = np.arange(n_live)
        x = self.x[:self.n][live]
        x_low = self.x_low[:self.n][live]
        levels = self.levels[:self.n][live]
        adj = []
        for l in range(self.cfg.n_layers):
            A = self.adj[l][:self.n][live]
            A = np.where(A >= 0, remap[np.where(A >= 0, A, 0)], -1)
            adj.append(A.astype(np.int32))
        lv_top = int(levels.max())
        entry_cands = np.nonzero(levels == lv_top)[0]
        self.__init__(self.cfg, self.filt, x, x_low, levels, adj,
                      int(entry_cands[0]), seed=int(
                          self.rng.integers(0, 2**31 - 1)),
                      epoch=self.epoch, device=self.device)
        self.last_remap = remap
        return {"n_before": n_before, "n_after": self.n,
                "tombstone_frac_before": frac_before,
                "capacity": self.cap, "remap": remap,
                "pca_drift": drift}

    def pca_drift(self) -> dict:
        """How much variance of the LIVE distribution the frozen
        projection still captures, vs. what it captured at fit time.
        A large drop means inserts moved the data manifold and the
        low-dim filter is losing selectivity — refit offline.
        Only meaningful for the PCA filter; other kinds report no
        drift (their refit criteria live elsewhere)."""
        if self.pca is None:
            return {"captured_live": None, "captured_fit": None,
                    "drift": 0.0, "refit_recommended": False,
                    "note": f"drift check n/a for filter "
                            f"{self.filt.kind!r}"}
        live = ~self.deleted[:self.n]
        xc = self.x[:self.n][live] - self.pca.mean
        tot = float((xc * xc).sum())
        proj = xc @ self.pca.components
        captured = float((proj * proj).sum()) / max(tot, 1e-12)
        fit = float(self.pca.explained.sum())
        return {"captured_live": captured, "captured_fit": fit,
                "drift": fit - captured,
                "refit_recommended": bool(
                    fit - captured > self.cfg.pca_drift_tol)}

    # ------------------------------------------------------------------
    # search / snapshot
    # ------------------------------------------------------------------

    def search(self, queries: np.ndarray, **kw):
        """Convenience: batched search over the current epoch."""
        return search_batched(self._db, jnp.asarray(queries),
                              filt=self.filt, **kw)

    def _snapshot_arrays(self) -> Dict[str, np.ndarray]:
        """The unpadded array schema of one index snapshot (shared by
        ``save`` and the sharded stacked snapshot, which stores one of
        these per shard under a prefix)."""
        fk = self.filt.kind
        filt_arrays = {}
        if fk == "pca":
            filt_arrays = dict(pca_mean=self.pca.mean,
                               pca_components=self.pca.components,
                               pca_explained=self.pca.explained)
        elif fk == "pq":
            filt_arrays = dict(pq_centroids=self.filt.cb.centroids)
        elif fk == "cascade":
            # both stages' parameters: the PQ traversal codebook AND
            # the PCA promote projection (x_mid is recomputed on load)
            filt_arrays = dict(pq_centroids=self.filt.cb.centroids,
                               pca_mean=self.pca.mean,
                               pca_components=self.pca.components,
                               pca_explained=self.pca.explained)
        return dict(
            n=np.int64(self.n), entry=np.int64(self.entry),
            epoch=np.int64(self.epoch),
            n_layers=np.int64(self.cfg.n_layers), filter_kind=fk,
            x=self.x[:self.n], x_low=self.x_low[:self.n],
            levels=self.levels[:self.n], deleted=self.deleted[:self.n],
            **filt_arrays,
            **{f"adj{l}": self.adj[l][:self.n]
               for l in range(self.cfg.n_layers)})

    def save(self, path) -> None:
        """Snapshot the whole index (graph + vectors + tombstones +
        filter payload + filter parameters) to one npz, under the
        integrity envelope (format version + content checksum) that
        ``load`` verifies."""
        write_snapshot(path, self._snapshot_arrays())

    @classmethod
    def _from_arrays(cls, z: Dict[str, np.ndarray], cfg: PHNSWConfig,
                     *, seed: int = 0) -> "MutableIndex":
        fk = str(z["filter_kind"]) if "filter_kind" in z else "pca"
        if fk == "pca":
            filt = PCAFilter(
                PCA(mean=z["pca_mean"], components=z["pca_components"],
                    explained=z["pca_explained"]),
                low_dtype=cfg.low_dtype)
        elif fk == "pq":
            filt = PQFilter(PQCodebook(centroids=z["pq_centroids"]))
        elif fk == "cascade":
            filt = CascadeFilter(
                PQCodebook(centroids=z["pq_centroids"]),
                PCA(mean=z["pca_mean"], components=z["pca_components"],
                    explained=z["pca_explained"]))
        else:
            filt = IdentityFilter(dim=z["x"].shape[1])
        n_layers = int(z["n_layers"])
        return cls(cfg, filt, z["x"], z["x_low"], z["levels"],
                   [z[f"adj{l}"] for l in range(n_layers)],
                   int(z["entry"]), deleted=z["deleted"], seed=seed,
                   epoch=int(z["epoch"]))

    @classmethod
    def load(cls, path, cfg: PHNSWConfig, *, seed: int = 0
             ) -> "MutableIndex":
        """Restore from ``save``'s npz. Raises ``SnapshotCorruptError``
        (typed, from ``repro.distributed.faults``) on a truncated,
        bit-flipped, envelope-less, or version-mismatched file."""
        return cls._from_arrays(read_snapshot(path), cfg, seed=seed)
