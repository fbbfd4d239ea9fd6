"""Sharded mutable pHNSW index: P shard-local ``MutableIndex`` replicas
of the single-shard machinery behind one mutable, globally-addressed
front (DESIGN.md § Sharded serving).

* **Global id space.** ``gid = shard * stride + local`` with ``stride``
  = the uniform per-shard buffer capacity (a power of two). Owner
  lookup is a divide — no routing table to keep consistent.
* **Routing.** Deletes and replace-upserts go to the owner shard
  (owner-offset routing: ``gid // stride``); fresh inserts round-robin
  across shards (deterministic, keeps shards balanced so the
  fixed-shape per-shard search programs stay load-matched).
* **Publication.** Every mutation republishes a stacked ``ShardedDB``
  snapshot (leaves = per-shard device buffers stacked along a leading P
  dim) under a bumped ``epoch``. In steady state no leaf changes shape
  — same zero-recompile guarantee as the single-shard index; the
  non-steady-state events are the same two (capacity growth, a shard's
  top layer rising) plus their sharded twist: growth on ANY shard grows
  ALL shards (the stride must stay uniform) and RENUMBERS global ids.
  ``reserve()`` up front, exactly like ``MutableIndex``.
* **Compaction** is deliberately NOT auto-triggered (it would renumber
  one shard's local ids and corrupt the global id space mid-traffic);
  ``delete`` always runs shard-local ``auto_compact=False``.

Search runs through ``core/distributed.py``: ``shard_search_host`` on a
single device (simulated shards), ``distributed_search`` when a mesh is
provided — the two are bit-equal.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from repro.configs.base import PHNSWConfig
from repro.core.distributed import (ShardedDB, build_shard_graphs,
                                    distributed_search, place_stacked,
                                    shard_search_host)
from repro.core.filters import FilterSpec, make_filter
from repro.distributed import faults as _faults
from repro.index.mutable import (MutableIndex, read_snapshot,
                                 write_snapshot)
from repro.obs.trace import NULL_SPAN


class ShardedMutableIndex:
    """P shard-local mutable indexes + one stacked device snapshot."""

    def __init__(self, shards: Sequence[MutableIndex], filt: FilterSpec,
                 cfg: PHNSWConfig, mesh=None):
        """``mesh`` (a ``core.distributed.serving_mesh``) places every
        stacked leaf's shard s on the device at ``model`` index s; the
        shard indexes should hold their buffers there already (``build``
        does that), so publishing moves nothing between devices."""
        assert len(shards) >= 1
        self.shards: List[MutableIndex] = list(shards)
        self.filt = filt
        self.cfg = cfg
        self.mesh = mesh
        self.epoch = 0
        self._rr = 0                      # round-robin insert cursor
        self._align_capacity()
        self._publish()

    @classmethod
    def build(cls, x: np.ndarray, cfg: PHNSWConfig, n_shards: int, *,
              seed: int = 0, filt: Optional[FilterSpec] = None,
              builder: Optional[str] = None,
              mesh=None) -> "ShardedMutableIndex":
        """Fit ONE shared filter on the full dataset, partition
        (remainder distributed), and build each shard's graph + mutable
        index independently — through the one construction pipeline
        (``builder`` defaults to ``cfg.builder``, the wave pipeline;
        equal-sized shards reuse its compiled probe program, and the
        shard indexes' subsequent wave inserts share it too). With a
        ``mesh`` the shards build concurrently and each shard index
        lives on its own device."""
        filt = filt or make_filter(cfg, x, seed=seed)
        graphs = build_shard_graphs(x, cfg, n_shards, seed=seed,
                                    builder=builder, mesh=mesh)
        devs = [None] * n_shards if mesh is None \
            else list(mesh.devices.reshape(-1))
        shards = [MutableIndex.from_graph(g, filt, seed=seed + 101 * s + 1,
                                          device=devs[s])
                  for s, g in enumerate(graphs)]
        return cls(shards, filt, cfg, mesh=mesh)

    # ------------------------------------------------------------------
    # id space / aggregates
    # ------------------------------------------------------------------

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def stride(self) -> int:
        """Global-id stride = the uniform per-shard capacity. Changes
        only on capacity growth (which renumbers global ids)."""
        return self.shards[0].cap

    @property
    def n_live(self) -> int:
        return sum(s.n_live for s in self.shards)

    @property
    def tombstone_frac(self) -> float:
        n = sum(s.n for s in self.shards)
        return sum(s.n_deleted for s in self.shards) / max(n, 1)

    @property
    def sdb(self) -> ShardedDB:
        """The current epoch's stacked device snapshot."""
        return self._sdb

    def owner(self, gids: np.ndarray) -> np.ndarray:
        return np.asarray(gids, np.int64) // self.stride

    def live_global_ids(self) -> np.ndarray:
        """Global ids of live nodes across all shards, ascending."""
        return np.concatenate([s.live_ids() + i * self.stride
                               for i, s in enumerate(self.shards)])

    # uniform mutable-index surface (benchmarks/serving treat the two
    # index kinds interchangeably; ids are GLOBAL here)
    live_ids = live_global_ids

    def pca_drift(self) -> dict:
        """The WORST per-shard drift report (every shard shares one
        frozen filter, so any shard crossing the refit threshold means
        the global projection needs a refit), with the per-shard
        reports attached."""
        reps = [s.pca_drift() for s in self.shards]
        worst = max(reps, key=lambda r: r["drift"] or 0.0)
        return {**worst, "per_shard": reps}

    def live_ground_truth(self, q: np.ndarray, at: int) -> np.ndarray:
        """Exact top-``at`` over the global LIVE set, as GLOBAL ids."""
        from repro.data.vectors import brute_force_topk
        gids = self.live_global_ids()
        x = np.concatenate([s.x[s.live_ids()] for s in self.shards])
        return gids[brute_force_topk(x, q, at)]

    def is_deleted(self, gids: np.ndarray) -> np.ndarray:
        """Tombstone flags for global ids (pad slots count as deleted)."""
        gids = np.asarray(gids, np.int64)
        sh, loc = gids // self.stride, gids % self.stride
        return np.array([self.shards[int(s)].deleted[int(l)]
                         for s, l in zip(sh.ravel(), loc.ravel())],
                        bool).reshape(gids.shape)

    # ------------------------------------------------------------------
    # capacity / publication
    # ------------------------------------------------------------------

    def _align_capacity(self) -> None:
        cap = max(s.cap for s in self.shards)
        for s in self.shards:
            if s.cap < cap:
                s.reserve(cap)

    def reserve(self, per_shard_capacity: int) -> None:
        """Pre-grow EVERY shard (the stride must stay uniform): pay the
        one growth recompile + global-id renumbering now, before
        traffic."""
        for s in self.shards:
            s.reserve(per_shard_capacity)
        self._align_capacity()
        self._publish()

    def _publish(self, span=NULL_SPAN) -> None:
        """Stack the per-shard device snapshots into a new epoch's
        ShardedDB. Pure data movement — in steady state every leaf
        keeps its shape, so compiled search programs are reused. An
        installed ``FaultPlan``'s ``delay_swap`` event stretches the
        window between mutation and publication (readers keep the
        previous epoch — the swap stays atomic, just late; a trace span
        records the injected delay as a ``delay_swap`` event)."""
        pub = span.child("publish", epoch=self.epoch + 1)
        plan = _faults.active()
        if plan is not None:
            slept = plan.swap_delay_hook()
            if slept > 0.0:
                pub.event("delay_swap", seconds=slept)
        n_pub = max(s.top for s in self.shards) + 1
        per = [s.device_layers(n_pub) for s in self.shards]
        stride = self.stride
        Pn = self.n_shards
        stack = lambda parts: place_stacked(parts, self.mesh)
        self.epoch += 1
        self._sdb = ShardedDB(
            adj=[stack([adj[l] for adj, _ in per]) for l in range(n_pub)],
            packed_low=[stack([pck[l] for _, pck in per])
                        for l in range(n_pub)],
            low=stack([s._dev_low for s in self.shards]),
            high=stack([s._dev_high for s in self.shards]),
            entries=stack([np.int32(s.entry) for s in self.shards]),
            offsets=stack([np.int32(i * stride) for i in range(Pn)]),
            counts=stack([np.int32(stride)] * Pn),
            cfg=self.cfg,
            deleted=stack([s._dev_deleted for s in self.shards]),
            low2=None if self.shards[0]._dev_low2 is None else
            stack([s._dev_low2 for s in self.shards]),
            filter_kind=self.filt.kind,
        )
        pub.set(n_layers=n_pub)
        pub.end()

    # ------------------------------------------------------------------
    # mutation
    # ------------------------------------------------------------------

    def upsert(self, xs: np.ndarray,
               ids: Optional[np.ndarray] = None, *,
               span=NULL_SPAN) -> np.ndarray:
        """Insert vectors (with ``ids``: tombstone those global ids
        first — replace semantics). Fresh inserts round-robin across
        shards. Returns the new GLOBAL ids, aligned with ``xs``. If any
        shard had to grow, ALL shards grow and previously handed-out
        global ids are renumbered (reserve() up front to avoid).
        ``span`` records per-shard routing events and the publish."""
        if ids is not None:
            # publish once at the end — the intermediate post-delete
            # snapshot would never be served
            self._delete(ids, span=span)
        xs = np.asarray(xs, np.float32)
        Pn = self.n_shards
        assign = (self._rr + np.arange(len(xs))) % Pn
        self._rr = (self._rr + len(xs)) % Pn
        plan = _faults.active()
        locs = {}
        for s in range(Pn):
            m = assign == s
            if m.any():
                # a killed shard rejects its slice BEFORE any shard
                # state changes for it (typed ShardKilledError; slices
                # already applied to healthy shards stay applied — the
                # caller retries the batch or reroutes)
                if plan is not None:
                    plan.shard_mutation_hook(s)
                span.event("route_upsert", shard=s, n=int(m.sum()))
                locs[s] = (m, self.shards[s].upsert(xs[m]))
        # gids are computed AFTER the post-insert capacity alignment so
        # a mid-batch growth can't hand out ids under a stale stride
        self._align_capacity()
        stride = self.stride
        gids = np.empty(len(xs), np.int64)
        for s, (m, loc) in locs.items():
            gids[m] = s * stride + loc
        self._publish(span=span)
        return gids

    def delete(self, gids: np.ndarray, *, span=NULL_SPAN) -> int:
        """Tombstone global ids on their owner shards (owner-offset
        routing; idempotent, out-of-range ids ignored). Returns the
        number newly deleted. Never auto-compacts (compaction would
        renumber the global id space)."""
        n = self._delete(gids, span=span)
        if n:
            self._publish(span=span)
        return n

    def _delete(self, gids: np.ndarray, *, span=NULL_SPAN) -> int:
        """Shard-local tombstoning without the snapshot publish."""
        gids = np.atleast_1d(np.asarray(gids, np.int64))
        stride = self.stride
        plan = _faults.active()
        n = 0
        for s in range(self.n_shards):
            m = (gids >= 0) & (gids // stride == s)
            if m.any():
                if plan is not None:
                    plan.shard_mutation_hook(s)
                span.event("route_delete", shard=s, n=int(m.sum()))
                n += self.shards[s].delete(gids[m] % stride,
                                           auto_compact=False)
        return n

    # ------------------------------------------------------------------
    # snapshot (one npz for all shards — the replica-shipping unit)
    # ------------------------------------------------------------------

    def save(self, path) -> None:
        """Snapshot EVERY shard plus the global-id bookkeeping into one
        npz (per-shard arrays live under an ``s{i}_`` prefix), sealed
        by the same integrity envelope as ``MutableIndex.save`` — one
        file is the unit replica re-seeding ships."""
        arrays = {"n_shards": np.int64(self.n_shards),
                  "rr": np.int64(self._rr),
                  "sharded_epoch": np.int64(self.epoch)}
        for i, s in enumerate(self.shards):
            for k, v in s._snapshot_arrays().items():
                arrays[f"s{i}_{k}"] = v
        write_snapshot(path, arrays)

    @classmethod
    def load(cls, path, cfg: PHNSWConfig, *, seed: int = 0
             ) -> "ShardedMutableIndex":
        """Restore a ``save``d sharded index (typed
        ``SnapshotCorruptError`` on integrity failure). Per-shard rng
        seeds are re-derived exactly as ``build`` derives them, so a
        restored replica draws the same insert levels as one that
        lived through the same history from the same seed."""
        z = read_snapshot(path)
        Pn = int(z["n_shards"])
        shards = []
        for i in range(Pn):
            pre = f"s{i}_"
            zi = {k[len(pre):]: v for k, v in z.items()
                  if k.startswith(pre)}
            shards.append(MutableIndex._from_arrays(
                zi, cfg, seed=seed + 101 * i + 1))
        idx = cls(shards, shards[0].filt, cfg)
        idx._rr = int(z["rr"])
        idx.epoch = int(z["sharded_epoch"])
        return idx

    # ------------------------------------------------------------------
    # search
    # ------------------------------------------------------------------

    def search(self, queries: np.ndarray, *, mesh=None, **kw):
        """Batched sharded search over the current epoch: the mesh
        collective path when ``mesh`` is given, the bit-equal
        single-device loop otherwise. Returns ([B, ef0] dists, [B, ef0]
        GLOBAL ids)."""
        q = jnp.asarray(queries)
        if mesh is not None:
            return distributed_search(mesh, self._sdb, q, filt=self.filt,
                                      **kw)
        return shard_search_host(self._sdb, q, filt=self.filt, **kw)
