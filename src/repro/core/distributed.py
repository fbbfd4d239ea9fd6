"""Distributed pHNSW: database sharded across the mesh (the paper's
Section VI future work — "partitioning the billion-scale database into
smaller parts while preserving efficient coordination" — built here as a
first-class feature, at full feature parity with the single-shard
engine: any filter kind, deferred re-ranking, tombstones).

Scheme (scale-out ANN as deployed in practice):
  * the dataset is partitioned into P shards along the ``model`` axis
    (remainder vectors spread over the first ``n % P`` shards — no tail
    is ever dropped); each shard gets its own independently-built HNSW
    graph (host-side, embarrassingly parallel at build time) over ONE
    shared filter (PCA projection / PQ codebook fitted on the full
    dataset, so filter distances are comparable across shards);
  * queries are sharded along the ``data`` (+``pod``) axes and
    REPLICATED along ``model``;
  * every device runs the fixed-shape batched pHNSW search
    (search_jax) over its local shard — identical compiled program, no
    cross-device traffic during traversal; tombstones ride along as the
    per-shard word-packed ``deleted`` bitmap (traversed, never
    returned);
  * per-shard top-ef results are all-gathered over ``model`` and merged
    with one kSort.L pass (global index = shard offset + local index);
  * under DEFERRED re-ranking the per-shard traversal stays purely in
    filter space and hands back the WIDE ``rerank_mult * ef0`` list;
    the cross-shard merge happens on filter distances, and ONE global
    batched Dist.H re-ranks the merged list — each shard scores only
    the merged candidates it owns and a psum assembles the row
    (total Dist.H evals per query = rerank_mult * ef0 across the whole
    mesh, same as single-shard deferred);
  * the deferred CASCADE widens the per-shard lists further to
    ``promote_mult * ef0`` PQ-space candidates, merges on PQ
    distances, and inserts a GLOBAL promote stage before the Dist.H
    pass: each shard scores the merged candidates it owns against its
    PCA side-car (``low2``) rows, a psum assembles the mid-stage row,
    and the list is trimmed to ``rerank_mult * ef0`` — so the whole
    mesh still pays exactly one batched Dist.H of the single-shard
    deferred width.

Collective cost per query batch: one all-gather of [P, B_local, E]
(dist, idx) pairs (E = ef0, or rerank_mult*ef0 when deferred,
promote_mult*ef0 for the cascade) plus, when deferred, one
[B_local, E] psum (two for the cascade) — a few KB; the traversal
itself is communication-free.

``shard_search_host`` runs the IDENTICAL program without a mesh (a
python loop over shards + the same merge/re-rank) — bit-equal to
``distributed_search`` on any mesh, so single-device CI can lock down
multi-shard semantics and the multi-device job only has to assert
mesh == host.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import PHNSWConfig
from repro.constants import INF as _INF
from repro.core.graph import build_hnsw
from repro.core.pca import PCA
from repro.core.search_jax import (PackedDB, PackedLayer, pack_bitmap,
                                   pack_host, _rank_sort_with_payload,
                                   _search_batched_impl)
from repro.kernels import ops

INF = jnp.float32(_INF)


def shard_bounds(n: int, n_shards: int) -> List[Tuple[int, int]]:
    """[start, end) per shard: ``n // P`` each, the ``n % P`` remainder
    spread one-per-shard from the front — every vector is owned by
    exactly one shard (the seed implementation silently dropped the
    tail)."""
    per, rem = divmod(n, n_shards)
    out, start = [], 0
    for s in range(n_shards):
        size = per + (1 if s < rem else 0)
        out.append((start, start + size))
        start += size
    assert start == n
    return out


@dataclass
class ShardedDB:
    """Stacked per-shard databases: every leaf has leading dim P.
    Shards may hold unequal counts (remainder distribution, per-shard
    mutation); rows are padded to a uniform per-shard height — pad rows
    have no adjacency and are never linked, so they are unreachable.
    ``counts[s]`` is the live row span of shard s (ownership test for
    the global deferred re-rank); ``offsets[s]`` maps local ids to the
    global id space. ``deleted`` (optional) stacks the per-shard
    word-packed tombstone bitmaps. ``filter_kind`` is METADATA, same
    contract as ``PackedDB``."""
    adj: List[jax.Array]          # per layer: [P, N, M_l]
    packed_low: List[jax.Array]   # per layer: [P, N, M_l, pl]
    low: jax.Array                # [P, N, pl]
    high: jax.Array               # [P, N, D]
    entries: jax.Array            # [P] int32
    offsets: jax.Array            # [P] int32 global-id offset per shard
    counts: jax.Array             # [P] int32 rows owned per shard
    cfg: PHNSWConfig
    deleted: Optional[jax.Array] = None   # [P, ceil(N/32)] int32
    low2: Optional[jax.Array] = None      # [P, N, d_low] f32 side-car
    filter_kind: str = "pca"

    @property
    def n_shards(self) -> int:
        return int(self.high.shape[0])

    def shard_db(self, s) -> PackedDB:
        """The PackedDB view of one shard (``s`` may be a traced index
        inside jit; with integer 0 after shard_map it is the local
        shard)."""
        layers = [PackedLayer(adj=a[s], packed_low=p[s])
                  for a, p in zip(self.adj, self.packed_low)]
        return PackedDB(layers=layers, low=self.low[s], high=self.high[s],
                        entry=self.entries[s], cfg=self.cfg,
                        deleted=None if self.deleted is None
                        else self.deleted[s],
                        low2=None if self.low2 is None else self.low2[s],
                        filter_kind=self.filter_kind)

    def select(self, keep) -> "ShardedDB":
        """The survivor-only twin of a degraded db: slice every stacked
        leaf down to the ``keep`` shards while KEEPING each survivor's
        original global offset — global ids and the merge tie-break
        order (lower shard first) are preserved, so searching this db
        is the host oracle that degraded-mode (live-masked) results are
        asserted bit-equal against."""
        k = jnp.asarray(np.atleast_1d(np.asarray(keep, np.int64)))
        return dataclasses.replace(
            self,
            adj=[a[k] for a in self.adj],
            packed_low=[p[k] for p in self.packed_low],
            low=self.low[k], high=self.high[k],
            entries=self.entries[k], offsets=self.offsets[k],
            counts=self.counts[k],
            deleted=None if self.deleted is None else self.deleted[k],
            low2=None if self.low2 is None else self.low2[k])


jax.tree_util.register_dataclass(
    ShardedDB,
    data_fields=["adj", "packed_low", "low", "high", "entries",
                 "offsets", "counts", "deleted", "low2"],
    meta_fields=["cfg", "filter_kind"])


def stacked_db_view(sdb: ShardedDB) -> PackedDB:
    """The STACKED PackedDB view of a ShardedDB: every leaf keeps its
    leading shard dim P (``shard_db`` strips it for one shard; this
    keeps all of them). Not searchable directly — it is the vmap
    operand of the slotted sharded programs
    (``search_jax._slot_step_sharded_jit`` / ``_slot_admit_sharded_jit``),
    which map the per-shard program over axis 0 of every leaf
    (``entries`` [P] becomes each lane's scalar ``entry``)."""
    return PackedDB(
        layers=[PackedLayer(adj=a, packed_low=p)
                for a, p in zip(sdb.adj, sdb.packed_low)],
        low=sdb.low, high=sdb.high, entry=sdb.entries, cfg=sdb.cfg,
        deleted=sdb.deleted, low2=sdb.low2, filter_kind=sdb.filter_kind)


def _pad_rows(a: np.ndarray, n: int, fill) -> np.ndarray:
    """Pad axis 0 of ``a`` to ``n`` rows with ``fill``."""
    if a.shape[0] == n:
        return a
    pad = np.full((n - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return np.concatenate([a, pad])


def serving_mesh(n_shards: int) -> Mesh:
    """The (1, P) ``("data", "model")`` mesh a P-shard index is served
    on: shard s on device s. Fails, rather than falling back to one
    device, when there are fewer devices than shards."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(f"{n_shards} shards need {n_shards} devices; "
                         f"found {len(devs)} ({devs[0].platform})")
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh((1, n_shards), ("data", "model"), (auto, auto),
                         devices=devs[:n_shards])


def place_stacked(parts, mesh: Optional[Mesh] = None) -> jax.Array:
    """Stack per-shard arrays along a new leading shard dim. With a
    ``mesh``, part s goes straight to the device at ``model`` index s
    (``NamedSharding(mesh, P("model"))``), never staged on one device;
    without one, the stack lives on the default device."""
    if mesh is None:
        return jnp.stack(parts)
    shape = (len(parts),) + tuple(np.shape(parts[0]))
    sharding = NamedSharding(mesh, P("model"))
    rows = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        part = parts[idx[0].start]
        part = part[None] if isinstance(part, jax.Array) \
            else np.asarray(part)[None]
        rows.append(jax.device_put(part, dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, rows)


def build_shard_graphs(x: np.ndarray, cfg: PHNSWConfig, n_shards: int,
                       *, seed: int = 0, builder: Optional[str] = None,
                       mesh: Optional[Mesh] = None):
    """One HNSW graph per ``shard_bounds`` partition, shard s seeded
    ``seed + s``. With a ``mesh`` every shard builds at once, shard s on
    the device at ``model`` index s: each wave probe runs on its own
    chip, and the host-side linking is numpy that mostly runs outside
    the interpreter lock. The graphs are the same either way."""
    bounds = shard_bounds(len(x), n_shards)
    devs = None if mesh is None else list(mesh.devices.reshape(-1))

    def one(s):
        a, b = bounds[s]
        on = contextlib.nullcontext() if devs is None \
            else jax.default_device(devs[s])
        with on:
            return build_hnsw(x[a:b], cfg, seed=seed + s, builder=builder)

    if devs is None:
        return [one(s) for s in range(n_shards)]
    with ThreadPoolExecutor(n_shards) as ex:
        return list(ex.map(one, range(n_shards)))


def build_sharded(x: np.ndarray, cfg: PHNSWConfig, filt, n_shards: int,
                  *, deleted: Optional[np.ndarray] = None,
                  graphs=None, seed: int = 0,
                  builder: Optional[str] = None,
                  mesh: Optional[Mesh] = None) -> ShardedDB:
    """Partition ``x`` into ``n_shards`` (remainder distributed, no tail
    dropped), build one HNSW graph per shard, and stack the packed
    databases. ``filt`` is the SHARED filter — any
    ``core.filters.FilterSpec`` fitted on the full dataset, or a bare
    ``PCA`` (the seed API, adopted as a ``PCAFilter``). ``deleted``
    ([n] bool, optional) seeds the per-shard tombstone bitmaps.
    ``graphs`` (per-shard ``HNSWGraph`` over exactly the shard_bounds
    partition) skips the builds — graphs are filter-independent, so
    callers comparing filter kinds build once. Shard builds route
    through the one construction pipeline (``builder`` defaults to
    ``cfg.builder`` — the wave pipeline; equal-sized shards share its
    compiled probe program, so P shards pay ONE compile). ``mesh`` (a
    ``serving_mesh``) builds the shards concurrently, each on its own
    device (``build_shard_graphs``), and places each shard's arrays
    there; without one every leaf lives on the default device."""
    from repro.core.filters import PCAFilter
    if isinstance(filt, PCA):
        filt = PCAFilter(filt, low_dtype=cfg.low_dtype)
    n = len(x)
    bounds = shard_bounds(n, n_shards)
    n_max = max(e - s for s, e in bounds)
    if graphs is None:
        graphs = build_shard_graphs(x, cfg, n_shards, seed=seed,
                                    builder=builder, mesh=mesh)
    hosts, highs, entries, dels = [], [], [], []
    for s, (a, b) in enumerate(bounds):
        xs, g = x[a:b], graphs[s]
        assert len(g.x) == b - a, "graphs must match shard_bounds"
        # keep layer counts uniform across shards for stacking
        hosts.append(pack_host(g, filt.encode(xs), filt=filt,
                               drop_empty_layers=False))
        highs.append(_pad_rows(np.asarray(xs, np.float32), n_max, 0))
        entries.append(np.int32(g.entry))
        if deleted is not None:
            # pad slots marked deleted too (unreachable, but the bitmap
            # shape must stack)
            d = _pad_rows(deleted[a:b].astype(bool), n_max, True)
            dels.append(pack_bitmap(d))
    stack = lambda xs: place_stacked(xs, mesh)
    pad = lambda arrs, fill: stack([_pad_rows(a, n_max, fill)
                                    for a in arrs])
    n_layers = len(hosts[0].adj)
    return ShardedDB(
        adj=[pad([h.adj[l] for h in hosts], -1) for l in range(n_layers)],
        packed_low=[pad([h.packed_low[l] for h in hosts], 0)
                    for l in range(n_layers)],
        low=pad([h.low for h in hosts], 0),
        high=stack(highs),
        entries=stack(entries),
        offsets=stack([np.int32(a) for a, _ in bounds]),
        counts=stack([np.int32(b - a) for a, b in bounds]),
        cfg=cfg,
        deleted=None if deleted is None else stack(dels),
        low2=None if hosts[0].low2 is None else
        pad([h.low2 for h in hosts], 0),
        filter_kind=filt.kind,
    )


# ---------------------------------------------------------------------------
# the shared per-shard + merge program (mesh and host paths run THE SAME
# traced code — bit-equal by construction)
# ---------------------------------------------------------------------------

def _shard_lists(db: PackedDB, offset, queries, qprep, *, ef0, ks,
                 deferred, rerank_mult, promote_mult=1):
    """One shard's pre-merge candidate lists: ([B, E] dists ascending,
    [B, E] GLOBAL ids). High-dim dists normally; the WIDE
    (rerank_mult * ef0 — promote_mult * ef0 for the cascade)
    filter-space list when deferred (the global promote/re-rank happens
    after the cross-shard merge)."""
    fd, fi, _, _ = _search_batched_impl(
        db, queries, qprep, ef0=ef0, k_schedule=ks, deferred=deferred,
        rerank_mult=rerank_mult, promote_mult=promote_mult,
        final_rerank=False)
    return fd, jnp.where(fi >= 0, fi + offset, -1)


def _merge_lists(fd_all, fi_all, k: int):
    """Cross-shard merge: [P, B, E] stacked per-shard ascending lists ->
    the global top-k ([B, k] dists, [B, k] ids) with one kSort.L pass
    (deterministic ties: lower shard, then lower slot)."""
    Pn, B, E = fd_all.shape
    fd_c = jnp.moveaxis(fd_all, 0, 1).reshape(B, Pn * E)
    fi_c = jnp.moveaxis(fi_all, 0, 1).reshape(B, Pn * E)
    vals, sel = ops.ksort_l(fd_c, k)
    return vals, jnp.take_along_axis(fi_c, sel, axis=1)


def _owned_dist_h(high, offset, count, gids, queries):
    """One shard's contribution to the global deferred re-rank: Dist.H
    for the merged candidates THIS shard owns, zeros elsewhere — the
    cross-shard sum (psum / host loop) assembles the full row, so the
    whole mesh pays exactly ONE batched Dist.H per query."""
    own = (gids >= offset) & (gids < offset + count)
    loc = jnp.where(own, gids - offset, 0)
    xh = jnp.take(high, loc, axis=0)                     # [B, E, D]
    return jnp.where(own, ops.dist_h(xh, queries), 0.0)


def _owned_dist_mid(low2, offset, count, gids, qpca):
    """One shard's contribution to the global cascade promote: PCA
    mid-stage dists (against the ``low2`` side-car) for the merged
    candidates THIS shard owns, zeros elsewhere — assembled by the same
    cross-shard sum as ``_owned_dist_h``."""
    own = (gids >= offset) & (gids < offset + count)
    loc = jnp.where(own, gids - offset, 0)
    mid = jnp.take(low2, loc, axis=0)                    # [B, E, d_low]
    return jnp.where(own, ops.dist_l(mid, qpca), 0.0)


def _global_promote(mi, dm, n_keep: int):
    """Sort the merged PQ-space list by the assembled mid-stage dists
    (stable — merge-order ties preserved, matching the host oracle's
    stable argsort) and trim to ``n_keep = rerank_mult * ef0``, the
    width the global Dist.H pass then pays."""
    dm = jnp.where(mi >= 0, dm, INF)
    pd, pi = _rank_sort_with_payload(dm, jnp.where(mi >= 0, mi, -1))
    return pd[:, :n_keep], pi[:, :n_keep]


def _global_rerank(md, mi, dh, ef0: int):
    """Sort the merged list by the assembled high-dim dists (stable on
    ties — same ``_rank_sort_with_payload`` as the single-shard deferred
    re-rank) and trim to ef0."""
    dh = jnp.where(mi >= 0, dh, INF)
    rd, ri = _rank_sort_with_payload(dh, jnp.where(mi >= 0, mi, -1))
    return rd[:, :ef0], ri[:, :ef0]


def _normalize(sdb: ShardedDB, ef0, k_schedule, deferred, rerank_mult,
               promote_mult=None):
    """Default + no-op normalization, mirroring ``search_batched`` so a
    caller varying a dead knob never recompiles a bit-identical
    program."""
    cfg = sdb.cfg
    ef0 = int(ef0 or cfg.ef0)
    if deferred is None:
        deferred = cfg.deferred_rerank
    ks = tuple(k_schedule
               or cfg.k_schedule_for(sdb.filter_kind, bool(deferred)))
    if rerank_mult is None:
        rerank_mult = cfg.rerank_mult
    if promote_mult is None:
        promote_mult = cfg.promote_mult
    if sdb.filter_kind == "none":
        deferred = False
    if not deferred:
        rerank_mult = 1
    if not (deferred and sdb.filter_kind == "cascade"):
        promote_mult = 1          # dead knob outside the cascade
    else:
        # the promote pool is never narrower than the re-rank pool
        promote_mult = max(int(promote_mult), int(rerank_mult))
    return ef0, ks, bool(deferred), int(rerank_mult), int(promote_mult)


@functools.partial(jax.jit, static_argnames=("mesh", "ef0", "k_schedule",
                                             "deferred", "rerank_mult",
                                             "promote_mult"))
def _mesh_search_jit(mesh, sdb, queries, qprep, live, ef0, k_schedule,
                     deferred, rerank_mult, promote_mult):
    b_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    m_ax = "model"
    has_del = sdb.deleted is not None
    cascade = deferred and sdb.filter_kind == "cascade"

    def local_search(adj, packed_low, low, high, entry, offset, count,
                     dele, lo2, lv, q, qp):
        # leaves arrive with the leading shard dim = 1: squeeze it
        db = PackedDB(
            layers=[PackedLayer(adj=a[0], packed_low=p[0])
                    for a, p in zip(adj, packed_low)],
            low=low[0], high=high[0], entry=entry[0], cfg=sdb.cfg,
            deleted=dele[0] if has_del else None,
            low2=lo2[0] if cascade else None,
            filter_kind=sdb.filter_kind)
        fd, gi = _shard_lists(db, offset[0], q, qp, ef0=ef0,
                              ks=k_schedule, deferred=deferred,
                              rerank_mult=rerank_mult,
                              promote_mult=promote_mult)
        # degraded mode: a dead shard's lists are masked to (INF, -1)
        # — pure DATA, shapes unchanged, so kill/recover cycles reuse
        # the compiled program (zero recompiles)
        fd = jnp.where(lv[0], fd, INF)
        gi = jnp.where(lv[0], gi, -1)
        fd_all = jax.lax.all_gather(fd, m_ax, axis=0)      # [P, B, E]
        gi_all = jax.lax.all_gather(gi, m_ax, axis=0)
        E = fd.shape[1]
        md, mi = _merge_lists(fd_all, gi_all, E)
        if cascade:
            # the GLOBAL promote trim: psum-assembled PCA mid-stage
            # scores over the merged PQ-space list
            qpca = qp[:, low.shape[-1] * 256:]
            dm = jax.lax.psum(
                jnp.where(lv[0],
                          _owned_dist_mid(lo2[0], offset[0], count[0],
                                          mi, qpca), 0.0), m_ax)
            md, mi = _global_promote(mi, dm, ef0 * rerank_mult)
        if deferred:
            dh = jax.lax.psum(
                jnp.where(lv[0],
                          _owned_dist_h(high[0], offset[0], count[0],
                                        mi, q), 0.0), m_ax)
            return _global_rerank(md, mi, dh, ef0)
        return md, mi

    n_l = len(sdb.adj)
    q_spec = P(b_ax, None)
    qp_spec = P(b_ax, *([None] * (qprep.ndim - 1)))
    in_specs = (
        [P(m_ax, None, None)] * n_l,          # adj
        [P(m_ax, None, None, None)] * n_l,    # packed_low
        P(m_ax, None, None), P(m_ax, None, None),
        P(m_ax), P(m_ax), P(m_ax),
        P(m_ax, None) if has_del else P(),
        P(m_ax, None, None) if cascade else P(),
        P(m_ax),                              # live
        q_spec, qp_spec,
    )
    out_specs = (P(b_ax, None), P(b_ax, None))
    # no varying-axis typing: the per-shard search seeds while_loop
    # carries with shard-invariant constants that its body turns
    # shard-varying; the out_specs hold by construction (all_gather /
    # psum outputs)
    fn = jax.shard_map(local_search, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    dele = sdb.deleted if has_del else jnp.zeros((), jnp.int32)
    lo2 = sdb.low2 if cascade else jnp.zeros((), jnp.float32)
    return fn(sdb.adj, sdb.packed_low, sdb.low, sdb.high, sdb.entries,
              sdb.offsets, sdb.counts, dele, lo2, live, queries, qprep)


@functools.partial(jax.jit, static_argnames=("ef0", "k_schedule",
                                             "deferred", "rerank_mult",
                                             "promote_mult"))
def _host_search_jit(sdb, queries, qprep, live, ef0, k_schedule,
                     deferred, rerank_mult, promote_mult):
    """The meshless twin of ``_mesh_search_jit``: an unrolled loop over
    shards + the same merge, global promote (cascade), and global
    re-rank. all_gather == stack, psum == sum of the per-shard owned
    contributions (exactly one non-zero term per slot, so the float
    result is bit-equal). ``live`` [P] bool masks dead shards to
    (INF, -1) — data, not shape, so degraded mode never recompiles."""
    Pn = sdb.n_shards
    cascade = deferred and sdb.filter_kind == "cascade"
    fds, gis = [], []
    for s in range(Pn):
        fd, gi = _shard_lists(sdb.shard_db(s), sdb.offsets[s], queries,
                              qprep, ef0=ef0, ks=k_schedule,
                              deferred=deferred, rerank_mult=rerank_mult,
                              promote_mult=promote_mult)
        fds.append(jnp.where(live[s], fd, INF))
        gis.append(jnp.where(live[s], gi, -1))
    E = fds[0].shape[1]
    md, mi = _merge_lists(jnp.stack(fds), jnp.stack(gis), E)
    if cascade:
        qpca = qprep[:, sdb.low.shape[-1] * 256:]
        dm = jnp.zeros_like(md)
        for s in range(Pn):
            dm = dm + jnp.where(live[s],
                                _owned_dist_mid(sdb.low2[s],
                                                sdb.offsets[s],
                                                sdb.counts[s], mi, qpca),
                                0.0)
        md, mi = _global_promote(mi, dm, ef0 * rerank_mult)
    if deferred:
        dh = jnp.zeros_like(md)
        for s in range(Pn):
            dh = dh + jnp.where(live[s],
                                _owned_dist_h(sdb.high[s], sdb.offsets[s],
                                              sdb.counts[s], mi, queries),
                                0.0)
        return _global_rerank(md, mi, dh, ef0)
    return md, mi


def _prepare_qprep(sdb: ShardedDB, queries, q_low, filt):
    if q_low is not None:
        return q_low
    if filt is not None:
        if filt.kind != sdb.filter_kind:
            raise ValueError(f"filter mismatch: sharded db carries a "
                             f"{sdb.filter_kind!r} payload, filt is "
                             f"{filt.kind!r}")
        return filt.prepare_jnp(queries)
    if sdb.filter_kind == "none":
        return queries[:, :0].astype(jnp.float32)
    raise ValueError("q_low or filt required for the "
                     f"{sdb.filter_kind!r} filter")


def _norm_live(sdb: ShardedDB, live) -> jax.Array:
    """[P] bool live mask (default: everyone lives). Always a DATA
    argument of the compiled programs — all-live and degraded requests
    share one program."""
    if live is None:
        return jnp.ones((sdb.n_shards,), bool)
    return jnp.asarray(live).astype(bool)


def shard_live_counts(sdb: ShardedDB) -> np.ndarray:
    """[P] live (owned, non-tombstoned) row counts per shard — the
    denominator basis of the degraded-mode ``coverage`` stat. Counts
    each shard's ownership span minus the tombstone bits inside it
    (pad slots sit outside the span or are born tombstoned, so both
    frozen unequal shards and mutable capacity-padded shards report
    their true live population)."""
    counts = np.asarray(sdb.counts, np.int64)
    if sdb.deleted is None:
        return counts
    words = np.asarray(sdb.deleted).astype(np.uint32)       # [P, nw]
    bits = np.unpackbits(words.view(np.uint8), axis=1,
                         bitorder="little")                 # [P, nw*32]
    dead_in_span = np.array([int(bits[s, :counts[s]].sum())
                             for s in range(len(counts))], np.int64)
    return counts - dead_in_span


def coverage_stats(sdb: ShardedDB, live) -> dict:
    """The degraded-mode accounting attached to ``return_stats``
    results: ``coverage`` = fraction of the index's live vectors
    reachable through the surviving shards (exact, tombstone-aware),
    plus the raw masks/counts."""
    lc = shard_live_counts(sdb)
    lv = np.ones(sdb.n_shards, bool) if live is None \
        else np.asarray(live, bool)
    total = int(lc.sum())
    reach = int(lc[lv].sum())
    return {"coverage": reach / max(total, 1),
            "degraded": bool(~lv.all()),
            "live_shards": int(lv.sum()),
            "n_shards": sdb.n_shards,
            "live_mask": lv,
            "reachable": reach, "total_live": total}


def distributed_search(mesh: Mesh, sdb: ShardedDB, queries, q_low=None,
                       *, filt=None, ef0: int = 0, k_schedule=None,
                       deferred: Optional[bool] = None,
                       rerank_mult: Optional[int] = None,
                       promote_mult: Optional[int] = None,
                       live=None, return_stats: bool = False):
    """Sharded batched search over ``mesh``. queries: [B, D] global;
    ``q_low`` is the active filter's per-query prep (or pass ``filt``
    to compute it here; the identity filter needs neither). Returns
    (dists [B, ef0], GLOBAL idx [B, ef0]). On a 1-shard mesh this is
    bit-equal to single-shard ``search_batched`` for every filter kind
    and re-rank mode. ``live`` ([P] bool, optional) serves DEGRADED
    from the surviving shards only; with ``return_stats`` a third
    element carries the ``coverage`` accounting."""
    qprep = _prepare_qprep(sdb, queries, q_low, filt)
    ef0, ks, deferred, rm, pm = _normalize(sdb, ef0, k_schedule,
                                           deferred, rerank_mult,
                                           promote_mult)
    fd, fi = _mesh_search_jit(mesh, sdb, queries, qprep,
                              _norm_live(sdb, live), ef0, ks,
                              deferred, rm, pm)
    if return_stats:
        return fd, fi, coverage_stats(sdb, live)
    return fd, fi


def shard_search_host(sdb: ShardedDB, queries, q_low=None, *, filt=None,
                      ef0: int = 0, k_schedule=None,
                      deferred: Optional[bool] = None,
                      rerank_mult: Optional[int] = None,
                      promote_mult: Optional[int] = None,
                      live=None, return_stats: bool = False):
    """``distributed_search`` without a mesh: the same per-shard
    programs and the same merge, on however many devices exist (one is
    fine) — bit-equal to the mesh path. This is the simulated-shards
    entry point for single-device tests/benchmarks and the serving
    default when no mesh is configured. ``live`` / ``return_stats``:
    see ``distributed_search``."""
    qprep = _prepare_qprep(sdb, queries, q_low, filt)
    ef0, ks, deferred, rm, pm = _normalize(sdb, ef0, k_schedule,
                                           deferred, rerank_mult,
                                           promote_mult)
    fd, fi = _host_search_jit(sdb, queries, qprep,
                              _norm_live(sdb, live), ef0, ks,
                              deferred, rm, pm)
    if return_stats:
        return fd, fi, coverage_stats(sdb, live)
    return fd, fi


def search_cache_sizes() -> Tuple[int, int]:
    """(mesh, host) compiled-program cache sizes — the sharded
    zero-recompile assertions read these."""
    return (_mesh_search_jit._cache_size(),
            _host_search_jit._cache_size())


# ---------------------------------------------------------------------------
# the resilient per-shard path (serving plane, DESIGN.md § Fault
# tolerance): probe shards ONE AT A TIME so a failure costs exactly that
# shard's attempt, then merge whatever answered. One compiled probe
# program serves every shard (uniform stacked shapes, shard id is data),
# and the merge takes the answered mask as data — a kill/recover cycle
# never recompiles anything.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("ef0", "k_schedule",
                                             "deferred", "rerank_mult",
                                             "promote_mult"))
def _shard_probe_jit(sdb, s, queries, qprep, ef0, k_schedule, deferred,
                     rerank_mult, promote_mult):
    return _shard_lists(sdb.shard_db(s), sdb.offsets[s], queries, qprep,
                        ef0=ef0, ks=k_schedule, deferred=deferred,
                        rerank_mult=rerank_mult,
                        promote_mult=promote_mult)


def probe_shard(sdb: ShardedDB, s: int, queries, qprep, *, ef0: int = 0,
                k_schedule=None, deferred: Optional[bool] = None,
                rerank_mult: Optional[int] = None,
                promote_mult: Optional[int] = None, span=None
                ) -> Tuple[np.ndarray, np.ndarray, float]:
    """ONE shard's pre-merge candidate lists, timed and
    fault-injectable: the per-shard half of the resilient serving path
    (and the injection point of ``distributed.faults`` — kill raises,
    stall sleeps, corrupt garbles the return). Returns
    (fd [B, E], gi [B, E] GLOBAL ids, wall seconds); the wall time
    feeds the per-shard straggler monitor. ``span`` (a ``repro.obs``
    trace span, optional) receives a ``probe`` event with the measured
    wall time."""
    from repro.distributed import faults as _faults
    ef0, ks, deferred, rm, pm = _normalize(sdb, ef0, k_schedule,
                                           deferred, rerank_mult,
                                           promote_mult)
    plan = _faults.active()
    # the wall clock starts BEFORE the fault hook: an injected stall is
    # latency the coordinator actually observed, so it must feed the
    # straggler monitor like any real slow shard
    t0 = time.monotonic()
    if plan is not None:
        plan.shard_query_hook(s)
    fd, gi = _shard_probe_jit(sdb, jnp.int32(s), queries, qprep, ef0,
                              ks, deferred, rm, pm)
    gi.block_until_ready()
    wall = time.monotonic() - t0
    fd, gi = np.asarray(fd), np.asarray(gi)
    if plan is not None:
        fd, gi = plan.corrupt_hook(s, fd, gi)
    if span is not None:
        span.event("probe", shard=s, wall_ms=wall * 1e3)
    return fd, gi, wall


def check_shard_result(fd: np.ndarray, gi: np.ndarray, offset: int,
                       count: int) -> bool:
    """Merge-boundary integrity check of one shard's candidate lists:
    distances finite-or-sentinel, non-negative, ascending; ids either
    -1 (empty slot) or inside the shard's global ownership range. A
    shard failing this is treated as a ``ShardCorruptError`` — its
    answer never reaches the merge."""
    fd = np.asarray(fd)
    gi = np.asarray(gi)
    if np.isnan(fd).any() or (fd < 0).any():
        return False
    if (np.diff(fd, axis=1) < 0).any():
        return False
    ok = (gi == -1) | ((gi >= offset) & (gi < offset + count))
    return bool(ok.all())


@functools.partial(jax.jit, static_argnames=("ef0", "deferred",
                                             "cascade", "rerank_mult"))
def _merge_surviving_jit(fd_all, gi_all, live, high, offsets, counts,
                         low2, queries, qpca, ef0, deferred, cascade,
                         rerank_mult):
    """Merge the [P, B, E] per-shard stacks from ``probe_shard`` under
    an answered-mask: the same masking, merge, global promote
    (cascade), and deferred global re-rank as ``_host_search_jit`` —
    bit-equal to searching the survivor subset."""
    Pn = fd_all.shape[0]
    fd_all = jnp.where(live[:, None, None], fd_all, INF)
    gi_all = jnp.where(live[:, None, None], gi_all, -1)
    E = fd_all.shape[2]
    md, mi = _merge_lists(fd_all, gi_all, E)
    if cascade:
        dm = jnp.zeros_like(md)
        for s in range(Pn):
            dm = dm + jnp.where(live[s],
                                _owned_dist_mid(low2[s], offsets[s],
                                                counts[s], mi, qpca),
                                0.0)
        md, mi = _global_promote(mi, dm, ef0 * rerank_mult)
    if deferred:
        dh = jnp.zeros_like(md)
        for s in range(Pn):
            dh = dh + jnp.where(live[s],
                                _owned_dist_h(high[s], offsets[s],
                                              counts[s], mi, queries),
                                0.0)
        return _global_rerank(md, mi, dh, ef0)
    return md, mi


def merge_surviving(sdb: ShardedDB, fd_all, gi_all, live, queries, *,
                    qprep=None, ef0: int = 0, k_schedule=None,
                    deferred: Optional[bool] = None,
                    rerank_mult: Optional[int] = None,
                    promote_mult: Optional[int] = None):
    """Complete a request from the shards that answered: merge the
    stacked per-shard lists (dead/unanswered rows may hold anything —
    they are masked to (INF, -1) first) and run the global promote
    (cascade; needs ``qprep``, the same per-query prep handed to
    ``probe_shard``) plus the deferred global re-rank over the
    survivors. Returns ([B, ef0] dists, [B, ef0] GLOBAL ids)."""
    ef0, ks, deferred, rm, pm = _normalize(sdb, ef0, k_schedule,
                                           deferred, rerank_mult,
                                           promote_mult)
    cascade = deferred and sdb.filter_kind == "cascade"
    if cascade and qprep is None:
        raise ValueError("the deferred cascade merge needs qprep")
    low2 = sdb.low2 if cascade else jnp.zeros((), jnp.float32)
    qpca = (jnp.asarray(qprep)[:, sdb.low.shape[-1] * 256:] if cascade
            else jnp.zeros((queries.shape[0], 0), jnp.float32))
    return _merge_surviving_jit(jnp.asarray(np.asarray(fd_all)),
                                jnp.asarray(np.asarray(gi_all)),
                                _norm_live(sdb, live), sdb.high,
                                sdb.offsets, sdb.counts, low2, queries,
                                qpca, ef0, deferred, cascade, rm)


def resilient_cache_sizes() -> Tuple[int, int]:
    """(probe, merge) compiled-program cache sizes of the resilient
    path — the fault-cycle zero-recompile assertions read these."""
    return (_shard_probe_jit._cache_size(),
            _merge_surviving_jit._cache_size())
