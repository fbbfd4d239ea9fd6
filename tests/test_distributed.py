"""Distribution: sharding-rule coverage, fault-tolerance logic, gradient
compression, multi-device sharded search + cross-mesh checkpoint restore
(subprocess with forced host device count), and the sharded pHNSW
serving path at full feature parity (ISSUE-4): 1-shard bit-equality for
every filter kind x rerank mode, remainder-distribution regression,
property-based cross-shard merge invariants, a seeded stress sweep vs
the sharded host oracle, a sharded churn scenario (zero steady-state
recompiles, rebuild recall parity), and the golden 8k recall-floor
fixture."""
import dataclasses
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.configs import ARCH_IDS, get_config, get_smoke_config, SHAPES
from repro.distributed import sharding as shd
from repro.distributed.fault import (GradSkipPolicy, StepMonitor,
                                     healthy_mesh_shape, remesh)
from repro.models import get_model
from repro.optim.compression import compress_grads, decompress_grads

RERANK_MULT = 3


@pytest.fixture(scope="module")
def shard_filters(small_dataset, small_graph, small_pca):
    """One shared FilterSpec per kind, fitted on the FULL small dataset
    (the sharded contract: one filter, many shard graphs)."""
    from repro.core.filters import IdentityFilter, PCAFilter, make_filter
    x, _, _ = small_dataset
    cfg_pq = dataclasses.replace(small_graph.cfg, filter_kind="pq",
                                 pq_train_iters=3)
    cfg_c = dataclasses.replace(cfg_pq, filter_kind="cascade",
                                pq_train_iters=8)
    return {
        "pca": PCAFilter(small_pca),
        "pq": make_filter(cfg_pq, x, seed=0),
        "cascade": make_filter(cfg_c, x, seed=0, pca=small_pca,
                               levels=small_graph.levels),
        "none": IdentityFilter(dim=x.shape[1]),
    }


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_rules_cover_all_archs(arch):
    """Every parameter leaf of every arch must have a sharding rule, with
    correct rank, on the production mesh axis sizes."""
    cfg = get_config(arch)
    api = get_model(cfg)
    a_params = api.abstract_params()

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    specs = shd.param_specs(cfg, a_params, FakeMesh())
    flat = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "_normalized_spec") or True)
    n = len(jax.tree.leaves(a_params))
    assert len(jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))) == n


def test_param_specs_divisibility():
    """No spec may shard a non-divisible dim (whisper's vocab 51865)."""
    cfg = get_config("whisper-medium")
    api = get_model(cfg)
    a_params = api.abstract_params()

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    specs = shd.param_specs(cfg, a_params, FakeMesh())
    flat_p = jax.tree_util.tree_flatten_with_path(a_params)[0]
    flat_s = jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    for (path, leaf), spec in zip(flat_p, flat_s):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is not None:
                size = {"data": 16, "model": 16}[ax]
                assert dim % size == 0, (path, leaf.shape, spec)


def test_step_monitor_straggler_detection():
    mon = StepMonitor(straggler_factor=2.0)
    for i in range(10):
        ev = mon.heartbeat(i, 1.0)
        assert ev.kind == "ok"
    ev = mon.heartbeat(10, 5.0)
    assert ev.kind == "straggler"
    ev = mon.heartbeat(11, 1.1)
    assert ev.kind == "ok"


def test_grad_skip_policy():
    pol = GradSkipPolicy(planned=8)
    for _ in range(6):
        pol.complete()
    assert pol.should_skip_rest(elapsed_s=100.0, deadline_s=10.0)
    assert not GradSkipPolicy(planned=8, completed=2).should_skip_rest(100, 10)
    assert pol.renorm() == pytest.approx(8 / 6)


def test_healthy_mesh_shape():
    assert healthy_mesh_shape(256) == (16, 16)
    assert healthy_mesh_shape(240) == (15, 16)
    with pytest.raises(RuntimeError):
        healthy_mesh_shape(8, model_parallel=16)


def test_compression_roundtrip():
    tree = {"a": jnp.asarray(np.random.default_rng(0)
                             .standard_normal((300, 17)), jnp.float32),
            "b": jnp.ones((5,), jnp.float32)}
    comp = compress_grads(tree)
    back = decompress_grads(comp, tree)
    for k in tree:
        err = np.abs(np.asarray(back[k]) - np.asarray(tree[k])).max()
        scale = np.abs(np.asarray(tree[k])).max()
        assert err <= scale / 127 * 1.01
    nbytes = sum(np.asarray(c["q"]).nbytes + np.asarray(c["scale"]).nbytes
                 for c in jax.tree.leaves(
                     comp, is_leaf=lambda t: isinstance(t, dict) and "q" in t))
    orig = sum(np.asarray(v).nbytes for v in tree.values())
    assert nbytes < orig / 3   # ~4x compression minus scale overhead


@pytest.mark.parametrize("kind", ["pca", "pq", "cascade", "none"])
@pytest.mark.parametrize("deferred", [False, True])
def test_distributed_single_shard_parity_bit_equal(
        small_dataset, small_graph, shard_filters, kind, deferred):
    """The ISSUE-4 acceptance bar: a 1-shard mesh runs the IDENTICAL
    program as single-shard search_batched for EVERY filter kind and
    re-rank mode — global ids and dists bit-equal, offsets 0, the
    all-gather/merge a no-op, and the deferred global re-rank reduced
    to the single-shard one. Covers both the meshless host loop and
    (for the canonical pca mode) the shard_map collective path."""
    from repro.core.distributed import (build_sharded, distributed_search,
                                        shard_search_host)
    from repro.core.search_jax import build_packed, search_batched
    x, q, gt = small_dataset
    filt = shard_filters[kind]
    db = build_packed(small_graph, filt.encode(x), filt=filt,
                      drop_empty_layers=False)
    sdb = build_sharded(x, small_graph.cfg, filt, 1, graphs=[small_graph])
    qd = jnp.asarray(q)
    qp = filt.prepare_jnp(qd)
    fd_b, fi_b = search_batched(db, qd, qp, deferred=deferred,
                                rerank_mult=RERANK_MULT)
    fd_h, fi_h = shard_search_host(sdb, qd, qp, deferred=deferred,
                                   rerank_mult=RERANK_MULT)
    np.testing.assert_array_equal(np.asarray(fi_h), np.asarray(fi_b))
    np.testing.assert_array_equal(np.asarray(fd_h), np.asarray(fd_b))
    if kind == "pca" and not deferred:
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        fd_d, fi_d = distributed_search(mesh, sdb, qd, qp,
                                        deferred=deferred,
                                        rerank_mult=RERANK_MULT)
        np.testing.assert_array_equal(np.asarray(fi_d), np.asarray(fi_b))
        np.testing.assert_array_equal(np.asarray(fd_d), np.asarray(fd_b))


def test_build_sharded_remainder_no_tail_drop(small_dataset, small_pca,
                                              small_graph):
    """Regression for the seed bug (`per = n // n_shards` dropped the
    n % P tail): with 4000 vectors over 3 shards every vector is owned
    by exactly one shard, and the TAIL vectors — unindexed entirely
    under the old code — are found as their own nearest neighbor."""
    from repro.core.distributed import (build_sharded, shard_bounds,
                                        shard_search_host)
    x, _, _ = small_dataset
    cfg = small_graph.cfg
    n, P = len(x), 3
    assert n % P != 0, "fixture must exercise a non-divisible split"
    bounds = shard_bounds(n, P)
    assert bounds[-1][1] == n
    assert sum(e - s for s, e in bounds) == n
    assert max(e - s for s, e in bounds) - \
        min(e - s for s, e in bounds) <= 1           # balanced
    sdb = build_sharded(x, cfg, small_pca, P)
    assert int(sdb.counts.sum()) == n
    # query the exact tail vectors: d(x, x) = 0 must win slot 0
    tail = np.arange(n - 5, n)
    qd = jnp.asarray(x[tail])
    qp = jnp.asarray(small_pca.transform(x[tail]).astype(np.float32))
    _, fi = shard_search_host(sdb, qd, qp)
    np.testing.assert_array_equal(np.asarray(fi)[:, 0], tail)


# --------- property-based cross-shard merge invariants (ISSUE-4) -----------

@settings(max_examples=40, deadline=None, derandomize=True)
@given(st.integers(1, 5), st.integers(1, 12), st.data())
def test_cross_shard_merge_invariants(P, E, data):
    """_merge_lists over P per-shard sorted lists: output sorted, a
    multiset-subset of the inputs, global ids in each shard's range,
    stable under duplicate distances / all-INF rows / k=1 / P=1 (where
    it must be the identity on the already-sorted input)."""
    from collections import Counter
    from repro.constants import INF
    from repro.core.distributed import _merge_lists
    pool = [0.0, 1.0, 1.0, 2.0, 2.5, float(np.float32(INF))]
    per = 100                                     # ids per shard range
    fd, fi = [], []
    for s in range(P):
        d = np.sort(np.asarray(
            data.draw(st.lists(st.sampled_from(pool),
                               min_size=E, max_size=E)), np.float32))
        ids = np.where(d < np.float32(INF),
                       np.arange(E, dtype=np.int32) + s * per, -1)
        fd.append(d)
        fi.append(ids)
    k = data.draw(st.integers(1, P * E))
    md, mi = _merge_lists(jnp.asarray(np.stack(fd))[:, None],
                          jnp.asarray(np.stack(fi))[:, None], k)
    md, mi = np.asarray(md[0]), np.asarray(mi[0])
    assert md.shape == (k,) and np.all(np.diff(md) >= 0)     # sorted
    # ids live in their owning shard's global range (or the -1 pad)
    for v in mi:
        assert v == -1 or 0 <= v % per < E
        assert v == -1 or 0 <= v // per < P
    have = Counter(zip(md.tolist(), mi.tolist()))
    src = Counter()
    for s in range(P):
        src.update(zip(fd[s].tolist(), fi[s].tolist()))
    for pair, c in have.items():
        assert src[pair] >= c, (pair, c)                     # subset
    if P == 1 and k == E:
        np.testing.assert_array_equal(md, fd[0])             # identity
        np.testing.assert_array_equal(mi, fi[0])


@settings(deadline=None, max_examples=40)
@given(E=st.integers(2, 12), data=st.data())
def test_global_promote_invariants(E, data):
    """_global_promote (the cascade's cross-shard mid-stage trim) is a
    STABLE sort of the merged list by promote-stage distance with -1
    pads pushed to INF, trimmed to n_keep — bit-equal to the host
    oracle's np.argsort(kind="stable") spelling, including duplicate
    distances, all-pad rows, and n_keep shorter than the valid set."""
    from repro.constants import INF
    from repro.core.distributed import _global_promote
    pool = [0.0, 1.0, 1.0, 2.0, 3.5]
    dm = np.asarray(data.draw(st.lists(st.sampled_from(pool),
                                       min_size=E, max_size=E)),
                    np.float32)
    mask = np.asarray(data.draw(st.lists(st.booleans(),
                                         min_size=E, max_size=E)))
    ids = np.where(mask, np.arange(E, dtype=np.int32) + 100,
                   np.int32(-1))
    n_keep = data.draw(st.integers(1, E))
    pd, pi = _global_promote(jnp.asarray(ids)[None],
                             jnp.asarray(dm)[None], n_keep)
    pd, pi = np.asarray(pd[0]), np.asarray(pi[0])
    keyed = np.where(ids >= 0, dm, np.float32(INF))
    order = np.argsort(keyed, kind="stable")
    np.testing.assert_array_equal(pd, keyed[order][:n_keep])
    np.testing.assert_array_equal(
        pi, np.where(ids >= 0, ids, -1)[order][:n_keep])


# --------- seeded stress: engine vs sharded oracle (ISSUE-4) ---------------

def test_sharded_stress_vs_oracle(small_dataset, small_graph,
                                  shard_filters):
    """Randomized seeded stress sweep: the sharded batched engine vs
    the sharded ``search_ref`` oracle across ALL filter x deferred x
    tombstone combinations on a remainder-bearing 3-shard split. The
    two implement one algorithm, so beyond recall parity (<= 0.02) the
    returned id SETS must agree on nearly every query (disagreements
    are float-tie edge cases, amplified by PQ's quantized lattice).
    The engine always carries a bitmap here (empty == no tombstones),
    so one compiled program serves both tombstone arms."""
    from repro.core.distributed import (build_sharded, shard_bounds,
                                        shard_search_host)
    from repro.core.graph import build_hnsw
    from repro.core.search_ref import recall_at, search_sharded
    x, q, gt = small_dataset
    cfg = small_graph.cfg
    P = 3
    rng = np.random.default_rng(42)
    bounds = shard_bounds(len(x), P)
    graphs = [build_hnsw(x[a:b], cfg, seed=7 + s)
              for s, (a, b) in enumerate(bounds)]
    doomed = np.zeros(len(x), bool)
    doomed[rng.choice(len(x), 200, replace=False)] = True
    doomed[gt[:12, 0]] = True                 # kill true answers too
    nq = 12
    for kind, filt in shard_filters.items():
        payloads = [filt.encode(x[a:b]) for a, b in bounds]
        mids = ([filt.encode_mid(x[a:b]) for a, b in bounds]
                if hasattr(filt, "encode_mid") else None)
        for tombs in (False, True):
            deleted = doomed if tombs else np.zeros(len(x), bool)
            dels = [deleted[a:b] for a, b in bounds]
            sdb = build_sharded(x, cfg, filt, P, graphs=graphs,
                                deleted=deleted)
            qd = jnp.asarray(q[:nq])
            qp = filt.prepare_jnp(qd)
            for deferred in ([False, True] if kind != "none"
                             else [False]):
                pm = max(cfg.promote_mult, RERANK_MULT)
                _, fi = shard_search_host(sdb, qd, qp,
                                          deferred=deferred,
                                          rerank_mult=RERANK_MULT,
                                          promote_mult=pm)
                fi = np.asarray(fi)
                assert not deleted[fi.ravel()].any(), \
                    (kind, tombs, deferred)
                r_b, r_r, exact = [], [], 0
                for i in range(nq):
                    ids, _ = search_sharded(
                        graphs, filt, payloads, q[i], deleted=dels,
                        deferred=deferred, rerank_mult=RERANK_MULT,
                        promote_mult=pm, payload_mids=mids)
                    assert not deleted[ids].any()
                    r_r.append(recall_at(ids, gt[i], 10))
                    r_b.append(recall_at(fi[i], gt[i], 10))
                    if set(ids.tolist()) == \
                            set(fi[i][:len(ids)].tolist()):
                        exact += 1
                tag = (kind, tombs, deferred)
                assert abs(np.mean(r_b) - np.mean(r_r)) <= 0.02, \
                    (tag, np.mean(r_b), np.mean(r_r))
                floor = 0.7 if kind in ("pq", "cascade") else 0.85
                assert exact >= floor * nq, (tag, exact, nq)


def test_sharded_churn_zero_recompile_and_rebuild_parity():
    """The sharded twin of the ISSUE-2 churn acceptance: a 2-shard
    mutable index absorbing +20% upserts and ~7% deletes through the
    serving layer triggers ZERO steady-state recompiles (jit cache
    counters of the sharded search and the per-shard insert probe),
    never surfaces a tombstoned global id, and lands recall@10 within
    0.02 of a from-scratch sharded rebuild on the final live set."""
    from repro.configs.base import PHNSWConfig
    from repro.core import distributed
    from repro.core.search_ref import recall_at
    from repro.data.vectors import make_queries, make_sift_like
    from repro.index import ShardedMutableIndex, mutable
    from repro.serve.vector_service import VectorSearchService

    cfg = PHNSWConfig(name="shch", n_points=2000, ef_construction=32)
    x_all = make_sift_like(2400, seed=21)
    x0, x_new = x_all[:2000], x_all[2000:]
    idx = ShardedMutableIndex.build(x0, cfg, 2, seed=1)
    idx.reserve(2048)      # pre-grow: uniform stride, no growth later
    svc = VectorSearchService(idx, batch_size=32)

    # warmup: compile the query program (service ctor), the per-shard
    # insert probes (first upsert round), then freeze the counters
    svc.upsert(x_new[:cfg.insert_batch])
    counters = (distributed.search_cache_sizes(),
                mutable._probe_jit._cache_size())

    svc.upsert(x_new[cfg.insert_batch:])
    rng = np.random.default_rng(2)
    doomed = rng.choice(idx.live_global_ids(), size=160, replace=False)
    svc.delete(doomed)

    q = make_queries(x_all, 32, seed=22)
    _, fi = svc.query(q)
    fi = np.asarray(fi)

    assert (distributed.search_cache_sizes(),
            mutable._probe_jit._cache_size()) == counters, \
        "steady-state sharded churn recompiled the engine"

    # tombstoned ids never surface; every id is live in its owner shard
    assert not np.isin(fi, doomed).any()
    assert (fi >= 0).all()
    assert not idx.is_deleted(fi).any()

    # recall parity vs a from-scratch sharded rebuild on the live set
    x_final = np.concatenate([s.x[s.live_ids()] for s in idx.shards])
    gt_live = idx.live_ground_truth(q, 10)
    r_mut = float(np.mean([recall_at(fi[i], gt_live[i], 10)
                           for i in range(len(q))]))
    idx2 = ShardedMutableIndex.build(x_final, cfg, 2, seed=3,
                                     filt=idx.filt)
    _, fi2 = idx2.search(q)
    fi2 = np.asarray(fi2)
    gt2 = idx2.live_ground_truth(q, 10)
    r_reb = float(np.mean([recall_at(fi2[i], gt2[i], 10)
                           for i in range(len(q))]))
    assert abs(r_mut - r_reb) <= 0.02, (r_mut, r_reb)


def test_frozen_sharded_db_serves(small_dataset, small_pca, small_graph):
    """A read-only ShardedDB behind VectorSearchService: global ids out,
    pad lanes never leak, stats correct — the serving layer takes a
    sharded backend transparently."""
    from repro.core.distributed import build_sharded
    from repro.core.search_ref import recall_at
    from repro.serve.vector_service import VectorSearchService
    x, q, gt = small_dataset
    sdb = build_sharded(x, small_graph.cfg, small_pca, 3)
    svc = VectorSearchService(sdb, small_pca, batch_size=16)
    idx_out, stats = svc.run_stream(q)
    r = float(np.mean([recall_at(idx_out[i], gt[i], 10)
                       for i in range(len(q))]))
    assert r > 0.75
    assert idx_out.shape[0] == len(q)
    assert (idx_out >= 0).all() and (idx_out < len(x)).all()
    assert svc.stats.queries == len(q)
    assert stats["p50_ms"] > 0


# --------- golden recall regression fixture (ISSUE-4) ----------------------
# Fixed-seed 8k dataset; the floors pin every compiled branch's
# recall@10 (measured at PR time minus a 0.03 margin), so a recall
# regression in any filter x rerank x shard combination fails tier-1
# instead of only moving a benchmark number.

GOLDEN_FLOORS = {
    # (kind, deferred): recall@10 floor, asserted for P=1 AND P=4.
    # Measured at PR-4 time (48 queries, seeds 11/12, graph seeds
    # 0/1..4): pca .975/.996, pq .906/.910, none .977 at P=1; every
    # P=4 value was >= its P=1 twin (the merge sees 4x ef0 candidates)
    ("pca", False): 0.94,
    ("pca", True): 0.96,
    ("pq", False): 0.87,
    ("pq", True): 0.87,
    ("none", False): 0.94,
    # the ISSUE-9 acceptance bar: the deferred cascade hits PCA-class
    # recall on PQ-class inline bytes. The P1 floor is the gate value
    # itself (deterministic fixture, measured .9958 at
    # pq_train_iters=16); the P4 twin (measured .9917 — the 2k shard
    # graphs, not the cascade, are the limiter) gets the usual
    # measured-minus-margin floor via the (P1, P4) tuple form.
    ("cascade", True): (0.995, 0.985),
}


@pytest.fixture(scope="module")
def golden8k():
    """The golden datum: fixed seeds end to end (data, queries, graph
    builds, PQ training), one shared filter per kind, shard graphs
    reused across kinds."""
    import dataclasses as _dc
    from repro.configs.base import PHNSWConfig
    from repro.core.filters import IdentityFilter, PCAFilter, make_filter
    from repro.core.graph import build_hnsw
    from repro.core.pca import fit_pca
    from repro.core.distributed import shard_bounds
    from repro.data.vectors import (brute_force_topk, make_queries,
                                    make_sift_like)
    cfg = PHNSWConfig(name="golden8k", n_points=8000, ef_construction=32)
    x = make_sift_like(8000, seed=11)
    q = make_queries(x, 48, seed=12)
    gt = brute_force_topk(x, q, 10)
    pca = fit_pca(x, cfg.d_low)
    g1 = build_hnsw(x, cfg, seed=0)
    graphs4 = [build_hnsw(x[a:b], cfg, seed=1 + s)
               for s, (a, b) in enumerate(shard_bounds(8000, 4))]
    filters = {
        "pca": PCAFilter(pca),
        "pq": make_filter(_dc.replace(cfg, filter_kind="pq",
                                      pq_train_iters=4), x, seed=0),
        # the cascade traverses on its codes and only promotes at the
        # exit, so code quality IS its recall ceiling: full training
        "cascade": make_filter(_dc.replace(cfg, filter_kind="cascade",
                                           pq_train_iters=16),
                               x, seed=0, pca=pca, levels=g1.levels),
        "none": IdentityFilter(dim=x.shape[1]),
    }
    return dict(cfg=cfg, x=x, q=q, gt=gt, g1=g1, graphs4=graphs4,
                filters=filters)


@pytest.mark.parametrize("kind,deferred", sorted(GOLDEN_FLOORS))
def test_golden_recall_floors(golden8k, kind, deferred):
    """Every (filter x rerank x shards) combination clears its pinned
    recall@10 floor, and the 4-shard merge costs at most 0.01 recall vs
    single-shard at matched ef0 (the ISSUE-4 acceptance bar)."""
    from repro.core.distributed import build_sharded, shard_search_host
    from repro.core.search_jax import build_packed, search_batched
    from repro.core.search_ref import recall_at
    d = golden8k
    filt = d["filters"][kind]
    db1 = build_packed(d["g1"], filt.encode(d["x"]), filt=filt)
    sdb4 = build_sharded(d["x"], d["cfg"], filt, 4, graphs=d["graphs4"])
    qd = jnp.asarray(d["q"])
    qp = filt.prepare_jnp(qd)
    _, fi1 = search_batched(db1, qd, qp, deferred=deferred)
    _, fi4 = shard_search_host(sdb4, qd, qp, deferred=deferred)
    fi1, fi4 = np.asarray(fi1), np.asarray(fi4)
    nq = len(d["q"])
    r1 = float(np.mean([recall_at(fi1[i], d["gt"][i], 10)
                        for i in range(nq)]))
    r4 = float(np.mean([recall_at(fi4[i], d["gt"][i], 10)
                        for i in range(nq)]))
    floor = GOLDEN_FLOORS[(kind, deferred)]
    f1, f4 = floor if isinstance(floor, tuple) else (floor, floor)
    assert r1 >= f1, (kind, deferred, "P1", r1)
    assert r4 >= f4, (kind, deferred, "P4", r4)
    assert r4 >= r1 - 0.01, (kind, deferred, r1, r4)


def test_golden_recall_floors_tombstoned(golden8k):
    """The tombstoned arm of the golden fixture (pca, per-step and
    deferred): 5% deletions incl. every rank-1 answer — live-set
    recall clears the floor, the 4-shard path stays within 0.01 of
    single-shard, and no tombstoned id ever surfaces."""
    import dataclasses as _dc
    from repro.core.distributed import build_sharded, shard_search_host
    from repro.core.search_jax import (build_packed, pack_bitmap,
                                       search_batched)
    from repro.core.search_ref import recall_at
    from repro.data.vectors import brute_force_topk
    d = golden8k
    filt = d["filters"]["pca"]
    rng = np.random.default_rng(13)
    deleted = np.zeros(8000, bool)
    deleted[rng.choice(8000, 400, replace=False)] = True
    deleted[d["gt"][:, 0]] = True
    live = np.nonzero(~deleted)[0]
    gt_live = live[brute_force_topk(d["x"][live], d["q"], 10)]
    db1 = _dc.replace(
        build_packed(d["g1"], filt.encode(d["x"]), filt=filt),
        deleted=jnp.asarray(pack_bitmap(deleted)))
    sdb4 = build_sharded(d["x"], d["cfg"], filt, 4, graphs=d["graphs4"],
                         deleted=deleted)
    qd = jnp.asarray(d["q"])
    qp = filt.prepare_jnp(qd)
    nq = len(d["q"])
    for deferred in (False, True):
        _, fi1 = search_batched(db1, qd, qp, deferred=deferred)
        _, fi4 = shard_search_host(sdb4, qd, qp, deferred=deferred)
        fi1, fi4 = np.asarray(fi1), np.asarray(fi4)
        assert not deleted[fi1.ravel()].any()
        assert not deleted[fi4.ravel()].any()
        r1 = float(np.mean([recall_at(fi1[i], gt_live[i], 10)
                            for i in range(nq)]))
        r4 = float(np.mean([recall_at(fi4[i], gt_live[i], 10)
                            for i in range(nq)]))
        assert r1 >= GOLDEN_FLOORS[("pca", deferred)] - 0.02, \
            (deferred, r1)
        assert r4 >= r1 - 0.01, (deferred, r1, r4)


def test_golden_degraded_recall_floor(golden8k):
    """The ISSUE-6 acceptance bar on the golden 8k datum: killing k of
    4 shards serves DEGRADED with (a) exact coverage accounting, (b)
    full-ground-truth recall monotonically non-increasing in k (losing
    shards only ever costs the neighbors they owned), (c) recall
    against the SURVIVORS' ground truth >= 0.90 — degraded mode
    answers as well as a healthy index built on just the survivors —
    and (d) no dead shard's id ever surfacing."""
    from repro.core.distributed import (build_sharded, shard_bounds,
                                        shard_live_counts,
                                        shard_search_host)
    from repro.core.search_ref import recall_at
    from repro.data.vectors import brute_force_topk
    d = golden8k
    filt = d["filters"]["pca"]
    sdb4 = build_sharded(d["x"], d["cfg"], filt, 4, graphs=d["graphs4"])
    qd = jnp.asarray(d["q"])
    qp = filt.prepare_jnp(qd)
    bounds = shard_bounds(8000, 4)
    lc = shard_live_counts(sdb4)
    nq = len(d["q"])
    prev = None
    for k_dead in range(3):                     # nested dead sets
        mask = np.ones(4, bool)
        mask[:k_dead] = False
        fd, fi, st = shard_search_host(sdb4, qd, qp, live=mask,
                                       return_stats=True)
        fi = np.asarray(fi)
        assert st["coverage"] == pytest.approx(
            lc[mask].sum() / lc.sum())          # exact, not estimated
        assert st["degraded"] == (k_dead > 0)
        for s in range(4):                      # dead ids never surface
            if not mask[s]:
                a, b = bounds[s]
                assert not ((fi >= a) & (fi < b)).any()
        r_full = float(np.mean([recall_at(fi[i], d["gt"][i], 10)
                                for i in range(nq)]))
        if prev is not None:
            assert r_full <= prev + 0.02, (k_dead, prev, r_full)
        prev = r_full
        rows = np.concatenate([np.arange(a, b)
                               for s, (a, b) in enumerate(bounds)
                               if mask[s]])
        gt_s = rows[brute_force_topk(d["x"][rows], d["q"], 10)]
        r_surv = float(np.mean([recall_at(fi[i], gt_s[i], 10)
                                for i in range(nq)]))
        assert r_surv >= 0.90, (k_dead, r_surv)


def test_search_batched_explicit_entry(small_dataset, small_graph,
                                       small_xlow, small_pca):
    """The explicit entry override reaches the descent: seeding from the
    db's own entry reproduces the default result exactly."""
    from repro.core.search_jax import build_packed, search_batched
    x, q, gt = small_dataset
    db = build_packed(small_graph, small_xlow)
    ql = jnp.asarray(small_pca.transform(q).astype(np.float32))
    fd0, fi0 = search_batched(db, jnp.asarray(q), ql)
    fd1, fi1 = search_batched(db, jnp.asarray(q), ql, entry=db.entry)
    np.testing.assert_array_equal(np.asarray(fi0), np.asarray(fi1))
    # a different (valid) entry still reaches high recall — the descent
    # is entry-robust, which is what the per-shard entries rely on
    alt = int(np.nonzero(small_graph.levels == small_graph.levels.max())
              [0][-1])
    _, fi2 = search_batched(db, jnp.asarray(q), ql, entry=alt)
    fi2 = np.asarray(fi2)
    from repro.core.search_ref import recall_at
    r = float(np.mean([recall_at(fi2[i], gt[i], 10)
                       for i in range(len(q))]))
    assert r > 0.85


SUBPROCESS_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs.base import PHNSWConfig
    from repro.data.vectors import make_sift_like, make_queries, brute_force_topk
    from repro.core.pca import fit_pca
    from repro.core.distributed import (build_sharded, distributed_search,
                                        shard_search_host)
    from repro.core.search_ref import recall_at

    cfg = PHNSWConfig(name="t", n_points=4000, ef_construction=40)
    x = make_sift_like(4000); q = make_queries(x, 16)
    gt = brute_force_topk(x, q, 10)
    pca = fit_pca(x, cfg.d_low)
    deleted = np.zeros(4000, bool)
    deleted[gt[:, 0]] = True                 # tombstone true answers
    sdb = build_sharded(x, cfg, pca, n_shards=4, deleted=deleted)
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    ql = jnp.asarray(pca.transform(q).astype(np.float32))
    qd = jnp.asarray(q)
    # the REAL collective path (all-gather + psum over 4 devices) must
    # be bit-equal to the single-device shard loop that tier-1 locks
    # down — per-step AND deferred, tombstones active
    for deferred in (False, True):
        fd_m, fi_m = distributed_search(mesh, sdb, qd, ql,
                                        deferred=deferred, rerank_mult=3)
        fd_h, fi_h = shard_search_host(sdb, qd, ql,
                                       deferred=deferred, rerank_mult=3)
        np.testing.assert_array_equal(np.asarray(fi_m), np.asarray(fi_h))
        np.testing.assert_array_equal(np.asarray(fd_m), np.asarray(fd_h))
        fi = np.asarray(fi_m)
        assert not deleted[fi.ravel()].any()
    r = float(np.mean([recall_at(np.asarray(fi_m)[i], gt[i], 10)
                       for i in range(len(q))]))
    print("MESH==HOST OK, recall", r)
""")


@pytest.mark.slow
def test_sharded_search_multidevice():
    """8 simulated devices, 4 shards: the shard_map collective path is
    bit-equal to the host shard loop under deferred re-ranking and
    tombstones (the host loop is what the rest of tier-1 verifies)."""
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_SHARDED],
                         capture_output=True, text=True,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"},
                         cwd=Path(__file__).resolve().parents[1])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH==HOST OK" in out.stdout


SUBPROCESS_PLACEMENT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax
    from repro.configs.base import PHNSWConfig
    from repro.core.distributed import (build_sharded, serving_mesh,
                                        shard_search_host)
    from repro.core.pca import fit_pca
    from repro.data.vectors import make_sift_like, make_queries
    from repro.index import ShardedMutableIndex

    cfg = PHNSWConfig(name="t", n_points=2000, ef_construction=40)
    x = make_sift_like(2000); q = make_queries(x, 16)
    mesh = serving_mesh(4)
    devs = [str(d) for d in mesh.devices.reshape(-1)]
    placed = lambda a: [str(s.device) for s in
                        sorted(a.addressable_shards,
                               key=lambda s: s.index[0].start)]
    # frozen: concurrent per-device builds == the sequential build
    pca = fit_pca(x, 15)
    sdb = build_sharded(x, cfg, pca, 4, mesh=mesh)
    ref = build_sharded(x, cfg, pca, 4)
    for leaf in (sdb.high, sdb.adj[0], sdb.packed_low[0], sdb.entries):
        assert placed(leaf) == devs, placed(leaf)
    np.testing.assert_array_equal(np.asarray(sdb.adj[0]),
                                  np.asarray(ref.adj[0]))
    # mutable: each shard index lives on its own device, before and
    # after a mutation's republish
    idx = ShardedMutableIndex.build(x, cfg, 4, seed=1, mesh=mesh)
    assert [str(*s._dev_high.devices()) for s in idx.shards] == devs
    gids = idx.upsert(make_sift_like(40, seed=9))
    idx.delete(gids[:5])
    assert placed(idx.sdb.high) == devs and placed(idx.sdb.deleted) == devs
    fd_m, fi_m = idx.search(q, mesh=mesh)
    one = jax.device_put(idx.sdb, jax.devices()[0])
    fd_h, fi_h = shard_search_host(one, jax.numpy.asarray(q),
                                   filt=idx.filt)
    np.testing.assert_array_equal(np.asarray(fi_m), np.asarray(fi_h))
    np.testing.assert_array_equal(np.asarray(fd_m), np.asarray(fd_h))
    print("PLACEMENT OK")
""")


def test_sharded_placement_multidevice():
    """4 simulated devices: with a mesh, every stacked leaf of the frozen
    and the mutable sharded index holds shard s on device s (never
    staged whole on one device), the concurrent per-device builds give
    the sequential build's graphs, and the mesh search stays bit-equal
    to the single-device shard loop after a mutation."""
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_PLACEMENT],
                         capture_output=True, text=True,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"},
                         cwd=Path(__file__).resolve().parents[1])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PLACEMENT OK" in out.stdout


SUBPROCESS_REMESH = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import save_checkpoint, restore_checkpoint
    from repro.distributed.fault import remesh

    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    mesh8 = jax.make_mesh((2, 4), ("data", "model"))
    sh8 = {"w": NamedSharding(mesh8, P("data", "model"))}
    t8 = jax.device_put(tree, sh8)
    d = tempfile.mkdtemp()
    save_checkpoint(d, 1, t8)
    # restore onto a SMALLER mesh (elastic downscale 8 -> 4 devices)
    mesh4 = jax.make_mesh((1, 4), ("data", "model"),
                          devices=jax.devices()[:4])
    sh4 = {"w": NamedSharding(mesh4, P("data", "model"))}
    t4 = restore_checkpoint(d, 1, tree, sh4)
    np.testing.assert_array_equal(np.asarray(t4["w"]), np.asarray(tree["w"]))
    # live remesh too
    t4b = remesh(t8, sh4)
    np.testing.assert_array_equal(np.asarray(t4b["w"]), np.asarray(tree["w"]))
    print("REMESH OK")
""")


@pytest.mark.slow
def test_checkpoint_remesh_multidevice():
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_REMESH],
                         capture_output=True, text=True,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"},
                         cwd=Path(__file__).resolve().parents[1])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "REMESH OK" in out.stdout


SUBPROCESS_MOE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models import moe as moe_mod
    from repro.distributed import sharding as shd

    cfg = get_smoke_config("qwen3-moe-235b-a22b")   # 4 experts
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    p = moe_mod.init_moe(cfg, jax.random.key(0), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model), jnp.float32)
    y0, _ = moe_mod._apply_moe_local(cfg, p, x, capacity_factor=100.0)
    with shd.activation_rules({}, mesh), mesh:
        y1, m = jax.jit(lambda p, x: moe_mod.apply_moe(
            cfg, p, x, capacity_factor=100.0))(p, x)
    err = float(jnp.max(jnp.abs(y1 - y0)))
    assert err < 1e-5, err
    # gradients flow through the shard_map dispatch
    def loss(p):
        with shd.activation_rules({}, mesh):
            y, _ = moe_mod.apply_moe(cfg, p, x, capacity_factor=100.0)
        return jnp.sum(jnp.square(y))
    with mesh:
        g = jax.jit(jax.grad(loss))(p)
    gn = sum(float(jnp.sum(jnp.square(v))) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
    print("MOE OK", err)
""")


@pytest.mark.slow
def test_moe_sharded_dispatch_multidevice():
    """The shard_map expert-parallel dispatch (the qwen3 perf fix) matches
    the local oracle and is differentiable."""
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_MOE],
                         capture_output=True, text=True,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"},
                         cwd=Path(__file__).resolve().parents[1])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MOE OK" in out.stdout


SUBPROCESS_PIPELINE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax, jax.numpy as jnp
    from repro.distributed.pipeline import build_pipeline_forward

    mesh = jax.make_mesh((1, 4), ("data", "model"))
    L, M, B, S, D = 8, 6, 2, 4, 16
    params = {"w": jax.random.normal(jax.random.key(0), (L, D, D)) * 0.1}
    layer_fn = lambda lp, x: jnp.tanh(x @ lp["w"])
    xs = jax.random.normal(jax.random.key(1), (M, B, S, D))
    def seq(params, xs):
        h = xs
        for l in range(L):
            h = layer_fn({"w": params["w"][l]}, h)
        return h
    pf = build_pipeline_forward(mesh, layer_fn, L)
    with mesh:
        out = jax.jit(pf)(params, xs)
    err = float(jnp.max(jnp.abs(out - seq(params, xs))))
    assert err < 1e-5, err
    print("PIPELINE OK", err)
""")


@pytest.mark.slow
def test_pipeline_parallel_multidevice():
    """GPipe-style pipeline over the model axis == sequential forward."""
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_PIPELINE],
                         capture_output=True, text=True,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"},
                         cwd=Path(__file__).resolve().parents[1])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PIPELINE OK" in out.stdout
