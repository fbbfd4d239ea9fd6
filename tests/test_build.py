"""Wave-based bulk construction (core/build.py): wave-vs-sequential
recall parity on the 8k fixture across every filter kind, graph
structural invariants, fixed-seed determinism, the cache-key builder
separation, and the MutableIndex wave-insert zero-recompile
guarantee."""
import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp

from repro.configs.base import PHNSWConfig
from repro.core.build import (build_hnsw_wave, graph_invariants,
                              link_wave_layer, select_heuristic_batch)
from repro.core.graph import build_hnsw, build_hnsw_ref, cached_graph
from repro.core.search_jax import build_packed, search_batched
from repro.core.search_ref import recall_at
from repro.data.vectors import (brute_force_topk, make_queries,
                                make_sift_like)


@pytest.fixture(scope="module")
def build8k():
    """The 8k A/B fixture: the SAME (x, cfg, seed) built by both
    builders. ef_construction matches the churn scenario (32) to bound
    the sequential oracle's runtime."""
    cfg = PHNSWConfig(name="build8k", n_points=8000, ef_construction=32)
    x = make_sift_like(8000, seed=11)
    g_wave = build_hnsw(x, cfg, seed=5)          # cfg.builder == "wave"
    g_ref = build_hnsw_ref(x, cfg, seed=5)
    q = make_queries(x, 48, seed=12)
    gt = brute_force_topk(x, q, 10)
    return cfg, x, g_wave, g_ref, q, gt


@pytest.mark.parametrize("kind", ["pca", "pq", "none"])
def test_wave_vs_ref_recall_parity(build8k, kind):
    """Recall@10 of a wave-built graph never trails the sequential
    build by more than 0.01 — for every filter stage (the graph is
    filter-independent; the filter only changes the search). The bound
    is one-sided: the wave builder's richer candidate sets (full-beam
    probe + intra-wave block + symmetric peers) routinely come out
    AHEAD of the serial oracle at this ef_construction."""
    from repro.core.filters import make_filter
    cfg, x, g_wave, g_ref, q, gt = build8k
    filt = make_filter(dataclasses.replace(cfg, filter_kind=kind,
                                           pq_train_iters=4), x)
    rec = {}
    for name, g in (("wave", g_wave), ("ref", g_ref)):
        db = build_packed(g, filt=filt)
        _, fi = search_batched(db, jnp.asarray(q), filt=filt)
        fi = np.asarray(fi)
        rec[name] = float(np.mean([recall_at(fi[i], gt[i], 10)
                                   for i in range(len(q))]))
    assert rec["wave"] >= rec["ref"] - 0.01, rec


def test_wave_graph_invariants(build8k):
    """Degree bounds, -1 suffix padding, no self/dup links, links only
    to nodes at the layer, entry-reachability of every node per layer
    — and the builders share level assignment + entry for a seed."""
    cfg, x, g_wave, g_ref, q, gt = build8k
    for g in (g_wave, g_ref):
        inv = graph_invariants(g)
        assert inv["ok"], inv["violations"]
        assert all(f == 1.0 for f in inv["reachable_frac"]), \
            inv["reachable_frac"]
    np.testing.assert_array_equal(g_wave.levels, g_ref.levels)
    assert g_wave.entry == g_ref.entry
    for l, (aw, ar) in enumerate(zip(g_wave.layers, g_ref.layers)):
        assert aw.shape == ar.shape == (len(x), cfg.degree(l))


def test_wave_build_determinism():
    """Same (x, cfg, seed) -> bit-identical graph, run to run."""
    cfg = PHNSWConfig(name="det2k", n_points=2000, ef_construction=24,
                      wave_size=512)
    x = make_sift_like(2000, seed=7)
    g1 = build_hnsw_wave(x, cfg, seed=3)
    g2 = build_hnsw_wave(x, cfg, seed=3)
    assert g1.entry == g2.entry
    np.testing.assert_array_equal(g1.levels, g2.levels)
    for a1, a2 in zip(g1.layers, g2.layers):
        np.testing.assert_array_equal(a1, a2)


@pytest.mark.parametrize("ef,ef_upper", [(32, 16), (100, None)])
def test_probe_without_bitmap_matches_bitmap(build8k, monkeypatch, ef,
                                             ef_upper):
    """The wave probe over an identity-filter snapshot dedups by C/F
    membership instead of a visited bitmap: its neighborhoods are
    bit-equal to the bitmap traversal's at every layer."""
    import jax
    from repro.core import search_jax as sj
    cfg, x, g, _, q, _ = build8k
    n = len(x)
    top = int(g.levels.max()) + 1
    db = sj.PackedDB(
        layers=[sj.PackedLayer(adj=jnp.asarray(a),
                               packed_low=jnp.zeros((n, a.shape[1], 0)))
                for a in g.layers[:top]],
        low=jnp.zeros((n, 0)), high=jnp.asarray(x), entry=g.entry,
        cfg=cfg, deleted=None, filter_kind="none")
    qx = jnp.asarray(make_sift_like(64, seed=21))
    probe = lambda: [np.asarray(a) for a in sj.probe_neighborhoods(
        db, qx, jnp.zeros((64, 0)), ef, 16, filter_deleted=False,
        ef_upper=ef_upper)]
    jax.clear_caches()
    lists = probe()
    plain = sj.search_layer_batched
    monkeypatch.setattr(sj, "search_layer_batched",
                        lambda *a, **k: plain(*a, **{**k, "bitmap": True}))
    jax.clear_caches()
    bitmap = probe()
    jax.clear_caches()
    assert (lists[1][0] >= 0).any()
    for a, b in zip(lists, bitmap):
        np.testing.assert_array_equal(a, b)


def test_single_wave_build_is_searchable():
    """n < wave_size: one wave against a 1-node snapshot — the
    intra-wave block alone must produce a connected, searchable
    graph."""
    cfg = PHNSWConfig(name="one_wave", n_points=600,
                      ef_construction=24, wave_size=2048)
    x = make_sift_like(600, seed=9)
    g = build_hnsw_wave(x, cfg, seed=1)
    inv = graph_invariants(g)
    assert inv["ok"], inv["violations"]
    assert all(f == 1.0 for f in inv["reachable_frac"])
    from repro.core.pca import fit_pca
    pca = fit_pca(x, cfg.d_low)
    q = make_queries(x, 16, seed=10)
    gt = brute_force_topk(x, q, 10)
    db = build_packed(g, pca.transform(x).astype(np.float32))
    _, fi = search_batched(db, jnp.asarray(q), pca=pca)
    fi = np.asarray(fi)
    rec = float(np.mean([recall_at(fi[i], gt[i], 10)
                         for i in range(len(q))]))
    assert rec > 0.9, rec


def test_select_heuristic_batch_matches_scalar():
    """The batched Algorithm 4 agrees with the scalar oracle
    (graph._select_heuristic) node by node."""
    from repro.core.graph import _select_heuristic
    rng = np.random.default_rng(0)
    x = rng.normal(size=(200, 16)).astype(np.float32)
    B, C, m = 32, 24, 8
    cand_i = np.stack([rng.choice(200, C, replace=False)
                       for _ in range(B)]).astype(np.int64)
    qs = rng.normal(size=(B, 16)).astype(np.float32)
    cand_d = ((x[cand_i] - qs[:, None]) ** 2).sum(-1).astype(np.float32)
    o = np.argsort(cand_d, axis=1, kind="stable")
    cand_d = np.take_along_axis(cand_d, o, 1)
    cand_i = np.take_along_axis(cand_i, o, 1)
    rows, total, _ = select_heuristic_batch(x, cand_d, cand_i, m)
    for b in range(B):
        ref = _select_heuristic(
            x, [(float(d), int(i)) for d, i in zip(cand_d[b], cand_i[b])],
            m)
        assert list(rows[b][:total[b]]) == ref, b


def test_link_wave_layer_degree_bound_and_dedup():
    """Reverse linking respects the degree bound, never duplicates an
    edge, and re-selects overfull rows instead of dropping links."""
    rng = np.random.default_rng(1)
    n, m = 120, 6
    x = rng.normal(size=(n + 8, 16)).astype(np.float32)
    adj = np.full((n + 8, m), -1, np.int32)
    # a dense hub: every wave node will select node 0 (closest)
    x[0] = 0.0
    node_ids = np.arange(n, n + 8)
    x[node_ids] = rng.normal(scale=0.01, size=(8, 16)).astype(np.float32)
    C = 10
    cand_i = np.broadcast_to(np.arange(C), (8, C)).astype(np.int64).copy()
    cand_d = ((x[cand_i] - x[node_ids][:, None]) ** 2).sum(-1)
    o = np.argsort(cand_d, axis=1, kind="stable")
    cand_d = np.take_along_axis(cand_d, o, 1).astype(np.float32)
    cand_i = np.take_along_axis(cand_i, o, 1)
    dirty = link_wave_layer(x, adj, node_ids, cand_d, cand_i)
    valid = adj >= 0
    assert (valid.sum(1) <= m).all()
    # -1 padding is a suffix everywhere
    assert not (valid[:, 1:] & ~valid[:, :-1]).any()
    # no duplicate neighbors within any row
    s = np.sort(adj, axis=1)
    assert not ((s[:, 1:] == s[:, :-1]) & (s[:, 1:] >= 0)).any()
    # no self links
    assert not (adj == np.arange(len(adj))[:, None]).any()
    assert len(dirty)


def test_cached_graph_keys_builders_apart(tmp_path):
    """The cache key embeds the builder + a full-config hash: wave and
    ref builds of the same (x, seed) never collide, and a config tweak
    beyond M/efc (e.g. wave_size) gets its own entry."""
    cfg = PHNSWConfig(name="ck", n_points=400, ef_construction=16)
    x = make_sift_like(400, seed=2)
    g_w = cached_graph(x, cfg, tmp_path, seed=0)
    g_r = cached_graph(x, cfg, tmp_path, seed=0, builder="ref")
    files = sorted(p.name for p in tmp_path.glob("*.npz"))
    assert len(files) == 2, files
    assert any("_wavev" in f for f in files)
    assert any("_refv" in f for f in files)
    cfg2 = dataclasses.replace(cfg, wave_size=128)
    cached_graph(x, cfg2, tmp_path, seed=0)
    assert len(list(tmp_path.glob("*.npz"))) == 3
    # cache round-trip: reloading returns the identical graph
    g_w2 = cached_graph(x, cfg, tmp_path, seed=0)
    for a, b in zip(g_w.layers, g_w2.layers):
        np.testing.assert_array_equal(a, b)
    assert g_w2.entry == g_w.entry
    # both builders' cached graphs pass the invariant check
    for g in (g_w, g_r):
        assert graph_invariants(g)["ok"]


def test_mutable_wave_insert_zero_recompile(small_graph, small_pca):
    """Steady-state wave inserts through MutableIndex never recompile:
    the probe program (shared with the wave builder) and the search
    program stay cache-stable across churn."""
    from repro.core import search_jax
    from repro.index import MutableIndex, mutable

    idx = MutableIndex.from_graph(small_graph, small_pca, seed=1)
    idx.reserve(idx.n + 1200)
    x_new = make_sift_like(1200, seed=33)
    # warmup: compile the probe (first batch) and the search program
    # (at the steady-state query width — raw search has no pad lanes)
    idx.upsert(x_new[:idx.cfg.insert_batch])
    idx.search(x_new[:32])
    counters = (search_jax._search_batched_jit._cache_size(),
                mutable._probe_jit._cache_size())
    ids = idx.upsert(x_new[idx.cfg.insert_batch:])
    _, fi = idx.search(x_new[-32:])
    assert (search_jax._search_batched_jit._cache_size(),
            mutable._probe_jit._cache_size()) == counters, \
        "steady-state wave inserts recompiled the engine"
    # the wave-linked inserts are immediately findable
    hits = (np.asarray(fi)[:, 0] == ids[-32:])
    assert hits.mean() > 0.9
