"""Pluggable filter-stage pipeline: ref-vs-batched parity on every
filter x rerank combination, deferred-rerank telemetry and tombstone
semantics, payload accounting, and the generic cost-model pricing."""
import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.filters import (IdentityFilter, PCAFilter, PQFilter,
                                make_filter)
from repro.core.search_jax import build_packed, search_batched
from repro.core.search_ref import (recall_at, run_queries,
                                   search_filtered, search_hnsw)

RERANK_MULT = 3


@pytest.fixture(scope="module")
def filters(small_dataset, small_graph, small_pca):
    """One fitted FilterSpec per kind (PQ trained briefly — recall
    parity, not PQ quality, is under test here). The cascade adopts
    the shared PCA and trains its codebooks density-aware off the
    graph's level assignment."""
    x, _, _ = small_dataset
    cfg = dataclasses.replace(small_graph.cfg, filter_kind="pq",
                              pq_train_iters=3)
    # the cascade rides its codes through the whole traversal, so it
    # gets the full Lloyd schedule (same policy as the benches)
    cfg_c = dataclasses.replace(cfg, filter_kind="cascade",
                                pq_train_iters=8)
    return {
        "pca": PCAFilter(small_pca),
        "pq": make_filter(cfg, x, seed=0),
        "cascade": make_filter(cfg_c, x, seed=0, pca=small_pca,
                               levels=small_graph.levels),
        "none": IdentityFilter(dim=x.shape[1]),
    }


@pytest.fixture(scope="module")
def payloads(small_dataset, filters):
    x, _, _ = small_dataset
    return {k: f.encode(x) for k, f in filters.items()}


@pytest.fixture(scope="module")
def payload_mids(small_dataset, filters):
    """Side-car payloads for the filters that carry one (cascade)."""
    x, _, _ = small_dataset
    return {k: f.encode_mid(x) for k, f in filters.items()
            if hasattr(f, "encode_mid")}


@pytest.mark.parametrize("kind", ["pca", "pq", "cascade", "none"])
@pytest.mark.parametrize("deferred", [False, True])
def test_ref_vs_batched_parity(small_dataset, small_graph, filters,
                               payloads, payload_mids, kind, deferred):
    """search_batched and search_filtered agree on every filter x
    rerank combination: same recall@10 (within 0.02) and bit-equal
    returned id sets on (nearly) every query — the two engines run the
    same algorithm, so disagreements are confined to float-tie /
    frontier-truncation edge cases. The deferred cascade additionally
    exercises the PCA promote stage (side-car gather + mid-score trim)
    on both engines."""
    x, q, gt = small_dataset
    filt, payload = filters[kind], payloads[kind]
    db = build_packed(small_graph, payload, filt=filt)
    # mirror the engine's normalization: the promote pool can never be
    # narrower than the rerank pool (no-op outside deferred cascade)
    pm = max(small_graph.cfg.promote_mult, RERANK_MULT)
    _, fi = search_batched(db, jnp.asarray(q), filt=filt,
                           deferred=deferred, rerank_mult=RERANK_MULT,
                           promote_mult=pm)
    fi = np.asarray(fi)
    r_bat, r_ref, exact = [], [], 0
    for i in range(len(q)):
        ids, _ = search_filtered(small_graph, filt, payload, q[i],
                                 deferred=deferred,
                                 rerank_mult=RERANK_MULT,
                                 promote_mult=pm,
                                 payload_mid=payload_mids.get(kind))
        r_ref.append(recall_at(ids, gt[i], 10))
        r_bat.append(recall_at(fi[i], gt[i], 10))
        if set(ids.tolist()) == set(fi[i][:len(ids)].tolist()):
            exact += 1
    # PQ quantizes distances onto a small lattice, so EXACT filter-dist
    # ties between distinct nodes (identical code rows) are common —
    # the heap oracle breaks them by id, the fixed-shape engine by
    # slot, and per-step traversal amplifies the divergence; the dense
    # filters tie only at float-ulp granularity. The recall band and
    # the bit-equality floor are both wider for pq (and the cascade,
    # which traverses on the same lattice) accordingly.
    tol = 0.04 if kind in ("pq", "cascade") else 0.02
    assert abs(np.mean(r_bat) - np.mean(r_ref)) <= tol, \
        (kind, deferred, np.mean(r_bat), np.mean(r_ref))
    floor = {"pq": 0.8, "cascade": 0.75}.get(kind, 0.9)
    assert exact >= floor * len(q), \
        f"{kind}/deferred={deferred}: only {exact}/{len(q)} bit-equal"


def test_identity_filter_is_hnsw(small_dataset, small_graph, filters,
                                 payloads):
    """The filter bypass runs standard HNSW: the ref oracle routes to
    search_hnsw verbatim, and the batched engine reaches its recall."""
    x, q, gt = small_dataset
    filt = filters["none"]
    ids_f, _ = search_filtered(small_graph, filt, payloads["none"], q[0])
    ids_h, _ = search_hnsw(small_graph, q[0])
    np.testing.assert_array_equal(ids_f, ids_h)
    r_h, _ = run_queries(small_graph, q, gt, algo="hnsw")
    db = build_packed(small_graph, payloads["none"], filt=filt)
    _, fi = search_batched(db, jnp.asarray(q), filt=filt)
    fi = np.asarray(fi)
    r_b = float(np.mean([recall_at(fi[i], gt[i], 10)
                         for i in range(len(q))]))
    assert abs(r_b - r_h) <= 0.02


def test_deferred_rerank_cuts_dist_h(small_dataset, small_graph,
                                     filters, payloads):
    """The acceptance criterion: deferred PCA mode shows measurably
    fewer Dist.H evaluations per query in return_stats telemetry, at
    recall@10 within 0.01 of the per-step baseline."""
    x, q, gt = small_dataset
    filt = filters["pca"]
    db = build_packed(small_graph, payloads["pca"], filt=filt)
    rec, dhe = {}, {}
    for mode, deferred in (("per_step", False), ("deferred", True)):
        _, fi, st = search_batched(db, jnp.asarray(q), filt=filt,
                                   deferred=deferred,
                                   rerank_mult=RERANK_MULT,
                                   return_stats=True)
        fi = np.asarray(fi)
        rec[mode] = float(np.mean([recall_at(fi[i], gt[i], 10)
                                   for i in range(len(q))]))
        dhe[mode] = float(np.asarray(st["dist_h_evals"]).mean())
    assert abs(rec["deferred"] - rec["per_step"]) <= 0.01, rec
    assert dhe["deferred"] < 0.8 * dhe["per_step"], dhe
    # deferred Dist.H ~ rerank_mult * ef0 final candidates, not k/step
    assert dhe["deferred"] <= RERANK_MULT * small_graph.cfg.ef0 + 2


@pytest.mark.parametrize("kind", ["pca", "pq", "cascade"])
def test_tombstones_under_deferred_rerank(small_dataset, small_graph,
                                          filters, payloads,
                                          payload_mids, kind):
    """Tombstoned rows never surface under deferred re-ranking (the
    final high-dim re-rank list is drawn from the live-only F), and the
    host oracle agrees."""
    x, q, gt = small_dataset
    filt, payload = filters[kind], payloads[kind]
    from repro.index import MutableIndex
    idx = MutableIndex.from_graph(small_graph, filt, seed=1)
    dels = np.unique(gt[:, :3].ravel())       # delete many true answers
    idx.delete(dels, auto_compact=False)
    _, fi = idx.search(q, deferred=True, rerank_mult=RERANK_MULT)
    fi = np.asarray(fi)
    assert not np.isin(fi, dels).any()
    assert (fi >= 0).all() and (fi < idx.n).all()
    assert not idx.deleted[fi.ravel()].any()
    # live-ground-truth recall holds (deleted nodes still route)
    gt_live = idx.live_ground_truth(q, 10)
    rec = float(np.mean([recall_at(fi[i], gt_live[i], 10)
                         for i in range(len(q))]))
    assert rec > 0.8
    # ref oracle: same semantics
    deleted = np.zeros(len(x), bool)
    deleted[dels] = True
    ids, _ = search_filtered(small_graph, filt, payload, q[0],
                             deleted=deleted, deferred=True,
                             rerank_mult=RERANK_MULT,
                             payload_mid=payload_mids.get(kind))
    assert not np.isin(ids, dels).any()


def test_payload_bytes_accounting(small_graph, filters, payloads,
                                  small_dataset):
    """Layout-(3) byte accounting follows the filter payload: PQ codes
    (n_sub B/vec) shrink the store vs PCA f32 rows; the identity bypass
    pays only the index lists."""
    x, _, _ = small_dataset
    dbs = {k: build_packed(small_graph, payloads[k], filt=filters[k])
           for k in filters}
    assert dbs["pq"].bytes_layout3 < dbs["pca"].bytes_layout3
    assert dbs["none"].bytes_layout3 < dbs["pq"].bytes_layout3
    # identity: index bytes + the high table, nothing else
    nnz = sum(int((l.adj >= 0).sum()) for l in dbs["none"].layers)
    assert dbs["none"].bytes_layout3 == nnz * 4 + x.size * 4
    assert dbs["pq"].low.dtype == jnp.uint8
    assert dbs["none"].low.shape[1] == 0
    # per-vector pricing surfaces through the FilterSpec contract
    assert filters["pq"].bytes_per_vec == filters["pq"].cb.n_sub
    assert filters["pca"].bytes_per_vec == 15 * 4
    assert filters["none"].bytes_per_vec == 0
    # cascade: PQ-class INLINE bytes (same hot-stream burst as pq),
    # with the PCA rows off-stream in the low2 side-car
    assert filters["cascade"].bytes_per_vec == \
        filters["pq"].bytes_per_vec
    assert filters["cascade"].mid_bytes_per_vec == 15 * 4
    assert dbs["cascade"].bytes_layout3 == dbs["pq"].bytes_layout3
    assert dbs["cascade"].low.dtype == jnp.uint8
    assert dbs["cascade"].low2 is not None
    assert dbs["cascade"].low2.shape == (len(x), 15)
    assert dbs["cascade"].bytes_sidecar == len(x) * 15 * 4
    for k in ("pca", "pq", "none"):
        assert dbs[k].low2 is None and dbs[k].bytes_sidecar == 0


def test_cost_model_prices_filter_generically(small_dataset, small_graph,
                                              filters, payloads):
    """query_cost accepts the active FilterSpec and prices the filter
    compute by its cost_dims: at identical traversal stats, the PQ
    filter (n_sub lookups) models cheaper Dist.L time than PCA (d_low
    dims) iff n_sub < d_low scaling says so; DRAM bytes always follow
    the instrumented stats."""
    from repro.core.cost_model import DDR4, query_cost
    x, q, _ = small_dataset
    st = {}
    for kind in ("pca", "pq"):
        _, st[kind] = search_filtered(small_graph, filters[kind],
                                      payloads[kind], q[0])
    c_pca = query_cost(st["pca"], n_queries=1, dim=x.shape[1],
                       filt=filters["pca"], dram=DDR4)
    c_pq = query_cost(st["pq"], n_queries=1, dim=x.shape[1],
                      filt=filters["pq"], dram=DDR4)
    # PQ stats priced with PCA depth must differ from PQ depth pricing
    c_pq_mispriced = query_cost(st["pq"], n_queries=1, dim=x.shape[1],
                                d_low=filters["pca"].cost_dims, dram=DDR4)
    assert c_pq.breakdown["dist_l"] != c_pq_mispriced.breakdown["dist_l"]
    # the PQ trace moved fewer payload bytes (16 vs 60 B/vec inline)
    assert st["pq"].seq_bytes < st["pca"].seq_bytes
    assert c_pq.total_ns > 0 and c_pca.total_ns > 0


def test_cost_model_prices_cascade_two_stage(small_dataset, small_graph,
                                             filters, payloads,
                                             payload_mids):
    """The cascade trace carries a third distance class — the promote
    stage's PCA scores — and the cost model prices it as its own
    breakdown entry at mid_cost_dims depth, separate from the in-loop
    ADC (cost_dims) and the deferred Dist.H pass."""
    from repro.core.cost_model import DDR4, query_cost
    x, q, _ = small_dataset
    filt = filters["cascade"]
    _, st = search_filtered(small_graph, filt, payloads["cascade"],
                            q[0], deferred=True, rerank_mult=2,
                            promote_mult=4,
                            payload_mid=payload_mids["cascade"])
    assert st.dist_mid > 0                 # promote stage ran
    c = query_cost(st, n_queries=1, dim=x.shape[1], filt=filt,
                   dram=DDR4)
    assert c.breakdown.get("dist_m", 0) > 0
    # in-loop stage priced at ADC depth, promote stage at d_low depth
    import math
    from repro.core.cost_model import PROCESSOR
    cycles = c.breakdown["dist_m"] * PROCESSOR.freq_ghz
    assert cycles == math.ceil(st.dist_mid / PROCESSOR.dist_lanes) \
        * filt.mid_cost_dims
    # single-stage traces never grow a dist_m entry
    _, st_p = search_filtered(small_graph, filters["pca"],
                              payloads["pca"], q[0])
    assert st_p.dist_mid == 0
    c_p = query_cost(st_p, n_queries=1, dim=x.shape[1],
                     filt=filters["pca"], dram=DDR4)
    assert "dist_m" not in c_p.breakdown


@pytest.mark.parametrize("kind", ["pca", "pq", "cascade"])
def test_prepare_jnp_matches_host(small_dataset, filters, kind):
    """prepare_jnp (device path, shared ADC-table helper) reproduces
    the host prepare() tables/projections to float tolerance — the two
    engines must score candidates off the same per-query prep."""
    _, q, _ = small_dataset
    filt = filters[kind]
    host = filt.prepare(q[:8].astype(np.float32))
    dev = np.asarray(filt.prepare_jnp(jnp.asarray(q[:8])))
    assert host.shape == dev.shape
    np.testing.assert_allclose(host, dev, atol=2e-3, rtol=1e-4)


@pytest.mark.parametrize("kind", ["pca", "pq", "cascade"])
def test_device_prep_rows_bit_equal_across_widths(small_dataset, filters,
                                                  kind):
    """The device prep gives each query the same row at every batch
    width (the scheduler preps at its ladder widths, the synchronous
    path at the service batch), and its PCA projection is f32: within
    1e-5 of a float64 projection, relative to the largest coordinate."""
    _, q, _ = small_dataset
    filt = filters[kind]
    q64 = np.asarray(q[:64], np.float32)
    wide = np.asarray(filt.prepare_jnp(jnp.asarray(q64)))
    narrow = np.asarray(filt.prepare_jnp(jnp.asarray(q64[:16])))
    assert narrow.dtype == np.float32
    assert np.array_equal(narrow, wide[:16])
    if kind == "pq":
        return
    pca = filt.pca
    ref = (q64.astype(np.float64) - pca.mean.astype(np.float64)) \
        @ pca.components.astype(np.float64)
    got = wide[:, wide.shape[1] - pca.d_low:]
    assert np.abs(got - ref).max() <= 1e-5 * np.abs(ref).max()


@pytest.mark.parametrize("kind", ["pca", "cascade"])
def test_device_prep_projects_at_highest_precision(filters, kind):
    """Every matmul of the device prep asks for HIGHEST precision: a
    TPU's default is one bf16 pass, which the CPU cannot show by
    value."""
    from repro.core.filters import _prepare_jit
    filt = filters[kind]
    text = _prepare_jit.lower(type(filt)._prep, filt.device_consts(),
                              jnp.zeros((16, 128), jnp.float32)).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert dots and all("precision = [HIGHEST, HIGHEST]" in ln
                        for ln in dots)


def test_train_pq_small_n_and_reseed():
    """Regression: train_pq on fewer than 256 points must not crash
    (the sharded build path hits this), and empty clusters get reseeded
    — every centroid finite, codes stay decodable."""
    from repro.core.pq import adc_table, encode_pq, train_pq
    rng = np.random.default_rng(5)
    x = rng.normal(size=(60, 32)).astype(np.float32)
    cb = train_pq(x, 4, iters=3, seed=0)          # n=60 < 256 codes
    assert cb.centroids.shape == (4, 256, 8)
    assert np.isfinite(cb.centroids).all()
    codes = encode_pq(cb, x)
    assert codes.shape == (60, 4) and codes.dtype == np.uint8
    # ADC self-distance via own code is near-zero for tiny n (every
    # point effectively owns a centroid after reseed + jitter)
    tab = adc_table(cb, x[0])
    d0 = tab[np.arange(4), codes[0]].sum()
    assert d0 < 1e-2
    # weighted training: zero-weight support below 256 also survives
    w = np.zeros(60)
    w[:40] = 1.0
    cbw = train_pq(x, 4, iters=2, seed=1, weights=w)
    assert np.isfinite(cbw.centroids).all()
    # empty-cluster reseed: two tight blobs empty most of the 256
    # initial clusters every iteration — a stale centroid would
    # survive as a DUPLICATE dead code; after reseeding to the
    # farthest-assigned points every centroid row stays distinct
    blobs = np.concatenate([
        rng.normal(0.0, 0.05, (150, 16)),
        rng.normal(4.0, 0.05, (150, 16))]).astype(np.float32)
    cb2 = train_pq(blobs, 2, iters=4, seed=2)
    for m in range(2):
        assert len(np.unique(cb2.centroids[m], axis=0)) == 256


def test_mutable_index_with_pq_filter(small_dataset, small_graph,
                                      filters):
    """The mutable index refreshes whichever payload the filter owns:
    PQ-coded dirty rows re-gather uint8 codes, upserts encode through
    the filter, and search stays live."""
    from repro.data.vectors import make_sift_like
    from repro.index import MutableIndex
    x, q, _ = small_dataset
    idx = MutableIndex.from_graph(small_graph, filters["pq"], seed=1)
    assert idx.x_low.dtype == np.uint8
    assert idx.db.filter_kind == "pq"
    x_new = make_sift_like(80, seed=33)
    ids = idx.upsert(x_new)
    _, fi = idx.search(x_new[:16])
    hits = (np.asarray(fi)[:, 0] == ids[:16])
    assert hits.mean() > 0.8          # PQ filter is lossy but close
    # drift check degrades gracefully for non-PCA filters
    rep = idx.pca_drift()
    assert not rep["refit_recommended"]


def test_vector_service_identity_filter(small_dataset, small_graph,
                                        filters, payloads):
    """A frozen identity-filter PackedDB serves without any PCA."""
    from repro.serve.vector_service import VectorSearchService
    x, q, gt = small_dataset
    db = build_packed(small_graph, payloads["none"],
                      filt=filters["none"])
    svc = VectorSearchService(db, batch_size=16)
    idx_out, stats = svc.run_stream(q)
    r = float(np.mean([recall_at(idx_out[i], gt[i], 10)
                       for i in range(len(q))]))
    assert r > 0.75
    assert stats["p50_ms"] > 0
