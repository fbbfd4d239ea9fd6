"""Continuous-batching scheduler tests (DESIGN.md § Serving front-end):
delivery semantics under out-of-order retirement, SLO admission-control
accounting, adaptive-ef recall parity, and the zero-recompile
churn-under-load regression."""
import numpy as np
import pytest


@pytest.fixture(scope="module")
def stream_setup(small_dataset, small_graph, small_pca):
    from repro.core.filters import PCAFilter
    from repro.core.search_jax import build_packed
    from repro.data.vectors import brute_force_topk, make_queries
    from repro.serve.vector_service import VectorSearchService
    x, _, _ = small_dataset
    cfg = small_graph.cfg
    filt = PCAFilter(small_pca, low_dtype=cfg.low_dtype)
    db = build_packed(small_graph, filt.encode(x), filt=filt)
    q = make_queries(x, 200, seed=7)
    gt = brute_force_topk(x, q, 10)
    svc = VectorSearchService(db, small_pca)
    return svc, db, q, gt


def _recall10(idx, gt):
    return np.mean([len(set(idx[i, :10]) & set(gt[i])) / 10
                    for i in range(len(gt))])


def test_run_stream_matches_sync_bitwise(stream_setup):
    """The scheduler path returns the SAME ids as the synchronous
    batch path, in submission order — continuous batching changes
    when a query's work runs, never what it computes."""
    svc, _, q, _ = stream_setup
    idx_sync, st_sync = svc.run_stream_sync(q)
    idx_sched, st_sched = svc.run_stream(q, scheduler=True)
    assert st_sync["path"] == "sync"
    assert st_sched["path"] == "scheduler"
    assert np.array_equal(idx_sync.astype(np.int64), idx_sched)


def test_exactly_once_out_of_order(stream_setup):
    """Mixed-k traffic retires out of submission order (a k=24 query
    runs a deeper beam than a k=4 one); every rid is delivered exactly
    once, and each answer matches the synchronous program run at that
    request's effective ef."""
    from repro.core.search_jax import search_batched
    import jax.numpy as jnp
    svc, db, q, _ = stream_setup
    sched = svc.scheduler(ef=24)
    n = 96
    ks = [4 if i % 2 == 0 else 24 for i in range(n)]
    for i in range(n):
        assert sched.submit(q[i], k=ks[i], rid=i) == i
    got = {}
    order = []
    for c in sched.drain():
        assert c.rid not in got, "duplicate delivery"
        got[c.rid] = c
        order.append(c.rid)
    assert sorted(got) == list(range(n))
    assert order != sorted(order), "expected out-of-order retirement"
    # per-request parity: ef_eff = max(k, ef_policy=10)
    qj = jnp.asarray(q[:n])
    qp = svc.filt.prepare_jnp(qj)
    ref = {}
    for ef_eff in (10, 24):
        _, fi = search_batched(db, qj, qp, ef0=ef_eff)
        ref[ef_eff] = np.asarray(fi)
    for i in range(n):
        ef_eff = max(ks[i], 10)
        assert np.array_equal(got[i].ids, ref[ef_eff][i, :ks[i]])


def test_shed_accounting(stream_setup):
    """Bounded-queue overflow and expired deadlines shed with
    per-reason counters; shed + delivered == submitted."""
    svc, _, q, _ = stream_setup
    sched = svc.scheduler(n_slots=8, max_queue=4)
    fam = svc.stats.registry.get("phnsw_sched_shed_total")
    base_full = fam.labels(reason="queue_full").value
    base_dl = fam.labels(reason="deadline").value
    # overflow: 4 queue places, no ticks -> submissions 5.. shed
    rids = [sched.submit(q[i], k=10) for i in range(6)]
    assert rids[4] is None and rids[5] is None
    assert fam.labels(reason="queue_full").value == base_full + 2
    # expired deadline: scheduled far in the past
    import time
    late = sched.submit(q[6], k=10,
                        t_sched=time.monotonic() - 10.0,
                        deadline_ms=1.0)
    assert late is None
    assert fam.labels(reason="deadline").value == base_dl + 1
    delivered = sched.drain()
    assert len(delivered) == 4
    assert {c.rid for c in delivered} == {r for r in rids
                                          if r is not None}


def test_adaptive_ef_recall_parity(stream_setup):
    """Adaptive step budgets (p50 start + escalation) must not cost
    recall: >= the fixed-budget path's recall - 0.005. (They are in
    fact bit-equal — escalation re-runs the same monotone program.)"""
    svc, _, q, gt = stream_setup
    fixed = svc.scheduler(adaptive_budget=False)
    adaptive = svc.scheduler(adaptive_budget=True)
    esc = svc.stats.registry.get("phnsw_sched_escalations_total")

    def run(s):
        for i in range(len(q)):
            s.submit(q[i], k=10, rid=i)
        out = np.full((len(q), 10), -1, np.int64)
        for c in s.drain():
            out[c.rid] = c.ids
        return out

    idx_fixed = run(fixed)
    # two passes: the first fills the step histogram, the second runs
    # with p50 initial budgets (escalations must fire for deep queries)
    run(adaptive)
    before = esc.value
    idx_adaptive = run(adaptive)
    assert esc.value > before, "p50 budgets should force escalations"
    r_fixed = _recall10(idx_fixed, gt)
    r_adaptive = _recall10(idx_adaptive, gt)
    assert r_adaptive >= r_fixed - 0.005
    assert np.array_equal(idx_fixed, idx_adaptive)


def test_zero_recompile_under_churn(stream_setup):
    """Steady-state serving — admission churn, mixed k, adaptive
    escalation, repeated waves — reuses the warm compiled programs:
    the jit cache counters must not move."""
    from repro.core.search_jax import slot_cache_sizes
    svc, _, q, _ = stream_setup
    sched = svc.scheduler()          # cached default, already warm
    svc.run_stream(q[:64], scheduler=True)
    warm = slot_cache_sizes()
    for wave in range(3):
        for i in range(50):
            sched.submit(q[(wave * 50 + i) % len(q)],
                         k=(i % 10) + 1)
        sched.drain()
    svc.run_stream(q[64:128], scheduler=True)
    assert slot_cache_sizes() == warm


@pytest.mark.parametrize("kind", ["pca", "cascade"])
def test_scheduler_deferred_parity_bitwise(small_dataset, small_graph,
                                           small_pca, kind):
    """The ISSUE-9 acceptance bar: a deferred-rerank service (PCA and
    the cascade — whose retire path additionally runs the promote
    gather off the low2 side-car) serves through the continuous-batching
    scheduler bit-equal to the synchronous batch path, at healthy
    recall."""
    import dataclasses
    from repro.core.filters import PCAFilter, make_filter
    from repro.core.search_jax import build_packed
    from repro.data.vectors import brute_force_topk, make_queries
    from repro.serve.vector_service import VectorSearchService
    x, _, _ = small_dataset
    cfg = dataclasses.replace(small_graph.cfg, deferred_rerank=True,
                              filter_kind=kind, pq_train_iters=8)
    if kind == "pca":
        filt = PCAFilter(small_pca, low_dtype=cfg.low_dtype)
    else:
        filt = make_filter(cfg, x, seed=0, pca=small_pca,
                           levels=small_graph.levels)
    g = dataclasses.replace(small_graph, cfg=cfg)
    db = build_packed(g, filt.encode(x), filt=filt)
    assert db.cfg.deferred_rerank
    svc = VectorSearchService(db, filt=filt)
    assert svc.scheduler_supported
    q = make_queries(x, 120, seed=7)
    gt = brute_force_topk(x, q, 10)
    idx_sync, st_sync = svc.run_stream_sync(q)
    idx_sched, st_sched = svc.run_stream(q, scheduler=True)
    assert st_sched["path"] == "scheduler"
    assert np.array_equal(idx_sync.astype(np.int64), idx_sched)
    assert _recall10(idx_sched, gt) >= 0.9


def test_sharded_degraded_scheduler(small_dataset, small_pca):
    """The sharded slotted path serves GLOBAL ids; with a dead shard
    the done gate and the merge exclude it (answers never contain its
    ids) and completions carry degraded/coverage accounting."""
    from repro.core.distributed import build_sharded
    from repro.core.filters import PCAFilter
    from repro.data.vectors import make_queries
    from repro.serve.vector_service import VectorSearchService
    x, _, _ = small_dataset
    from repro.configs.base import PHNSWConfig
    cfg = PHNSWConfig(name="test4k", n_points=len(x),
                      ef_construction=50)
    filt = PCAFilter(small_pca, low_dtype=cfg.low_dtype)
    sdb = build_sharded(x, cfg, filt, 3, seed=0)
    svc = VectorSearchService(sdb, small_pca)
    q = make_queries(x, 60, seed=11)
    idx_all, _ = svc.run_stream(q, scheduler=True)
    idx_sync, _ = svc.run_stream_sync(q)
    assert np.array_equal(idx_sync.astype(np.int64), idx_all)
    sched = svc.scheduler()
    sched.set_live([True, False, True])
    for i in range(40):
        sched.submit(q[i], k=10, rid=i)
    comps = sched.drain()
    assert sorted(c.rid for c in comps) == list(range(40))
    offs = np.asarray(sdb.offsets)
    cnts = np.asarray(sdb.counts)
    lo, hi = offs[1], offs[1] + cnts[1]
    for c in comps:
        assert c.degraded and c.coverage < 1.0
        assert not ((c.ids >= lo) & (c.ids < hi)).any()


# --------------------------------------------------------------------------
# the tick's span tree and the exact Dist.H count
# --------------------------------------------------------------------------

PHASES = {"sched.admit", "sched.prepare", "sched.launch", "sched.wait",
          "sched.rerank", "sched.complete"}


def _serve_traced(svc, q, n_slots=16):
    """Serve ``q`` as a trickle (a few arrivals per tick) through a
    fresh scheduler with an enabled tracer; returns the scheduler, the
    completions and the finished ``sched.tick`` roots."""
    from repro.obs import Tracer
    from repro.serve.scheduler import StreamScheduler
    sched = StreamScheduler(svc, n_slots=n_slots)
    sched.tracer = Tracer(capacity=None)
    out = []
    for i in range(len(q)):
        sched.submit(q[i], k=10, rid=i)
        if i % 5 == 4:
            out.extend(sched.tick())
    out.extend(sched.drain())
    return sched, out, list(sched.tracer.finished)


def test_tick_span_tree(stream_setup):
    """An enabled tracer sees one ``sched.tick`` root per tick whose
    children are the tick's phases: the admitted and retired counts
    add up to the requests served, and the root's own time is under
    its children's."""
    svc, _, q, _ = stream_setup
    sched, comps, roots = _serve_traced(svc, q[:80])
    assert sorted(c.rid for c in comps) == list(range(80))
    assert roots and all(r.name == "sched.tick" for r in roots)
    for r in roots:
        assert {c.name for c in r.iter_spans()} - {"sched.tick"} <= PHASES
        assert r.children[0].name == "sched.admit"
        assert {"passes"} <= set(r.attrs)
        if r.find("sched.launch"):
            assert {"width", "in_flight"} <= set(r.attrs)
            assert r.find("sched.wait") is not None
        for s in r.iter_spans():
            assert s.t1 is not None and s.t0 <= s.t1
    launches = [s for r in roots for s in r.find_all("sched.launch")]
    assert {s.attrs["program"] for s in launches} <= {
        "admit_step", "step", "step_prefix"}
    assert "admit_step" in {s.attrs["program"] for s in launches}
    assert all(s.attrs["width"] in sched.rungs for s in launches)
    admits = [s for r in roots for s in r.find_all("sched.admit")]
    assert sum(s.attrs["admitted"] for s in admits) == 80
    assert all(s.attrs["queued_ms"] >= 0 and s.attrs["queued_max_ms"] >= 0
               for s in admits)
    done = [s for r in roots for s in r.find_all("sched.complete")]
    assert sum(s.attrs["retired"] for s in done) == 80
    assert sum(s.attrs["dist_h_rows"] for s in done) == \
        sum(c.dist_h for c in comps)
    total = sum(r.duration_ms for r in roots)
    children = sum(c.duration_ms for r in roots for c in r.children)
    assert total - children < children


def _deferred_svc(small_dataset, small_graph, small_pca, kind):
    import dataclasses
    from repro.core.filters import PCAFilter, make_filter
    from repro.core.search_jax import build_packed
    from repro.serve.vector_service import VectorSearchService
    x, _, _ = small_dataset
    cfg = dataclasses.replace(small_graph.cfg, deferred_rerank=True,
                              filter_kind=kind, pq_train_iters=8)
    if kind == "pca":
        filt = PCAFilter(small_pca, low_dtype=cfg.low_dtype)
    else:
        filt = make_filter(cfg, x, seed=0, pca=small_pca,
                           levels=small_graph.levels)
    g = dataclasses.replace(small_graph, cfg=cfg)
    db = build_packed(g, filt.encode(x), filt=filt)
    return VectorSearchService(db, filt=filt), db


@pytest.mark.parametrize("kind", ["pca", "cascade"])
def test_completion_dist_h_matches_sync(stream_setup, small_dataset,
                                        small_graph, small_pca, kind):
    """``Completion.dist_h`` is the synchronous path's exact
    ``dist_h_evals`` for the same query: per-step Dist.H under the PCA
    filter (escalations included), and the one deferred re-rank pass of
    the cascade, whose ``sched.rerank`` span counts its rows."""
    import jax.numpy as jnp
    from repro.core.search_jax import search_batched
    from repro.data.vectors import make_queries
    if kind == "pca":
        svc, db, q, _ = stream_setup
        q = q[:96]
    else:
        svc, db = _deferred_svc(small_dataset, small_graph, small_pca,
                                kind)
        q = make_queries(small_dataset[0], 96, seed=7)
    _, comps, roots = _serve_traced(svc, q)
    qj = jnp.asarray(q)
    _, fi, st = search_batched(db, qj, svc.filt.prepare_jnp(qj),
                               ef0=svc.ef0, return_stats=True)
    want = np.asarray(st["dist_h_evals"])
    got = np.zeros(len(q), np.int64)
    for c in comps:
        got[c.rid] = c.dist_h
        assert np.array_equal(c.ids, np.asarray(fi)[c.rid, :10])
    assert (want > 0).all()
    assert np.array_equal(got, want)
    reranks = [s for r in roots for s in r.find_all("sched.rerank")]
    if kind == "cascade":
        assert reranks and all(s.find("sched.wait") for s in reranks)
        assert sum(s.attrs["rows"] for s in reranks) == want.sum()
    else:
        assert not reranks


@pytest.fixture(scope="module")
def cascade_svc(small_dataset, small_graph, small_pca):
    return _deferred_svc(small_dataset, small_graph, small_pca,
                         "cascade")[0]


def test_admit_launch_uploads_raw_queries_only(cascade_svc, small_dataset):
    """A cascade scheduler uploads the raw queries and its per-row
    registers, never a prepared ``[width, n_sub*256 + d_low]`` table:
    each admitting ``sched.launch`` carries ``bytes`` <= width * (D*4 +
    64) and holds the ``sched.prepare`` of the device prep."""
    from repro.data.vectors import make_queries
    x = small_dataset[0]
    D = x.shape[1]
    q = make_queries(x, 60, seed=5)
    sched, comps, roots = _serve_traced(cascade_svc, q)
    assert sorted(c.rid for c in comps) == list(range(60))
    launches = [s for r in roots for s in r.find_all("sched.launch")]
    assert all(s.attrs["bytes"] >= 0 for s in launches)
    admits = [s for s in launches if s.attrs["program"] == "admit_step"]
    assert admits
    for s in admits:
        w = s.attrs["width"]
        assert D * 4 * w <= s.attrs["bytes"] <= w * (D * 4 + 64)
        prep, = s.find_all("sched.prepare")
        assert prep.attrs["rows"] == w
    assert all(s.find("sched.prepare") is None for s in launches
               if s.attrs["program"] != "admit_step")


def test_scheduler_never_calls_host_prepare(cascade_svc, small_dataset,
                                            monkeypatch):
    """The scheduler, from its construction to a drain, preps queries
    on the device only: the filter's host ``prepare`` is never called."""
    from repro.data.vectors import make_queries
    from repro.serve.scheduler import StreamScheduler

    def host_prepare(q):
        raise AssertionError("the scheduler called the host prepare")

    monkeypatch.setattr(cascade_svc.filt, "prepare", host_prepare)
    sched = StreamScheduler(cascade_svc, n_slots=32)
    q = make_queries(small_dataset[0], 40, seed=9)
    for i in range(len(q)):
        sched.submit(q[i], k=10, rid=i)
    assert sorted(c.rid for c in sched.drain()) == list(range(40))


def test_default_tracer_drain_allocates_no_spans(stream_setup):
    """With the service's default (disabled) tracer a whole drain, tick
    phases and escalations included, creates no ``Span``."""
    from repro.obs import Span
    svc, _, q, _ = stream_setup
    sched = svc.scheduler()
    for i in range(48):
        sched.submit(q[i], k=10, rid=i)
    before = Span.n_created
    assert len(sched.drain()) == 48
    assert Span.n_created == before


def test_tick_spans_in_profiler_trace(stream_setup, tmp_path):
    """Under an enabled tracer a tick's spans are also profiler
    annotations: a ``jax.profiler`` trace of one tick holds
    ``sched.tick`` on the host plane with its phases inside it."""
    import jax
    from jax.profiler import ProfileData
    from repro.obs import Tracer
    from repro.serve.scheduler import StreamScheduler
    svc, _, q, _ = stream_setup
    sched = StreamScheduler(svc, n_slots=16)
    sched.tracer = Tracer()
    for i in range(8):
        sched.submit(q[i], k=10, rid=i)
    jax.profiler.start_trace(str(tmp_path))
    sched.tick()
    jax.profiler.stop_trace()
    ev = {}
    for f in tmp_path.rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(f)).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("sched."):
                            ev.setdefault(e.name, []).append(
                                (e.start_ns, e.start_ns + e.duration_ns))
    assert {"sched.tick", "sched.admit", "sched.prepare",
            "sched.launch", "sched.wait"} <= set(ev)
    (t0, t1), = ev.pop("sched.tick")
    assert all(t0 <= a <= b <= t1 for spans in ev.values()
               for a, b in spans)
    root = sched.tracer.last("sched.tick")
    assert len(root.find_all("sched.wait")) == len(ev["sched.wait"])
