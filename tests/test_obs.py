"""Unified observability plane (ISSUE-7): log-bucketed histograms with
exact-to-bucket percentiles and lossless merge, thread-safe counters,
per-request trace spans threaded through the serving path (query ->
per-shard probe -> merge -> epoch swap; failover / snapshot shipping),
Prometheus + JSON exporters — and the zero-cost-when-disabled contract
(no span objects allocated on the untraced hot path; warmup batches
never pollute the histograms)."""
import json
import threading

import numpy as np
import pytest

from repro.obs import (NULL_SPAN, NULL_TRACER, Registry, Span, Tracer,
                       parse_prometheus, prometheus_families,
                       snapshot_json, to_prometheus)
from repro.obs.metrics import DEFAULT, Histogram


# --------------------------------------------------------------------------
# metrics core
# --------------------------------------------------------------------------

def test_histogram_percentiles_within_one_bucket_of_numpy():
    """Bucket quantiles track np.percentile within one log-bucket
    relative width (growth - 1), with EXACT extremes (min/max ride
    along), on a heavy-tailed latency-like distribution."""
    rng = np.random.default_rng(0)
    samples = np.exp(rng.normal(1.0, 1.2, 20_000))  # lognormal, ~ms
    h = Histogram()
    h.observe_many(samples)
    assert h.count == len(samples)
    assert h.percentile(0) == samples.min()
    assert h.percentile(100) == samples.max()
    for p in (1, 10, 25, 50, 75, 90, 99, 99.9):
        exact = float(np.percentile(samples, p))
        est = h.percentile(p)
        assert abs(est - exact) / exact <= h.growth - 1, (p, est, exact)
    assert h.mean == pytest.approx(float(samples.mean()))


def test_histogram_observe_many_matches_loop_and_merge_is_lossless():
    rng = np.random.default_rng(1)
    a, b = rng.exponential(5.0, 3_000), rng.exponential(0.5, 2_000)
    h_loop, h_vec, h_a, h_b = (Histogram() for _ in range(4))
    for v in a:
        h_loop.observe(v)
    h_vec.observe_many(a)
    np.testing.assert_array_equal(h_loop.counts, h_vec.counts)
    assert h_loop.count == h_vec.count
    h_a.observe_many(a)
    h_b.observe_many(b)
    h_a.merge(h_b)
    h_all = Histogram()
    h_all.observe_many(np.concatenate([a, b]))
    np.testing.assert_array_equal(h_a.counts, h_all.counts)
    assert h_a.min == h_all.min and h_a.max == h_all.max
    with pytest.raises(ValueError, match="bucket configs differ"):
        h_a.merge(Histogram(lo=1.0))


def test_histogram_out_of_range_and_empty():
    h = Histogram(lo=1.0, hi=100.0, growth=2.0)
    assert h.percentile(50) == 0.0                  # empty
    h.observe(0.001)                                # underflow -> bucket 0
    h.observe(1e9)                                  # overflow -> last
    assert h.counts[0] == 1 and h.counts[-1] == 1
    assert h.percentile(0) == 0.001                 # exact extremes kept
    assert h.percentile(100) == 1e9


def test_counter_gauge_histogram_thread_safety():
    reg = Registry()
    c = reg.counter("c_total")
    g = reg.gauge("g")
    h = reg.histogram("h")
    n_threads, per = 8, 5_000

    def work(k):
        for i in range(per):
            c.inc()
            g.inc()
            h.observe(float(i % 100 + 1))

    ts = [threading.Thread(target=work, args=(k,))
          for k in range(n_threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == n_threads * per               # no lost updates
    assert g.value == n_threads * per
    assert h.count == n_threads * per
    assert int(h.counts.sum()) == h.count
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)


def test_family_labels_and_redeclare_conflict():
    reg = Registry()
    fam = reg.counter("reqs_total", "by status", labels=("status",))
    fam.labels(status="ok").inc(3)
    fam.labels(status="err").inc()
    assert fam.labels(status="ok").value == 3
    assert reg.counter("reqs_total", labels=("status",)) is fam
    with pytest.raises(ValueError, match="re-declared"):
        reg.gauge("reqs_total")
    with pytest.raises(ValueError, match="labels"):
        fam.labels(shard=1)
    unl = reg.counter("plain_total")
    unl.inc(2)
    assert unl.value == 2                           # proxy to solo child
    with pytest.raises(AttributeError):
        unl.no_such_attr


def test_registry_reset_keeps_references_valid():
    reg = Registry()
    h = reg.histogram("lat")
    c = reg.counter("n_total")
    h.observe(5.0)
    c.inc()
    reg.emit("x", source="t")
    reg.reset()
    assert h.count == 0 and c.value == 0 and not reg.events
    h.observe(1.0)                                  # same objects still live
    assert reg.histogram("lat").count == 1


# --------------------------------------------------------------------------
# trace spans
# --------------------------------------------------------------------------

def test_span_nesting_and_event_ordering():
    tr = Tracer()
    with tr.span("root", a=1) as root:
        root.event("start")
        with root.child("left") as left:
            left.event("fault", attempt=0)
            left.event("backoff", ms=5)
            left.event("fault", attempt=1)
        with root.child("right") as right:
            right.set(ok=True)
    assert tr.last("root") is root
    assert [s.name for s in root.iter_spans()] == ["root", "left",
                                                   "right"]
    assert root.find("left").event_kinds() == ["fault", "backoff",
                                               "fault"]
    ts = [t for t, _, _ in root.find("left").events]
    assert ts == sorted(ts)                         # monotone offsets
    assert root.children[0] is left and root.children[1] is right
    d = root.to_dict()
    assert d["attrs"] == {"a": 1}
    assert [c["name"] for c in d["children"]] == ["left", "right"]
    json.dumps(d)                                   # JSON-serializable


def test_span_exit_records_error_and_propagates():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom") as s:
            raise RuntimeError("x")
    assert s.attrs["ok"] is False
    assert s.event_kinds() == ["error"]
    assert s.t1 is not None and tr.last("boom") is s


def test_disabled_tracer_allocates_no_spans():
    """THE zero-overhead contract: a disabled tracer returns the
    NULL_SPAN singleton, whose children are itself — a fully
    instrumented code path creates zero Span objects."""
    before = Span.n_created
    sp = NULL_TRACER.span("serve.query", n=64)
    assert sp is NULL_SPAN and not sp.enabled
    with sp.child("shard.probe", shard=0) as ps:
        ps.event("fault", error="nope")
        assert ps is NULL_SPAN
    assert sp.find("shard.probe") is None
    assert Span.n_created == before


def test_spans_on_the_monotonic_clock_and_in_the_profiler(tmp_path):
    """Spans are stamped on ``time.monotonic``; those of an enabled
    tracer (root and children) also land in a ``jax.profiler`` trace
    under their own names, and a span made without a tracer does not."""
    import time
    import jax
    from jax.profiler import ProfileData
    tr = Tracer()
    a = time.monotonic()
    jax.profiler.start_trace(str(tmp_path))
    with tr.span("obs.root") as root:
        with root.child("obs.child") as kid:
            kid.event("mark")
    with Span("obs.loose"):
        pass
    jax.profiler.stop_trace()
    b = time.monotonic()
    assert a <= root.t0 <= kid.t0 <= kid.t1 <= root.t1 <= b
    names = {ev.name for f in tmp_path.rglob("*.xplane.pb")
             for plane in ProfileData.from_file(str(f)).planes
             for line in plane.lines for ev in line.events}
    assert {"obs.root", "obs.child"} <= names
    assert "obs.loose" not in names


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------

def test_prometheus_roundtrip_and_snapshot_stability():
    reg = Registry()
    reg.counter("reqs_total", "requests", labels=("status",)) \
        .labels(status="ok").inc(7)
    reg.gauge("cov").set(0.75)
    h = reg.histogram("lat_ms", "latency")
    h.observe_many([0.5, 2.0, 2.1, 40.0])
    text = to_prometheus(reg)
    assert set(prometheus_families(text)) == {"reqs_total", "cov",
                                              "lat_ms"}
    parsed = parse_prometheus(text)
    assert parsed["reqs_total"] == [({"status": "ok"}, 7.0)]
    assert parsed["cov"] == [({}, 0.75)]
    assert parsed["lat_ms_count"][0][1] == 4.0
    assert parsed["lat_ms_sum"][0][1] == pytest.approx(44.6)
    # cumulative bucket series ends at the total, +Inf included
    buckets = parsed["lat_ms_bucket"]
    assert buckets[-1][0]["le"] == "+Inf" and buckets[-1][1] == 4.0
    cums = [v for _, v in buckets]
    assert cums == sorted(cums)
    with pytest.raises(ValueError):
        parse_prometheus("lat_ms{bad 1.0")
    # snapshot: byte-stable under re-serialization, carries quantiles
    s1, s2 = snapshot_json(reg), snapshot_json(reg)
    assert s1 == s2
    snap = json.loads(s1)
    lat = next(f for f in snap["families"] if f["name"] == "lat_ms")
    assert lat["children"][0]["count"] == 4
    assert lat["children"][0]["p50"] > 0


# --------------------------------------------------------------------------
# unified event stream: train-loop StepMonitor + serving ShardHealth
# --------------------------------------------------------------------------

def test_step_monitor_and_shard_health_share_event_stream():
    from repro.distributed.fault import StepMonitor
    from repro.distributed.faults import FaultPolicy, ShardHealth
    DEFAULT.reset()
    mon = StepMonitor(straggler_factor=2.0, source="train")
    for i in range(8):
        mon.heartbeat(i, 0.10)
    mon.heartbeat(8, 10.0)                          # obvious straggler
    health = ShardHealth(2, FaultPolicy(dead_after_failures=2))
    health.failure(0, RuntimeError("boom"))
    health.failure(0, RuntimeError("boom"))         # -> dead
    health.recover(0)
    kinds = [(e.kind, e.source) for e in DEFAULT.events]
    assert ("straggler", "train") in kinds
    assert ("failure", "serve.shard0") in kinds
    assert ("dead", "serve.shard0") in kinds
    assert ("recovered", "serve.shard0") in kinds
    # one record type, queryable by kind and source prefix
    assert all(type(e).__name__ == "ObsEvent" for e in DEFAULT.events)
    assert len(DEFAULT.events_of(source_prefix="serve.shard")) == 4
    assert DEFAULT.events_of("straggler")[0].target == 8
    assert DEFAULT.counter(
        "phnsw_heartbeats_total",
        labels=("source",)).labels(source="train").value == 9
    # an unnamed monitor stays OFF the obs plane (train loops that
    # predate the obs plane emit nothing)
    DEFAULT.reset()
    StepMonitor().heartbeat(0, 0.1)
    assert not DEFAULT.events


# --------------------------------------------------------------------------
# the serving path, traced end to end
# --------------------------------------------------------------------------

N_OBS, P_OBS, B_OBS = 2000, 4, 16


@pytest.fixture(scope="module")
def traced_svc():
    from repro.configs.base import PHNSWConfig
    from repro.data.vectors import make_queries, make_sift_like
    from repro.index import ShardedMutableIndex
    from repro.serve.vector_service import VectorSearchService
    from repro.distributed.faults import FaultPolicy
    cfg = PHNSWConfig(name="obs2k", n_points=N_OBS, ef_construction=32)
    x = make_sift_like(N_OBS, seed=51)
    q = make_queries(x, B_OBS, seed=52)
    idx = ShardedMutableIndex.build(x, cfg, P_OBS, seed=1)
    tracer = Tracer()
    pol = FaultPolicy(deadline_ms=250.0, max_retries=2, backoff_ms=1.0,
                      dead_after_failures=2)
    svc = VectorSearchService(idx, batch_size=B_OBS, fault_policy=pol,
                              tracer=tracer)
    return svc, idx, q, tracer


def test_warmup_batches_excluded_from_histograms(traced_svc):
    """Regression: the ctor's compile-warming batch must never appear
    in the latency histogram or the query counter (stats are reset IN
    PLACE after warmup, so scraper references stay valid)."""
    svc, _, q, tracer = traced_svc
    hist = svc.stats.latency_ms                     # pre-reset reference
    assert svc.stats.queries == 0
    assert hist.count == 0
    n0 = svc.stats.queries
    svc.query(q)
    assert svc.stats.queries == n0 + len(q)
    assert hist.count == n0 + len(q)                # same object counts


def test_end_to_end_degraded_query_trace(traced_svc):
    """THE acceptance scenario: kill 1 of 4 shards via the fault plan;
    ONE degraded request's span tree must tell the whole story —
    dead-shard probe fault, retry/backoff, dead-mark, and a merge with
    coverage=0.75 and the degraded flag."""
    from repro.distributed import faults
    from repro.distributed.faults import FaultPlan
    svc, _, q, tracer = traced_svc
    tracer.clear()
    with faults.inject(FaultPlan()) as plan:
        plan.add("kill_shard", 1)
        fd, fi, st = svc.query(q, return_stats=True)
    root = tracer.last("serve.query")
    assert root is not None and root.t1 is not None
    assert root.attrs["n"] == len(q)
    assert root.attrs["degraded"] is True
    assert root.attrs["coverage"] == pytest.approx(0.75)
    # all four shards were probed (none pre-marked dead)
    probes = root.find_all("shard.probe")
    assert sorted(p.attrs["shard"] for p in probes) == [0, 1, 2, 3]
    dead_p = next(p for p in probes if p.attrs["shard"] == 1)
    live_p = [p for p in probes if p.attrs["shard"] != 1]
    # the killed shard: fault -> backoff -> fault -> dead_mark, in
    # exactly that order (dead_after_failures=2, so the second fault
    # crosses the threshold and retries stop)
    assert dead_p.event_kinds() == ["fault", "backoff", "fault",
                                    "dead_mark"]
    assert dead_p.attrs["answered"] is False
    ev_fields = [f for _, k, f in dead_p.events if k == "fault"]
    assert all("ShardKilledError" in f["error"] for f in ev_fields)
    # healthy shards answered cleanly with a recorded probe wall
    for p in live_p:
        assert p.attrs["answered"] is True
        assert p.attrs["wall_ms"] > 0
        assert "probe" in p.event_kinds()
    # the merge span carries the request's degraded accounting
    merge = root.find("merge")
    assert merge is not None
    assert merge.attrs["coverage"] == pytest.approx(0.75)
    assert merge.attrs["degraded"] is True
    assert merge.attrs["live_shards"] == 3
    assert st["coverage"] == pytest.approx(0.75) and st["degraded"]
    assert svc.stats.degraded_queries >= 1
    # NEXT request skips the dead-marked shard outright — visible as a
    # root-level event, with only 3 probes
    svc.query(q)
    root2 = tracer.last("serve.query")
    assert "skip_dead_shard" in root2.event_kinds()
    assert sorted(p.attrs["shard"]
                  for p in root2.find_all("shard.probe")) == [0, 2, 3]
    svc.recover_shard(1)                            # leave module clean


def test_mutation_and_swap_trace(traced_svc):
    svc, idx, _, tracer = traced_svc
    rng = np.random.default_rng(7)
    ids = svc.upsert(rng.standard_normal(
        (6, idx.cfg.dim)).astype(np.float32))
    up = tracer.last("serve.upsert")
    assert [s.name for s in up.iter_spans()] == \
        ["serve.upsert", "publish", "epoch.swap"]
    assert up.attrs["n"] == 6
    # round-robin routing visible as events; publish carries the epoch
    assert set(up.event_kinds()) == {"route_upsert"}
    assert sum(f["n"] for _, k, f in up.events) == 6
    sw = up.find("epoch.swap")
    assert sw.attrs["to_epoch"] == sw.attrs["from_epoch"] + 1
    assert sw.attrs["to_epoch"] == svc.epoch
    n = svc.delete(ids[:2])
    assert n == 2
    dl = tracer.last("serve.delete")
    assert dl.find("publish") is not None
    assert dl.attrs["n"] == 2


def test_untraced_service_query_allocates_no_spans(traced_svc):
    """The disabled path through the REAL serving stack: same service,
    tracer swapped for the null one — zero Span objects per request."""
    svc, _, q, tracer = traced_svc
    svc.query(q)                                    # steady state
    svc.tracer = NULL_TRACER
    try:
        before = Span.n_created
        svc.query(q)
        assert Span.n_created == before
    finally:
        svc.tracer = tracer


def test_replica_failover_and_recovery_trace(tmp_path):
    from repro.configs.base import PHNSWConfig
    from repro.core.graph import build_hnsw
    from repro.core.pca import fit_pca
    from repro.data.vectors import make_queries, make_sift_like
    from repro.index import MutableIndex
    from repro.serve.replica import ReplicaSet
    from repro.serve.vector_service import VectorSearchService
    cfg = PHNSWConfig(name="obs-rep", n_points=600, ef_construction=32)
    x = make_sift_like(600, seed=61)
    q = make_queries(x, 8, seed=62)
    pca = fit_pca(x, cfg.d_low)
    idx = MutableIndex.from_graph(build_hnsw(x, cfg, seed=0), pca,
                                  seed=1)
    svc = VectorSearchService(idx, batch_size=8)
    tracer = Tracer()
    rs = ReplicaSet.replicate(svc, 2, snapshot_dir=tmp_path)
    rs.tracer = tracer
    rs.query(q)
    rq = tracer.last("replica.query")
    # the serving replica's request span is PARENTED under the
    # failover loop's span (explicit context passing end to end)
    assert [s.name for s in rq.iter_spans()][:2] == ["replica.query",
                                                     "serve.query"]
    assert rq.attrs["served_by"] == 0
    rs.upsert(make_sift_like(4, seed=63))
    rs._mark_dead(0, "test kill")
    rs.query(q)
    rq2 = tracer.last("replica.query")
    assert rq2.attrs["served_by"] == 1
    rs.recover(0)
    rc = tracer.last("replica.recover")
    names = [s.name for s in rc.iter_spans()]
    assert names == ["replica.recover", "replica.checkpoint",
                     "snapshot.ship", "oplog.replay"]
    assert rc.attrs["replica"] == 0
    assert rc.find("oplog.replay").attrs["n_replayed"] >= 0
    rs.assert_converged()
