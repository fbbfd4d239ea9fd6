"""Open-loop latency-under-load harness (DESIGN.md § Observability).

Closed-loop benchmarks (``bench_table3_qps``) measure *capacity*: the
next request starts when the previous one finishes, so the system is
never behind and latency percentiles say nothing about queueing. A
real service is OPEN-loop: requests arrive on their own clock whether
or not the server is ready, and latency under load — including the
queue wait — is the number an operator actually sees (this is the
classic coordinated-omission trap: closing the loop hides exactly the
slow requests that matter).

Protocol:

1. **Calibrate capacity** with a short closed loop (requests
   back-to-back) — this also A/Bs tracing ON vs OFF interleaved, the
   measured overhead the obs-smoke CI job gates at <= 10%.
2. **Offered-load points**: for each fraction of capacity, draw Poisson
   arrivals (seeded exponential inter-arrival times at the offered
   request rate), serve each request at its scheduled arrival time (or
   as soon as the server frees up, if it fell behind), and record
   ``now - scheduled_arrival`` — queue wait included — into a
   log-bucketed obs histogram labeled by the offered QPS. Both arms
   serve the same fixed compiled width (``svc_batch``, the canonical
   table3 B=64 config): the synchronous arm pads each underfull
   request's dead lanes, the scheduler arm packs queries from
   different requests into the same program — that padded-vs-packed
   A/B is what ``load/speedup_p99`` gates. ``load/sync_tight`` keeps a
   reference arm whose compiled width is tailored to the request size
   (the no-padding lower bound a fixed-shape deployment cannot offer
   under ragged traffic).
3. **Report exact sample percentiles**: p50/p99/p999 come from the raw
   latency samples (``numpy.percentile``), not the histogram buckets —
   the ~1.19x log-bucket width would otherwise quantize the sync/sched
   p99 ratio the serve gate compares. The same samples still land in
   the obs histograms, so the scrape exporter tells the same story at
   bucket resolution.

The canonical 8k run appends the tracked ``load`` section of
``BENCH_table3.json`` (own append-only history, like ``build`` /
``faults``); other sizes are CSV-only so CI gates on a small seeded
run without touching the tracked trajectory. ``prom_path`` dumps the
full Prometheus exposition text for the CI parse gate.
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Optional, Sequence

import numpy as np

from benchmarks.common import emit, load_bench_db


def _closed_loop(svc, batches, reps: int) -> float:
    """Back-to-back serving; returns achieved queries/sec."""
    n_q = 0
    t0 = time.perf_counter()
    for r in range(reps):
        for b in batches:
            svc.query(b)
            n_q += len(b)
    return n_q / (time.perf_counter() - t0)


def _overhead_ab(svc, batches, tracer, reps: int = 4) -> dict:
    """Interleaved traced/untraced closed-loop A/B on the SAME service
    and compiled program (alternating per rep so drift hits both arms
    equally). Returns qps for each arm + the traced/untraced ratio."""
    from repro.obs.trace import NULL_TRACER
    t_on, t_off, q_on, q_off = 0.0, 0.0, 0, 0
    for r in range(2 * reps):
        traced = r % 2 == 0
        svc.tracer = tracer if traced else NULL_TRACER
        t0 = time.perf_counter()
        for b in batches:
            svc.query(b)
        dt = time.perf_counter() - t0
        nq = sum(len(b) for b in batches)
        if traced:
            t_on += dt
            q_on += nq
        else:
            t_off += dt
            q_off += nq
    svc.tracer = NULL_TRACER
    qps_on, qps_off = q_on / t_on, q_off / t_off
    return {"qps_traced": qps_on, "qps_untraced": qps_off,
            "overhead_ratio": qps_on / qps_off}


def _open_loop_point(svc, rng, q, req_size: int, rate_rps: float,
                     n_requests: int, hist) -> dict:
    """One offered-load point: Poisson arrivals at ``rate_rps``
    requests/sec; latency is measured FROM THE SCHEDULED ARRIVAL (queue
    wait included — no coordinated omission). Percentiles are exact
    sample quantiles; the samples also land in ``hist`` for the
    exporter."""
    gaps = rng.exponential(1.0 / rate_rps, n_requests)
    picks = rng.integers(0, len(q) - req_size + 1, n_requests)
    lats: list = []
    t_start = time.perf_counter()
    arrivals = t_start + np.cumsum(gaps)
    for t_a, p in zip(arrivals, picks):
        now = time.perf_counter()
        if t_a > now:
            time.sleep(t_a - now)
        svc.query(q[p:p + req_size])
        ms = (time.perf_counter() - t_a) * 1e3
        lats.append(ms)
        hist.observe(ms)
    span_s = time.perf_counter() - t_start
    return {
        "offered_qps": rate_rps * req_size,
        "achieved_qps": len(lats) * req_size / span_s,
        "n_requests": len(lats),
        "p50_ms": float(np.percentile(lats, 50)),
        "p99_ms": float(np.percentile(lats, 99)),
        "p999_ms": float(np.percentile(lats, 99.9)),
        "mean_ms": float(np.mean(lats)),
    }


def _recall_at(ids_row, gt_row, k: int) -> float:
    m = min(k, len(gt_row))
    return len(set(np.asarray(ids_row[:m]).tolist())
               & set(np.asarray(gt_row[:m]).tolist())) / m


def _open_loop_point_sched(sched, rng, q, req_size: int,
                           rate_rps: float, n_requests: int, hist, *,
                           gt=None, k_mix=None,
                           ragged: bool = False) -> dict:
    """The continuous-batching arm of the open-loop A/B: the same
    Poisson request arrivals as ``_open_loop_point``, but each
    request's queries are SUBMITTED to the scheduler at the scheduled
    arrival and the scheduler ticks while the clock waits — request
    latency is when its LAST query retires, measured from the
    scheduled arrival (no coordinated omission). ``k_mix`` ((ks, p)
    arrays) draws a seeded per-query k mixture and ``ragged`` draws
    per-request sizes in [1, req_size] — the mixed-k ragged traffic
    mode. Returns the same point dict as the synchronous arm (exact
    sample percentiles) plus recall/shed accounting."""
    gaps = rng.exponential(1.0 / rate_rps, n_requests)
    sizes = (rng.integers(1, req_size + 1, n_requests) if ragged
             else np.full(n_requests, req_size))
    picks = rng.integers(0, len(q) - req_size, n_requests)
    n_q_total = int(sizes.sum())
    ks = (rng.choice(k_mix[0], size=n_q_total, p=k_mix[1])
          if k_mix is not None else np.full(n_q_total, 10))
    remaining: dict = {}
    worst_ms: dict = {}
    rid2req: dict = {}
    qmeta: dict = {}
    recalls = []
    lats: list = []

    def absorb(comps):
        for c in comps:
            r = rid2req.pop(c.rid)
            remaining[r] -= 1
            worst_ms[r] = max(worst_ms[r], c.latency_ms)
            if gt is not None:
                row, kq = qmeta.pop(c.rid)
                recalls.append(_recall_at(c.ids, gt[row], kq))
            if remaining[r] == 0:
                lats.append(worst_ms[r])
                hist.observe(worst_ms[r])

    shed0 = sched.svc.stats.registry.get("phnsw_sched_shed_total")
    shed_before = sum(c.value for c in shed0.children()) if shed0 else 0
    t0 = time.monotonic()
    arrivals = t0 + np.cumsum(gaps)
    rid = qi = 0
    for i in range(n_requests):
        t_a = arrivals[i]
        while True:
            now = time.monotonic()
            if now >= t_a:
                break
            if sched.in_flight or sched.queue_depth:
                absorb(sched.tick())
            else:
                time.sleep(min(t_a - now, 5e-4))
        remaining[i] = 0
        worst_ms[i] = 0.0
        for j in range(int(sizes[i])):
            kq = int(ks[qi])
            r = sched.submit(q[picks[i] + j], k=kq, rid=rid,
                             t_sched=t_a)
            if r is not None:
                rid2req[rid] = i
                qmeta[rid] = (picks[i] + j, kq)
                remaining[i] += 1
            rid += 1
            qi += 1
        if remaining[i] == 0:
            del remaining[i], worst_ms[i]
        # when arrivals outrun service, keep serving while admitting
        # (otherwise the queue only drains after the last arrival)
        if sched.queue_depth >= sched.S:
            absorb(sched.tick())
    absorb(sched.drain())
    span_s = time.monotonic() - t0
    shed_after = sum(c.value for c in shed0.children()) if shed0 else 0
    pt = {
        "offered_qps": rate_rps * float(sizes.mean()),
        "achieved_qps": (n_q_total - (shed_after - shed_before))
        / span_s,
        "n_requests": len(lats),
        "p50_ms": float(np.percentile(lats, 50)) if lats else 0.0,
        "p99_ms": float(np.percentile(lats, 99)) if lats else 0.0,
        "p999_ms": float(np.percentile(lats, 99.9)) if lats else 0.0,
        "mean_ms": float(np.mean(lats)) if lats else 0.0,
        "shed": int(shed_after - shed_before),
    }
    if gt is not None:
        pt["recall"] = float(np.mean(recalls)) if recalls else 0.0
    return pt


def main(n_points: int = 8_000, n_queries: int = 64,
         json_path: Optional[str] = None,
         prom_path: Optional[str] = None, seed: int = 0,
         req_size: int = 16, svc_batch: int = 64,
         offered_fracs: Sequence[float] = (0.3, 0.7, 0.8),
         n_requests: int = 120, calib_reps: int = 6,
         sched_slots: int = 64):
    from repro.core.search_jax import build_packed
    from repro.obs import (Registry, Tracer, parse_prometheus,
                           prometheus_families, to_prometheus)
    from repro.serve.vector_service import VectorSearchService

    _, x, g, pca, x_low, q, gt = load_bench_db(n_points, n_queries)
    rng = np.random.default_rng(seed)
    reg = Registry()
    tracer = Tracer()
    db = build_packed(g, x_low)
    # the CANONICAL service: one fixed compiled width (``svc_batch`` =
    # the tracked table3 B=64 config). The synchronous arm serves each
    # arriving request through it, padding dead lanes up to the static
    # batch dim — the scheduler arm packs queries from different
    # requests into the same width instead. That is the A/B the
    # speedup row gates: same traffic, same compiled width, padded vs
    # packed.
    svc = VectorSearchService(db, pca, batch_size=svc_batch,
                              registry=reg)
    rows = []

    # ---- closed-loop capacity + tracing-overhead A/B ----
    batches = [q[i:i + req_size] for i in
               range(0, len(q) - req_size + 1, req_size)]
    _closed_loop(svc, batches, 1)                     # steady-state warm
    cap_qps = _closed_loop(svc, batches, calib_reps)
    rows.append(("load/capacity", 1e6 / cap_qps,
                 f"qps={cap_qps:.0f};req_size={req_size};"
                 f"svc_batch={svc_batch};closed_loop=1"))
    # reference arm: a service whose compiled width is TAILORED to the
    # request size (no padding waste). A fixed-shape deployment cannot
    # actually serve ragged traffic this way without a program per
    # request shape, but the row keeps the comparison transparent:
    # whatever the padded arm loses to dead lanes is visible here.
    svc_tight = VectorSearchService(db, pca, batch_size=req_size,
                                    registry=Registry())
    _closed_loop(svc_tight, batches, 1)
    cap_tight = _closed_loop(svc_tight, batches, calib_reps)
    rows.append(("load/capacity_tight", 1e6 / cap_tight,
                 f"qps={cap_tight:.0f};req_size={req_size};"
                 f"svc_batch={req_size};closed_loop=1"))
    ab = _overhead_ab(svc, batches, tracer)
    rows.append(("obs/overhead", 0.0,
                 f"qps_traced={ab['qps_traced']:.0f};"
                 f"qps_untraced={ab['qps_untraced']:.0f};"
                 f"ratio={ab['overhead_ratio']:.3f}"))

    # ---- open-loop offered-load points ----
    fam = reg.histogram("phnsw_load_latency_ms",
                        "open-loop request latency from scheduled "
                        "arrival (ms), queue wait included",
                        labels=("offered_qps",))
    points = []
    for frac in offered_fracs:
        rate_rps = frac * cap_qps / req_size
        hist = fam.labels(offered_qps=f"{frac * cap_qps:.0f}")
        pt = _open_loop_point(svc, rng, q, req_size, rate_rps,
                              n_requests, hist)
        pt["offered_frac"] = frac
        points.append(pt)
        rows.append((f"load/offered{pt['offered_qps']:.0f}",
                     pt["p50_ms"] * 1e3,
                     f"offered_qps={pt['offered_qps']:.0f};"
                     f"achieved_qps={pt['achieved_qps']:.0f};"
                     f"p50_ms={pt['p50_ms']:.3f};"
                     f"p99_ms={pt['p99_ms']:.3f};"
                     f"p999_ms={pt['p999_ms']:.3f}"))
    # tailored-width reference at 0.8x of ITS OWN capacity
    pt_tight = _open_loop_point(
        svc_tight, rng, q, req_size, 0.8 * cap_tight / req_size,
        n_requests, fam.labels(offered_qps="tight0.8"))
    rows.append(("load/sync_tight", pt_tight["p50_ms"] * 1e3,
                 f"offered_qps={pt_tight['offered_qps']:.0f};"
                 f"achieved_qps={pt_tight['achieved_qps']:.0f};"
                 f"p50_ms={pt_tight['p50_ms']:.3f};"
                 f"p99_ms={pt_tight['p99_ms']:.3f}"))

    # ---- continuous-batching scheduler arm (same arrivals/clock) ----
    from repro.core.search_jax import slot_cache_sizes
    fam_s = reg.histogram("phnsw_sched_load_latency_ms",
                          "open-loop request latency through the "
                          "continuous-batching scheduler (ms), queue "
                          "wait included",
                          labels=("offered_qps",))
    sched = svc.scheduler(n_slots=sched_slots)
    sched_mk = svc.scheduler(ef=100, ef_policy=10,
                             n_slots=sched_slots)
    warm = slot_cache_sizes()
    sched_points = []
    for frac in offered_fracs:
        rate_rps = frac * cap_qps / req_size
        hist = fam_s.labels(offered_qps=f"{frac * cap_qps:.0f}")
        pt = _open_loop_point_sched(sched, rng, q, req_size, rate_rps,
                                    n_requests, hist, gt=gt)
        pt["offered_frac"] = frac
        sched_points.append(pt)
        rows.append((f"load/sched{pt['offered_qps']:.0f}",
                     pt["p50_ms"] * 1e3,
                     f"offered_qps={pt['offered_qps']:.0f};"
                     f"achieved_qps={pt['achieved_qps']:.0f};"
                     f"p50_ms={pt['p50_ms']:.3f};"
                     f"p99_ms={pt['p99_ms']:.3f};"
                     f"shed={pt['shed']};recall={pt['recall']:.4f}"))

    def _pt(pts, frac):
        return next((p for p in pts if p["offered_frac"] == frac), None)

    speedup = None
    s_sync, s_sched = _pt(points, 0.8), _pt(sched_points, 0.8)
    if s_sync and s_sched and s_sched["p99_ms"] > 0:
        speedup = s_sync["p99_ms"] / s_sched["p99_ms"]
        rows.append(("load/speedup_p99", 0.0,
                     f"frac=0.8;sync_p99_ms={s_sync['p99_ms']:.3f};"
                     f"sched_p99_ms={s_sched['p99_ms']:.3f};"
                     f"speedup={speedup:.2f}"))

    # ---- mixed-k ragged-arrival traffic (seeded k in {1,10,100}) ----
    # The synchronous path would have to serve EVERY query at ef>=100;
    # the scheduler compiles one ef=100 program and runs each query at
    # ef_eff = max(k, ef_policy) — the per-slot-k win this mode pins.
    k_mix = (np.array([1, 10, 100]),
             np.array([0.45, 0.45, 0.10]))
    rate_mk = 0.5 * cap_qps / req_size
    pt_mk = _open_loop_point_sched(
        sched_mk, rng, q, req_size, rate_mk, n_requests,
        fam_s.labels(offered_qps="mixed_k"), gt=gt,
        k_mix=k_mix, ragged=True)
    pt_mk["k_mix"] = {"ks": k_mix[0].tolist(),
                      "p": k_mix[1].tolist()}
    rows.append(("load/mixed_k", pt_mk["p50_ms"] * 1e3,
                 f"achieved_qps={pt_mk['achieved_qps']:.0f};"
                 f"p50_ms={pt_mk['p50_ms']:.3f};"
                 f"p99_ms={pt_mk['p99_ms']:.3f};"
                 f"shed={pt_mk['shed']};recall={pt_mk['recall']:.4f}"))
    recompiles = [a - b for a, b in zip(slot_cache_sizes(), warm)]
    rows.append(("load/recompiles", 0.0,
                 f"steady_state={sum(max(r, 0) for r in recompiles)}"))

    # ---- exporter: render, self-check the parse, optionally dump ----
    text = to_prometheus(reg)
    parsed = parse_prometheus(text)
    fams = prometheus_families(text)
    assert "phnsw_load_latency_ms" in fams and \
        "phnsw_sched_load_latency_ms" in fams and \
        "phnsw_request_latency_ms" in fams, fams
    assert "phnsw_load_latency_ms_count" in parsed
    if prom_path:
        Path(prom_path).write_text(text)
        rows.append(("obs/prometheus", 0.0,
                     f"families={len(fams)};path={prom_path}"))

    if json_path:
        entry = {
            "bench": "load",
            "n_points": n_points,
            "req_size": req_size,
            "svc_batch": svc_batch,
            "capacity_qps": cap_qps,
            "capacity_tight_qps": cap_tight,
            "sync_tight_point": pt_tight,
            "points": points,
            "sched_points": sched_points,
            "speedup_p99_at_0.8": speedup,
            "sched_slots": sched_slots,
            "recompiles": recompiles,
            "mixed_k": pt_mk,
            "overhead": ab,
        }
        p = Path(json_path)
        doc = {}
        if p.exists():
            try:
                doc = json.loads(p.read_text())
            except ValueError as e:
                # never silently replace a corrupted tracked trajectory
                raise RuntimeError(
                    f"{p} exists but is not valid JSON; refusing to "
                    f"overwrite the tracked trajectory") from e
        prev = doc.get("load")
        history = []
        if isinstance(prev, dict):
            history = prev.pop("history", [])
            history.append(prev)
        doc["load"] = {**entry, "history": history}
        p.write_text(json.dumps(doc, indent=2) + "\n")
    return emit(rows)


if __name__ == "__main__":
    main()
