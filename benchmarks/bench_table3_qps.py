"""Table III: single-query search throughput (QPS).

Rows:
  HNSW-CPU / pHNSW-CPU     — measured wall time of the host reference
                             implementations (the paper's CPU rows; our
                             CPU differs from their i9, ratios are what
                             transfer).
  HNSW-Std / pHNSW-Sep / pHNSW x {DDR4, HBM}
                           — the processor cost model driven by
                             instrumented traversal traces (paper's
                             synthesized-RTL rows).
  pHNSW-JAX-batched        — measured QPS of the fixed-shape batched
                             search (beyond-paper row: the TPU-native
                             engine, here timed on CPU).

derived column = QPS normalized to HNSW-CPU (paper's normalization), and
for pHNSW rows also the layout-(3) memory blow-up vs the raw dataset.
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Optional

import numpy as np

from benchmarks.common import batched_filter_ab, emit, load_bench_db
from repro.core.cost_model import table3, hw_variant_stats
from repro.core.search_jax import build_packed
from repro.core.search_ref import run_queries


def main(n_points: int = 50_000, n_queries: int = 200,
         json_path: Optional[str] = None, filter_kind: str = "pca",
         deferred: bool = False, rerank_mult: Optional[int] = None,
         n_shards: int = 1):
    """``filter_kind``/``deferred``/``rerank_mult`` select the filter
    stage and re-rank mode of the measured batched row (the CPU
    reference and cost-model rows stay on the paper's PCA
    configuration). The tracked BENCH_table3.json entry is only
    written for the canonical pca/per-step single-shard configuration
    and embeds a pca/pq/none/deferred A/B (``filters``).
    ``n_shards > 1`` adds a measured DISTRIBUTED row (the same filter x
    rerank mode over a P-way sharded build — the mesh collective path
    when the host exposes >= P devices, the bit-equal single-device
    shard loop otherwise)."""
    cfg, x, g, pca, x_low, q, gt = load_bench_db(n_points, n_queries)
    rows = []

    # --- CPU measured (reference implementations) ---
    t0 = time.perf_counter()
    r_cpu_h, st_sw = run_queries(g, q, gt, algo="hnsw")
    t_h = (time.perf_counter() - t0) / len(q)
    t0 = time.perf_counter()
    r_cpu_p, _ = run_queries(g, q, gt, algo="phnsw", x_low=x_low, pca=pca)
    t_p = (time.perf_counter() - t0) / len(q)
    qps_cpu_h = 1.0 / t_h
    rows.append(("table3/HNSW-CPU", t_h * 1e6,
                 f"norm=1.00;recall@10={r_cpu_h:.3f}"))
    rows.append(("table3/pHNSW-CPU", t_p * 1e6,
                 f"norm={(1 / t_p) / qps_cpu_h:.2f};recall@10={r_cpu_p:.3f}"))

    # --- processor cost model (hw_mode traces) ---
    _, st_h = run_queries(g, q, gt, algo="hnsw", hw_mode=True)
    _, st_p = run_queries(g, q, gt, algo="phnsw", x_low=x_low, pca=pca)
    _, st_s = run_queries(g, q, gt, algo="phnsw", x_low=x_low, pca=pca,
                          layout="separate")
    t3 = table3(hw_variant_stats(st_h, st_p, st_s), n_queries=len(q),
                dim=x.shape[1], d_low=x_low.shape[1])
    base = {d: t3["HNSW-Std"][d].qps for d in ("DDR4", "HBM")}
    for variant in ("HNSW-Std", "pHNSW-Sep", "pHNSW"):
        for dram in ("DDR4", "HBM"):
            c = t3[variant][dram]
            rows.append((f"table3/{variant}/{dram}", c.total_ns / 1e3,
                         f"qps={c.qps:.0f};vs_std={c.qps / base[dram]:.2f}x"))

    # --- layout (3) memory cost (Section IV-A claim: ~2.9x) ---
    db = build_packed(g, x_low)
    raw = x.size * 4
    rows.append(("table3/layout3_memory", 0.0,
                 f"bytes={db.bytes_layout3};vs_raw="
                 f"{db.bytes_layout3 / raw:.2f}x"))

    # --- batched JAX engine (beyond paper), measured; the filter stage
    # and rerank mode are pluggable (core/filters.py), and the single
    # measurement protocol lives in common.batched_filter_ab ---
    B = min(64, len(q))
    m = batched_filter_ab(cfg, x, g, pca, q, gt, batch=B, reps=5,
                          rerank_mult=rerank_mult,
                          modes=[(filter_kind, deferred)])[0]
    rows.append((f"table3/pHNSW-JAX-batched/{m['name']}",
                 m["us_per_query"],
                 f"qps={m['qps']:.0f};recall@10={m['recall']:.3f};"
                 f"steps_mean={m['steps_mean']:.1f};"
                 f"steps_p99={m['steps_p99']:.1f};"
                 f"dist_h_mean={m['dist_h_mean']:.1f}"))
    # --- sharded engine row (core/distributed.py), same measurement
    # protocol: the per-shard traversal + cross-shard merge, end to end
    if n_shards > 1:
        import time as _time
        import jax.numpy as jnp
        from benchmarks.common import make_bench_filter
        from repro.core.distributed import (build_sharded,
                                            distributed_search,
                                            serving_mesh)
        from repro.core.search_ref import recall_at
        # one device per shard, or fail: serving_mesh refuses to fall
        # back to the single-device shard loop
        mesh = serving_mesh(n_shards)
        filt = make_bench_filter(filter_kind, cfg, x, pca,
                                 levels=g.levels)
        sdb = build_sharded(x, cfg, filt, n_shards, mesh=mesh)
        qd = jnp.asarray(q[:B])
        qprep = filt.prepare_jnp(qd)
        kw = dict(deferred=deferred,
                  rerank_mult=int(rerank_mult or cfg.rerank_mult))
        run = lambda: distributed_search(mesh, sdb, qd, qprep, **kw)
        run()[1].block_until_ready()                   # compile
        t0 = _time.perf_counter()
        reps = 5
        for _ in range(reps):
            _, fi = run()
        fi.block_until_ready()
        dt = (_time.perf_counter() - t0) / reps
        fi = np.asarray(fi)
        rec = float(np.mean([recall_at(fi[i], gt[i], cfg.recall_at)
                             for i in range(B)]))
        mode = filter_kind + ("-deferred" if deferred else "")
        rows.append((f"table3/pHNSW-JAX-sharded/p{n_shards}-{mode}",
                     dt / B * 1e6,
                     f"qps={B / dt:.0f};recall@10={rec:.3f};"
                     f"path=mesh;platform={mesh.devices.flat[0].platform};"
                     f"vs_1shard={m['qps'] / (B / dt):.2f}x_slowdown"))

    # the tracked perf trajectory pins the canonical single-shard
    # configuration
    if json_path and (filter_kind != "pca" or deferred or n_shards > 1):
        json_path = None
    if json_path:
        # filter-stage A/B on the same graph/queries, embedded in the
        # tracked entry (pca / pq / none / pca-deferred)
        ab = batched_filter_ab(cfg, x, g, pca, q, gt, batch=B)
        rows.extend((f"table3/filter_ab/{a['name']}",
                     a["us_per_query"],
                     f"qps={a['qps']:.0f};recall@10={a['recall']:.3f};"
                     f"dist_h_mean={a['dist_h_mean']:.1f};"
                     f"bytes_per_vec={a['bytes_per_vec']};"
                     f"sidecar_bytes_per_vec="
                     f"{a['sidecar_bytes_per_vec']}")
                    for a in ab)
        entry = {
            "bench": "table3_qps",
            "n_points": n_points,
            "batch": B,
            "qps": m["qps"],
            "us_per_query": m["us_per_query"],
            "recall_at_10": m["recall"],
            "steps_mean": m["steps_mean"],
            "steps_p99": m["steps_p99"],
            "steps_max": m["steps_max"],
            "dist_h_mean": m["dist_h_mean"],
            "filters": {a["name"]: {k: a[k] for k in
                                    ("qps", "recall", "dist_h_mean",
                                     "bytes_per_vec",
                                     "sidecar_bytes_per_vec",
                                     "rerank_mult", "promote_mult")}
                        for a in ab},
        }
        # append-only perf trajectory: latest entry at top level (the
        # tracked number), prior --perf-smoke runs under "history"; the
        # "build" / "faults" / "load" sections (bench_build's /
        # bench_faults' / bench_load's own append-only trajectories)
        # are carried forward untouched, not buried into the QPS
        # history
        p = Path(json_path)
        history, carried = [], {}
        if p.exists():
            try:
                prev = json.loads(p.read_text())
                history = prev.pop("history", [])
                for k in ("build", "faults", "load"):
                    if k in prev:
                        carried[k] = prev.pop(k)
                history.append(prev)
            except (ValueError, KeyError):
                pass
        doc = {**entry, "history": history, **carried}
        p.write_text(json.dumps(doc, indent=2) + "\n")
    return emit(rows)


if __name__ == "__main__":
    main()
