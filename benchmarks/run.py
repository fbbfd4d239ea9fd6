"""Benchmark orchestrator — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus a roofline summary appendix
when dry-run artifacts exist).

  PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller database (8k points) for quick runs")
    ap.add_argument("--n-points", type=int, default=None)
    ap.add_argument("--perf-smoke", action="store_true",
                    help="only the batched-QPS benchmark on a small "
                         "database; writes BENCH_table3.json (QPS, "
                         "recall, mean/p99 steps) for the tracked perf "
                         "trajectory")
    ap.add_argument("--churn", action="store_true",
                    help="only the mutable-index churn benchmark "
                         "(mixed insert/delete/query workload)")
    ap.add_argument("--build", action="store_true",
                    help="only the build benchmark: wave-pipeline vs "
                         "sequential-oracle throughput (vectors/sec) "
                         "and recall-after-build A/B; the canonical "
                         "8k/default-wave run appends the tracked "
                         "'build' section of BENCH_table3.json")
    ap.add_argument("--wave-size", type=int, default=None,
                    help="override cfg.wave_size for --build")
    ap.add_argument("--faults", action="store_true",
                    help="only the fault-tolerance benchmark: "
                         "recall-vs-dead-shards curve (P=4) plus the "
                         "kill/degraded/failover/reseed/recover cycle "
                         "with zero-recompile accounting; the canonical "
                         "8k run appends the tracked 'faults' section "
                         "of BENCH_table3.json")
    ap.add_argument("--load", action="store_true",
                    help="only the open-loop latency-under-load "
                         "harness: Poisson arrivals at fractions of "
                         "the calibrated capacity, p50/p99/p999 from "
                         "the obs histograms, plus the traced-vs-"
                         "untraced overhead A/B; the canonical 8k run "
                         "appends the tracked 'load' section of "
                         "BENCH_table3.json")
    ap.add_argument("--prom-out", type=str, default=None,
                    help="with --load: dump the Prometheus text "
                         "exposition of the run's metrics registry to "
                         "this path (the CI obs-smoke parse gate)")
    ap.add_argument("--filter", choices=("pca", "pq", "cascade", "none"),
                    default="pca", dest="filter_kind",
                    help="filter stage for the measured batched row "
                         "(core/filters.py); the tracked "
                         "BENCH_table3.json entry is only written for "
                         "the canonical pca/per-step configuration")
    ap.add_argument("--deferred", action="store_true",
                    help="deferred re-ranking: traverse on filter "
                         "distances, one batched Dist.H per query")
    ap.add_argument("--rerank-mult", type=int, default=None,
                    help="deferred-rerank candidate multiplier "
                         "(default: cfg.rerank_mult)")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the database P ways and measure the "
                         "distributed path (perf-smoke and churn "
                         "benches) on a mesh of P devices; on the CPU "
                         "(JAX_PLATFORMS=cpu) P host devices are "
                         "simulated. Never touches the tracked "
                         "BENCH_table3.json entry")
    args = ap.parse_args()
    import os
    if args.shards > 1 and os.environ.get("JAX_PLATFORMS") == "cpu":
        # simulated host devices; must precede the first jax use
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.shards}").strip()
    import jax
    from repro.runtime import enable_compile_cache
    dev = jax.devices()[0]
    print(f"# device {dev.platform} {dev.device_kind} x"
          f"{len(jax.devices())}; compile cache {enable_compile_cache()}",
          file=sys.stderr)
    n_points = args.n_points or \
        (8_000 if args.fast or args.perf_smoke else 50_000)
    n_queries = 64 if args.fast or args.perf_smoke else 200
    json_path = str(Path(__file__).resolve().parents[1]
                    / "BENCH_table3.json")

    from benchmarks import (bench_build, bench_churn, bench_faults,
                            bench_fig2_kselect, bench_fig5_energy,
                            bench_kernel_footprint, bench_load,
                            bench_pq_ablation, bench_table3_qps)

    if args.load:
        print("name,us_per_call,derived")
        t0 = time.time()
        n = args.n_points or 8_000
        # the tracked "load" section pins the canonical 8k
        # configuration; other sizes are CSV-only (CI gates on 2k)
        jp = json_path if n == 8_000 else None
        bench_load.main(n_points=n, n_queries=64, json_path=jp,
                        prom_path=args.prom_out)
        if jp:
            print(f"# wrote {jp} (load section)", file=sys.stderr)
        print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
        return

    if args.build:
        print("name,us_per_call,derived")
        t0 = time.time()
        n = args.n_points or 8_000
        # the tracked "build" section pins the canonical 8k /
        # default-wave configuration; other sizes are CSV-only
        jp = json_path if (n == 8_000 and args.wave_size is None) \
            else None
        bench_build.main(n_points=n, n_queries=n_queries,
                         json_path=jp, wave_size=args.wave_size)
        if jp:
            print(f"# wrote {jp} (build section)", file=sys.stderr)
        print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
        return

    if args.faults:
        print("name,us_per_call,derived")
        t0 = time.time()
        n = args.n_points or 8_000
        # the tracked "faults" section pins the canonical 8k/P=4
        # configuration; other sizes are CSV-only (CI gates on 2k)
        jp = json_path if n == 8_000 else None
        bench_faults.main(n_points=n, n_queries=64, n_shards=4,
                          json_path=jp)
        if jp:
            print(f"# wrote {jp} (faults section)", file=sys.stderr)
        print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
        return

    if args.churn:
        print("name,us_per_call,derived")
        t0 = time.time()
        # an explicit --n-points is honored; only the default shrinks
        bench_churn.main(n_points=args.n_points or 8_000,
                         n_queries=n_queries, n_shards=args.shards)
        print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
        return

    if args.perf_smoke:
        print("name,us_per_call,derived")
        t0 = time.time()
        bench_table3_qps.main(n_points=n_points, n_queries=n_queries,
                              json_path=json_path,
                              filter_kind=args.filter_kind,
                              deferred=args.deferred,
                              rerank_mult=args.rerank_mult,
                              n_shards=args.shards)
        if args.filter_kind == "pca" and not args.deferred \
                and args.shards == 1:
            print(f"# wrote {json_path}", file=sys.stderr)
        print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
        return

    print("name,us_per_call,derived")
    t0 = time.time()
    # BENCH_table3.json tracks the fixed --perf-smoke configuration
    # only; full runs at other sizes must not overwrite it
    for mod, kwargs in (
        (bench_table3_qps, dict(n_points=n_points, n_queries=n_queries,
                                filter_kind=args.filter_kind,
                                deferred=args.deferred,
                                rerank_mult=args.rerank_mult,
                                n_shards=args.shards)),
        (bench_fig2_kselect, dict(n_points=n_points,
                                  n_queries=min(n_queries, 100))),
        (bench_fig5_energy, dict(n_points=n_points, n_queries=n_queries)),
        (bench_kernel_footprint, {}),
        (bench_pq_ablation, dict(n_points=n_points,
                                 n_queries=min(n_queries, 64))),
        (bench_churn, dict(n_points=args.n_points or 8_000,
                           n_queries=min(n_queries, 64),
                           n_shards=args.shards)),
    ):
        try:
            mod.main(**kwargs)
        except Exception:
            print(f"# {mod.__name__} FAILED", file=sys.stderr)
            traceback.print_exc()
            raise
    # roofline appendix (rows only if the dry-run has been run)
    from repro.launch.roofline import load_all
    for r in load_all("pod16x16"):
        step_s = max(r["compute_s"], r["memory_s"], r["collective_s"])
        print(f"roofline/{r['arch']}/{r['shape']},"
              f"{step_s * 1e6:.1f},"
              f"bottleneck={r['bottleneck']};"
              f"roofline_frac={r['roofline_fraction']};"
              f"useful_flops={r['useful_flops_ratio']}")
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
