#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, by a sweep on the chip:

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 10 \
        --fractions 0.4 0.6 0.8 0.9 1.0 1.1 1.2

One set-up of the cell's deployment, then a closed loop of
``4 x n_slots`` outstanding queries for ``--seconds`` to measure the
closed-loop rate R, then the cell's open-loop mix at each fraction of R
for ``--seconds``. Each point prints one JSON line: offered and
achieved queries/s, p50/p99 latency from the scheduled arrival, the
backlog (requests submitted but not answered) at the window's close,
and how late the generator ran. The knee is the highest rate with no
growing backlog; the cell's ``rate_qps`` is set below it by hand
(PERF.md records the sweep). Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import deploy, drive, spec  # noqa: E402


def _point(win, seconds: float) -> dict:
    lat = win.col("latency_ms")
    by_end = int(np.count_nonzero(win.col("t_done") <= win.t_end))
    out = {"achieved_qps": by_end / seconds,
           "backlog_at_close": win.submitted - by_end,
           "unanswered": win.unanswered,
           "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
           "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None}
    if win.late_s is not None:
        out["late_p99_ms"] = float(np.percentile(win.late_s, 99) * 1e3)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fractions", type=float, nargs="+",
                    default=[0.4, 0.6, 0.8, 0.9, 1.0, 1.1, 1.2])
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: needs a TPU")
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.mix(cell)
    if mix["loop"] != "open":
        raise SystemExit("sweep: the cell's mix is not an open loop")
    x = deploy.make_data(cfg, args.seed)
    t = time.perf_counter()
    svc = deploy.service(cfg, x, args.seed)
    sched = deploy.scheduler(svc, cfg)
    drive.warm_up(sched, deploy.make_queries(
        cfg, x, 32 * int(cfg["n_slots"]), args.seed, 2), int(mix["k"]),
        limit_s=drive.DRAIN_S)
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    closed = {**mix, "loop": "closed", "outstanding": 4 * cfg["n_slots"]}
    loop = spec.loop(closed)
    pool = deploy.QueryPool(cfg, x, args.seed, 9)
    win = loop.run(sched, pool, loop.plan(closed, args.seconds, args.seed,
                                          pool), closed, args.seconds)
    rate = _point(win, args.seconds)["achieved_qps"]
    print(json.dumps({"closed_loop_qps": rate}), flush=True)
    loop = spec.loop(mix)
    for i, f in enumerate(args.fractions):
        m = {**mix, "rate_qps": round(f * rate)}
        pool = deploy.QueryPool(cfg, x, args.seed, 10 + i)
        plan = loop.plan(m, args.seconds, args.seed, pool)
        win = loop.run(sched, pool, plan, m, args.seconds)
        print(json.dumps({"fraction": f, "offered_qps": len(plan)
                          / args.seconds, **_point(win, args.seconds)}),
              flush=True)

if __name__ == "__main__":
    main()
