#!/usr/bin/env python3
"""Run one benchmark cell once:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``bench/cells/<cell>.json``) names a deployment and a traffic
mix. The run makes the data and queries from ``--seed``, builds the
index through the program's build path, brings up the service and its
scheduler, warms every program the window runs, and then drives the mix
for ``--seconds``. Everything before the window is ``setup_s``. After
the window the service is freed and every answer is compared with the
exact reference (``reference.py``) under the deployment's distance
``metric``; each compared number and its limit
are printed as the last lines of standard error and under ``checks``
in the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and ``checks``.

It refuses to run (exit code != 0, no result line) without a TPU, with
fewer chips than the cell asks for, or with REPRO_FORCE_PALLAS_INTERPRET
or REPRO_KERNEL_IMPL set. JAX's persistent compilation cache is kept
where ``repro.runtime.enable_compile_cache`` puts it (inside the
checkout unless ``JAX_COMPILATION_CACHE_DIR`` is set).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import deploy, drive, reduce_trace, reference, spec  # noqa: E402

KERNEL_ENV = ("REPRO_FORCE_PALLAS_INTERPRET", "REPRO_KERNEL_IMPL")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def check_platform(chips: int) -> None:
    """Exit unless JAX finds at least ``chips`` TPUs and no environment
    variable steers the kernels away from their compiled path."""
    for var in KERNEL_ENV:
        if os.environ.get(var):
            raise SystemExit(f"bench: {var} is set; the benchmark runs "
                             "only the compiled kernels")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found "
                         f"{devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX "
                         f"found {len(devs)}")


# the traced slice: from a quarter of the window, half of it, at most
# two seconds (a device trace of every step of a long window is large)
TRACE_AT, TRACE_SHARE, TRACE_MAX_S = 0.25, 0.5, 2.0
# where the readers of each kind of metric are
READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}
# warm-up queries per scheduler slot: every program the window runs
# runs once, and the step-budget telemetry fills
WARM_PER_SLOT = 32


@dataclass
class Run:
    """What a metric reader sees: the deployment and mix, the window,
    its length, the set-up time, the comparison's readings, the reduced
    trace (None untraced or without a device) and the span of
    ``time.monotonic`` a per-layer reader reads over (the traced slice
    when traced, else the window)."""
    cfg: dict
    mix: dict
    win: drive.Window
    seconds: float
    setup_s: float
    checks: dict
    trace: Optional[dict] = None
    span: tuple = (float("-inf"), float("inf"))
    peaks: Optional[dict] = None

    def answered(self, name: str) -> np.ndarray:
        """Number column ``name`` of the answers retired in the span."""
        a, b = self.span
        t = self.win.col("t_done")
        return self.win.col(name)[(t >= a) & (t <= b)]

    def tick_seconds(self):
        a, b = self.span
        return [dt for t, dt in self.win.ticks if a <= t < b]

    def note(self, msg: str) -> None:
        log(msg)


def _device(jax) -> dict:
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices()) if stats else None}


def measure(cell_name: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, cfg_over: Optional[dict] = None,
            mix_over: Optional[dict] = None,
            bench: Path = spec.BENCH,
            warmup_queries: Optional[int] = None,
            drain_s: float = drive.DRAIN_S) -> dict:
    """One run of a cell, without the platform check. ``cfg_over`` and
    ``mix_over`` replace entries of the deployment and the mix (tests
    run a cell at a tiny size with them); ``bench`` is the directory
    the cell's files are found in, beside its ``BENCHMARK.json``."""
    import jax
    cell = spec.cell(cell_name, bench)
    cfg = {**spec.config(cell["config"], bench), **(cfg_over or {})}
    mix = {**spec.mix(cell, bench), **(mix_over or {})}
    man = spec.manifest(bench.parent)
    loop = spec.loop(mix, bench)
    k = int(mix["k"])
    n_warm = warmup_queries if warmup_queries is not None \
        else WARM_PER_SLOT * int(cfg["n_slots"])

    metric = cfg["metric"]
    x = deploy.make_data(cfg, seed, bench)
    pool = deploy.QueryPool(cfg, x, seed, 1, bench)
    plan = loop.plan(mix, seconds, seed, pool)
    t = time.perf_counter()
    svc = deploy.service(cfg, x, seed, bench)
    log(f"build: {time.perf_counter() - t:.1f} s for {len(x)} vectors")
    t = time.perf_counter()
    sched = deploy.scheduler(svc, cfg)
    warm_missing = drive.warm_up(
        sched, deploy.make_queries(cfg, x, n_warm, seed, 2, bench), k,
        limit_s=drain_s)
    log(f"scheduler and warm-up: {time.perf_counter() - t:.1f} s"
        + (f"; {warm_missing} warm-up queries never came back"
           if warm_missing else ""))

    compiles = []
    counting = [False]
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if counting[0] and name.startswith("/jax/core/compile/") else None)
    marks = {}

    # a directory of this run's own under the cell's ``out/``, so that
    # runs side by side never read or delete each other's trace
    trace_dir = None
    if trace:
        (bench / "out").mkdir(parents=True, exist_ok=True)
        trace_dir = Path(tempfile.mkdtemp(prefix="trace-",
                                          dir=bench / "out"))

    def start_trace():
        jax.profiler.start_trace(str(trace_dir))
        marks["ann"] = jax.profiler.TraceAnnotation(reduce_trace.WINDOW)
        marks["ann"].__enter__()
        marks["a"] = time.monotonic()

    def stop_trace():
        marks["b"] = time.monotonic()
        marks["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()

    trace_at = TRACE_AT * seconds
    hooks = [(trace_at, start_trace),
             (trace_at + min(TRACE_SHARE * seconds, TRACE_MAX_S),
              stop_trace)] if trace else []
    setup_s = time.perf_counter() - t_start
    full_gc = []        # (start, seconds) of each full collection

    def on_gc(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                full_gc.append([time.perf_counter(), 0.0])
            elif full_gc:
                full_gc[-1][1] = time.perf_counter() - full_gc[-1][0]

    counting[0] = True
    gc.callbacks.append(on_gc)
    win = loop.run(sched, pool, plan, mix, seconds, traced=trace,
                   hooks=hooks, drain_s=drain_s)
    gc.callbacks.remove(on_gc)
    counting[0] = False
    win.compiles = len(compiles)
    log(f"full garbage collections in the window and drain: "
        f"{len(full_gc)}, {sum(d for _, d in full_gc) * 1e3:.1f} ms")
    device = _device(jax)
    log(f"window: {win.submitted} submitted, {len(win.answers)} "
        f"answered, {len(win.ticks)} ticks, {win.compiles} compile "
        f"events in the window")
    if win.late_s is not None and len(win.late_s):
        log(f"generator lateness p50 / p99 / max: "
            f"{np.percentile(win.late_s, 50) * 1e3:.3f} / "
            f"{np.percentile(win.late_s, 99) * 1e3:.3f} / "
            f"{win.late_s.max() * 1e3:.3f} ms")

    reduced = None
    if trace:
        t = time.perf_counter()
        try:
            reduced = reduce_trace.reduce(reduce_trace.load(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.1f} s: window "
            f"{reduced['window_s']:.4f} s, busy {reduced['busy_s']:.4f} s")

    # the program's state goes before the reference runs
    del sched, svc
    gc.collect()
    rids = np.array(sorted(win.answers), np.int64)
    ids = np.full((len(rids), k), -1, np.int64)
    dists = np.full((len(rids), k), np.nan, np.float32)
    for row, r in enumerate(rids):
        a_ids = win.rows["ids"][win.answers[r]]
        a_dists = win.rows["dists"][win.answers[r]]
        ids[row, :len(a_ids)] = a_ids[:k]
        dists[row, :len(a_dists)] = a_dists[:k]
    t = time.perf_counter()
    q = pool[rids]
    gt = reference.exact_topk(x, q, 10, metric=metric) if len(rids) \
        else np.zeros((0, 10), np.int64)
    verdict = reference.judge(x, q, ids, dists, gt,
                              unanswered=win.unanswered + warm_missing,
                              limits=cfg, metric=metric, k=k)
    log(f"reference and comparison: {time.perf_counter() - t:.1f} s "
        f"over {len(rids)} answers")

    run = Run(cfg=cfg, mix=mix, win=win, seconds=seconds, setup_s=setup_s,
              checks=verdict["checks"])
    kind = "end_to_end"
    if trace:
        from bench import peaks
        kind = "per_layer"
        run.trace = reduced if reduced["busy_s"] > 0 else None
        run.span = (marks["a"], marks["b"])
        run.peaks = peaks.peaks_for(device["kind"]) \
            if device["platform"] == "tpu" else None
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    metrics = {}
    for m in man[kind]:
        if spec.applies(m, cell_name):
            v = spec.reader(m["name"], bench, READERS[kind])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    # attempted and failed count the warm-up's requests too
    out = {"correct": verdict["correct"],
           "attempted": win.submitted + n_warm,
           "failed": win.unanswered + warm_missing, "metrics": metrics,
           "device": device}
    if trace:
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in
                           list(reduced["ops"].items())[:10]],
            "idle_gaps": [[n, s] for n, s in reduced["gaps"][:10]]}
        log(f"idle by host span: {reduced['idle_by_label']}")
    out["compiles_in_window"] = win.compiles
    out["checks"] = verdict["checks"]
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    check_platform(int(cell["chips"]))
    import jax
    from repro.runtime import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    res = measure(args.workload, args.seed, args.seconds,
                  bool(args.trace), t_start=T_START)
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
