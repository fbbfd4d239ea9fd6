#!/usr/bin/env python3
"""Smoke test of the served pHNSW path on a TPU, at the paper's own
deployment: a SIFT1M-shaped index (128-d f32 vectors, squared L2, PCA
128 -> 15, six layers, M=16/M0=32, ``configs/sift1m_phnsw.CONFIG``)
built from a seed and served through ``VectorSearchService``.

    python chip_smoke.py                # one chip: build, serve, check
    python chip_smoke.py --four-chips   # only the 4-shard mesh path

One process, no children. It refuses to run (exit code != 0, no result
line) without a TPU, or with REPRO_FORCE_PALLAS_INTERPRET or
REPRO_KERNEL_IMPL set: either would trace something other than the
compiled kernels. Earlier lines report what was built and measured;
the last line is the JSON result
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Compiled programs go to JAX's persistent compilation cache
(``repro.runtime.enable_compile_cache``).

The phases are functions (``run_one_chip``, ``run_four_chips``,
``verify``) so that a CPU test can drive them at a tiny size.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import resource
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_FULL = 1_000_000
# N is cut so that the wave build plus serving stay well inside the
# smoke's 1200 s limit on one v5e (CHANGES.md records the measured
# build rate behind the cut).
N_DEFAULT = 250_000
# The paper's operating point for this configuration: recall@10 = 0.92
# at ef0=10 with the k-schedule (16, 8, 3, 3, 3, 3) (Sections III-B,
# V-A). Both arms must reach it.
RECALL_FLOOR = 0.92
# The deferred cascade's operating point on this data. Its recall falls
# with N at the config defaults (promote_mult 6, ef_upper 1): at 250k,
# 512 queries, recall@10 is 0.825 / 0.917 at promote_mult 6 / 10, and
# ef0 up to 40 or rerank_mult up to 10 does not lift 0.918. The misses
# are whole queries: the greedy descent on PQ distances leaves ~2% of
# them in the wrong cluster (11 of 512), where layer 0 finds nothing
# near. An upper-layer beam of 4 leaves 2 there: 0.940 (XLA:CPU, which
# gave the chip's 0.9166 exactly at ef_upper 1).
CASCADE_PROMOTE_MULT = 10
CASCADE_EF_UPPER = 4
KERNEL_ENV = ("REPRO_FORCE_PALLAS_INTERPRET", "REPRO_KERNEL_IMPL")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check_platform() -> None:
    """Exit unless JAX's first device is a TPU and no environment
    variable steers the kernels away from their compiled path."""
    for var in KERNEL_ENV:
        if os.environ.get(var):
            raise SystemExit(f"chip_smoke: {var} is set; the smoke runs "
                             "only the compiled kernels")
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {platform}")


def exact_topk(x: np.ndarray, q: np.ndarray, k: int,
               block: int = 1 << 16) -> np.ndarray:
    """Exact squared-L2 top-k ids of each query over ``x``: a blocked
    matmul and ``lax.top_k`` on the device, sharing no code with the
    engine — the recall reference. HIGHEST precision keeps the f32
    matmul in f32 on a TPU (its default rounds operands to bf16)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(best_d, best_i, xb, base, n_valid, qd):
        d = jnp.sum(xb * xb, axis=1)[None, :] - 2.0 * jnp.dot(
            qd, xb.T, precision=jax.lax.Precision.HIGHEST)
        col = jnp.arange(xb.shape[0], dtype=jnp.int32)
        d = jnp.where(col[None, :] < n_valid, d, jnp.inf)
        cd = jnp.concatenate([best_d, d], axis=1)
        ci = jnp.concatenate(
            [best_i, jnp.broadcast_to(base + col, d.shape)], axis=1)
        neg, pos = jax.lax.top_k(-cd, k)
        return -neg, jnp.take_along_axis(ci, pos, axis=1)

    block = min(block, len(x))
    qd = jnp.asarray(q, jnp.float32)
    best_d = jnp.full((len(q), k), jnp.inf, jnp.float32)
    best_i = jnp.full((len(q), k), -1, jnp.int32)
    for s in range(0, len(x), block):
        xb = np.zeros((block, x.shape[1]), np.float32)
        xb[:len(x) - s] = x[s:s + block]
        best_d, best_i = step(best_d, best_i, jnp.asarray(xb),
                              jnp.int32(s), jnp.int32(len(x) - s), qd)
    return np.asarray(best_i)


def recall_at_10(ids: np.ndarray, gt: np.ndarray) -> float:
    ids, gt = np.asarray(ids)[:, :10], np.asarray(gt)[:, :10]
    return float((ids[:, :, None] == gt[:, None, :]).any(-1).mean())


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def host_mem() -> str:
    """Host memory now: this process's resident set and its peak, and
    the machine's memory in use (MemTotal - MemAvailable), in GiB."""
    info = {}
    for path in ("/proc/self/status", "/proc/meminfo"):
        with open(path) as f:
            for line in f:
                key, _, val = line.partition(":")
                if val.strip().endswith("kB"):
                    info[key] = int(val.split()[0]) / 2**20
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    return (f"host RSS {info['VmRSS']:.1f} GiB (peak {peak:.1f}), machine "
            f"in use {info['MemTotal'] - info['MemAvailable']:.1f} of "
            f"{info['MemTotal']:.1f} GiB")


def compile_tick(svc, quantum: int = 32):
    """Compile the scheduler's fused admit+step tick program at the full
    slot width, ahead of time and before anything else has compiled it.
    Returns (seconds, number of Pallas kernel calls in the compiled
    program — 0 means an oracle or interpret-mode body was traced)."""
    import jax.numpy as jnp
    from repro.core import search_jax as sj
    S, D = svc.batch, svc._dim
    qp = svc.filt.prepare(np.zeros((S, D), np.float32))
    state = sj.make_slot_state(svc.db, S, qp, ef=svc.ef0)
    t0 = time.perf_counter()
    compiled = sj._slot_admit_step_jit.lower(
        svc.db, state, jnp.zeros((S, D), jnp.float32), jnp.asarray(qp),
        jnp.full((S,), S, jnp.int32), jnp.full((S,), svc.ef0, jnp.int32),
        jnp.zeros((S,), jnp.int32), width=S, quantum=quantum,
        expand_width=svc.db.cfg.expand_width).compile()
    return time.perf_counter() - t0, \
        compiled.as_text().count("tpu_custom_call")


def run_one_chip(n: int, n_queries: int, seed: int, *, batch: int = 64,
                 cascade_batches: int = 8) -> dict:
    """Build the index through ``MutableIndex.build`` (PCA + the wave
    builder), serve ``n_queries`` through the scheduler
    (``run_stream``) and the synchronous path (``query`` batches), then
    repack the same graph with the deferred cascade filter and serve a
    few batches of the same queries through it."""
    import jax
    from repro.configs.sift1m_phnsw import CONFIG
    from repro.core.filters import make_filter
    from repro.core.graph import HNSWGraph
    from repro.core.search_jax import build_packed
    from repro.data.vectors import make_queries, make_sift_like
    from repro.index import MutableIndex
    from repro.kernels import ops
    from repro.serve.vector_service import VectorSearchService

    res = {"n": n, "kernel_path": ops.kernel_path()}
    log(f"kernel path: {res['kernel_path']}")
    cut = "" if n >= N_FULL else f" (cut from SIFT1M's {N_FULL})"
    log(f"N = {n} vectors x {CONFIG.dim} f32{cut}, {n_queries} queries, "
        f"seed {seed}")
    x = make_sift_like(n, CONFIG.dim, seed=seed)
    q = make_queries(x, n_queries, seed=seed + 1)

    t0 = time.perf_counter()
    idx = MutableIndex.build(x, CONFIG, seed=seed)
    jax.block_until_ready(idx.db)
    res["build_s"] = time.perf_counter() - t0
    res["build_vps"] = n / res["build_s"]
    log(f"build: {res['build_s']:.1f} s, {res['build_vps']:.0f} vectors/s "
        f"(PCA fit + wave build + publish), {idx.top + 1} layers")
    log(host_mem())

    t0 = time.perf_counter()
    gt = exact_topk(x, q, 10)
    log(f"exact reference top-10: {time.perf_counter() - t0:.1f} s")

    svc = VectorSearchService(idx, batch_size=batch)
    res["tick_compile_s"], res["tick_custom_calls"] = compile_tick(svc)
    log(f"tick program cold compile: {res['tick_compile_s']:.1f} s, "
        f"{res['tick_custom_calls']} tpu_custom_call")
    t0 = time.perf_counter()
    sched = svc.scheduler()
    log(f"scheduler warm-up ({len(sched.rungs)} widths): "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    ids_sched, st = svc.run_stream(q)
    log(f"run_stream ({st['path']}): {n_queries} queries, "
        f"{time.perf_counter() - t0:.2f} s wall")
    ids_sync, _ = svc.run_stream_sync(q)
    res["sched_path"] = st["path"]
    res["sched_equals_sync"] = bool(np.array_equal(
        ids_sync.astype(np.int64), ids_sched))
    res["recall_pca"] = recall_at_10(ids_sched, gt)
    log(f"scheduler ids == synchronous ids: {res['sched_equals_sync']}")
    log(f"recall@10 pca (scheduler): {res['recall_pca']:.4f}")
    log(host_mem())

    # repack the same graph for the deferred cascade; free the PCA
    # snapshot first (the cascade db is a second full index on device)
    cfg_c = dataclasses.replace(CONFIG, filter_kind="cascade",
                                deferred_rerank=True,
                                promote_mult=CASCADE_PROMOTE_MULT,
                                ef_upper=CASCADE_EF_UPPER)
    g = HNSWGraph(cfg=cfg_c, x=x, levels=idx.levels[:n].copy(),
                  layers=[a[:n].copy() for a in idx.adj], entry=idx.entry)
    pca = idx.pca
    del svc, sched, idx
    gc.collect()
    jax.clear_caches()            # the PCA arm's programs are done
    log(f"PCA index freed: {host_mem()}")
    t0 = time.perf_counter()
    filt = make_filter(cfg_c, x, pca=pca, seed=seed, levels=g.levels)
    db = build_packed(g, filt=filt)
    log(f"cascade repack (PQ S={cfg_c.pq_n_sub} train + encode + "
        f"upload): {time.perf_counter() - t0:.1f} s; {host_mem()}")
    svc = VectorSearchService(db, filt=filt, batch_size=batch)
    nq = min(n_queries, cascade_batches * batch)
    ids = np.concatenate([svc.query(q[i:i + batch])[1]
                          for i in range(0, nq, batch)])
    res["recall_cascade"] = recall_at_10(ids, gt[:nq])
    log(f"recall@10 cascade (deferred, {nq} queries): "
        f"{res['recall_cascade']:.4f}")
    res["peak_bytes"] = peak_bytes()
    log(f"device peak_bytes_in_use: {res['peak_bytes']}")
    log(host_mem())
    return res


def run_four_chips(n: int, seed: int, *, n_queries: int = 64) -> dict:
    """Build the 4-shard index at ``n`` total vectors with each shard's
    arrays on its own device, search it over the mesh
    (``distributed_search``) and with the single-device shard loop
    (``shard_search_host``) on one device, and compare."""
    import jax
    import jax.numpy as jnp
    from repro.configs.sift1m_phnsw import CONFIG
    from repro.core.distributed import (build_sharded, distributed_search,
                                        serving_mesh, shard_search_host)
    from repro.core.filters import make_filter
    from repro.data.vectors import make_queries, make_sift_like

    mesh = serving_mesh(4)
    log(f"four chips: N = {n} vectors over 4 shards, mesh "
        f"{dict(mesh.shape)}")
    x = make_sift_like(n, CONFIG.dim, seed=seed)
    q = make_queries(x, n_queries, seed=seed + 1)
    filt = make_filter(CONFIG, x, seed=seed)
    t0 = time.perf_counter()
    sdb = build_sharded(x, CONFIG, filt, 4, seed=seed, mesh=mesh)
    jax.block_until_ready(sdb)
    res = {"n": n, "build_s": time.perf_counter() - t0}
    log(f"sharded build: {res['build_s']:.1f} s, "
        f"{n / res['build_s']:.0f} vectors/s")
    res["placement"] = sorted((int(s.index[0].start), str(s.device))
                              for s in sdb.high.addressable_shards)
    for shard, dev in res["placement"]:
        log(f"shard {shard} high on {dev}")
    qd = jnp.asarray(q)
    qp = filt.prepare_jnp(qd)
    t0 = time.perf_counter()
    fd_m, fi_m = jax.block_until_ready(distributed_search(mesh, sdb, qd,
                                                          qp))
    log(f"distributed_search (compile + run): "
        f"{time.perf_counter() - t0:.1f} s")
    one = jax.device_put(sdb, jax.devices()[0])
    fd_h, fi_h = shard_search_host(one, qd, qp)
    res["mesh_equals_host"] = bool(
        np.array_equal(np.asarray(fi_m), np.asarray(fi_h))
        and np.array_equal(np.asarray(fd_m), np.asarray(fd_h)))
    res["recall"] = recall_at_10(np.asarray(fi_m), exact_topk(x, q, 10))
    log(f"mesh == host (ids and dists, bit-equal): "
        f"{res['mesh_equals_host']}")
    log(f"recall@10 mesh: {res['recall']:.4f}")
    return res


def verify(res: dict, *, need_kernels: bool = True) -> None:
    """Raise unless every check of the run held."""
    bad = []
    if "placement" in res:
        if len({dev for _, dev in res["placement"]}) != 4:
            bad.append(f"shards share devices: {res['placement']}")
        if not res["mesh_equals_host"]:
            bad.append("mesh result differs from shard_search_host")
        if res["recall"] < RECALL_FLOOR:
            bad.append(f"mesh recall {res['recall']:.4f} < {RECALL_FLOOR}")
    else:
        if res["sched_path"] != "scheduler":
            bad.append(f"run_stream took the {res['sched_path']} path")
        if not res["sched_equals_sync"]:
            bad.append("scheduler ids differ from the synchronous ids")
        for arm in ("pca", "cascade"):
            r = res[f"recall_{arm}"]
            if r < RECALL_FLOOR:
                bad.append(f"{arm} recall@10 {r:.4f} < {RECALL_FLOOR}")
        if need_kernels and res["tick_custom_calls"] <= 0:
            bad.append("no Pallas kernel in the compiled tick program")
    if bad:
        raise RuntimeError("chip_smoke failed: " + "; ".join(bad))


def result_line() -> str:
    """The last stdout line: the device as JAX reports it."""
    import jax
    dev = jax.devices()[0]
    return json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}})


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard mesh phase (4 chips)")
    ap.add_argument("--n", type=int, default=N_DEFAULT,
                    help="index size in vectors")
    ap.add_argument("--queries", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    check_platform()
    t0 = time.perf_counter()
    from repro.runtime import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        res = run_four_chips(args.n, args.seed)
    else:
        res = run_one_chip(args.n, args.queries, args.seed)
    log(f"total: {time.perf_counter() - t0:.1f} s")
    verify(res)
    print(result_line())


if __name__ == "__main__":
    main()
