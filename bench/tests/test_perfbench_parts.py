"""The yardstick's parts on their own: the trace reduction on a
constructed trace and on one recorded here, the work counts against
hand-computed bytes, the peaks table, and the script's refusal to run
without a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from _tiny import ROOT
from bench import peaks, reduce_trace, work


_DIST_H = "%dist_h.4 = f32[64,16]{1,0} custom-call(%p.1, %p.2)"
# an operation that takes the kernel's output is not the kernel
_USES_DIST_H = "%fusion.3 = f32[64]{0} fusion(%dist_h.4), kind=kLoop"


def _trace():
    """One device: ops [0,10) [12,20) [15,18) [30,40) ns (the last the
    Dist.H kernel, named as XLA names a Pallas call after its jitted
    wrapper); host: window [0,50), tick [0,22), idle [22,50)."""
    dev = {"name": np.array(["fusion.1", "fusion.2", _USES_DIST_H,
                             _DIST_H], object),
           "label": np.array(["fusion.1", "fusion.2", _USES_DIST_H,
                              f"{_DIST_H} jit(tick)/while/body/"
                              "jit(dist_h)/pallas_call"], object),
           "start": np.array([0, 12, 15, 30], np.float64),
           "end": np.array([10, 20, 18, 40], np.float64)}
    host = {"name": np.array(["bench.window", "bench.tick",
                              "bench.idle_wait"], object),
            "start": np.array([0, 0, 22], np.float64),
            "end": np.array([50, 22, 50], np.float64)}
    return reduce_trace.Trace(devices=[dev], host=host)


def test_reduce_constructed_trace():
    r = reduce_trace.reduce(_trace())
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(28e-9)       # 10 + 8 + 10
    assert reduce_trace.kernel_seconds(r, "dist_h") == pytest.approx(10e-9)
    assert reduce_trace.kernel_seconds(r, "merge_topk_sorted") == 0
    assert reduce_trace.kernel_seconds(r, "dist") == 0
    assert r["ops"][_DIST_H] == pytest.approx(10e-9)
    assert r["ops"][_USES_DIST_H] == pytest.approx(3e-9)
    gaps = [(lab, round(s * 1e9)) for lab, s in r["gaps"]]
    assert gaps == [("bench.idle_wait", 10), ("bench.idle_wait", 10),
                    ("bench.tick", 2)]
    assert r["idle_by_label"] == {"bench.idle_wait": pytest.approx(20e-9),
                                  "bench.tick": pytest.approx(2e-9)}


def test_reduce_clips_to_window():
    tr = _trace()
    tr.host["start"][0], tr.host["end"][0] = 5, 35
    r = reduce_trace.reduce(tr)
    assert r["window_s"] == pytest.approx(30e-9)
    assert r["busy_s"] == pytest.approx(18e-9)       # 5 + 8 + 5
    assert reduce_trace.kernel_seconds(r, "dist_h") == pytest.approx(5e-9)


def test_reduce_without_device_ops():
    tr = reduce_trace.Trace(devices=[], host=_trace().host)
    r = reduce_trace.reduce(tr)
    assert r["busy_s"] == 0 and r["ops"] == {} and r["gaps"] == []
    assert reduce_trace.kernel_seconds(r, "dist_h") == 0


def test_load_recorded_trace(tmp_path):
    """A trace recorded here holds the benchmark's host annotations (the
    CPU has no device plane, so no device operations)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(reduce_trace.WINDOW):
        with jax.profiler.TraceAnnotation("bench.tick"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = reduce_trace.load(tmp_path)
    assert {"bench.window", "bench.tick"} <= set(tr.host["name"])
    r = reduce_trace.reduce(tr)
    assert r["window_s"] > 0


def test_work_hand_computed():
    pca = {"filter_kind": "pca", "M0": 32, "dim": 128, "d_low": 15,
           "k_schedule": [16, 8, 3], "deferred_rerank": False,
           "metric": "l2"}
    w = work.role_work(pca, [10, 20])                 # 30 steps, 2 queries
    assert w["filter"] == (3 * 32 * 15 * 30, 4 * (32 * 15 + 15) * 30)
    assert w["filter"][1] == 59_400
    assert w["dist_h"] == (3 * 16 * 30 * 128, 4 * 16 * 30 * 128
                           + 4 * 128 * 30)
    assert w["dist_h"][1] == 261_120
    cas = {"filter_kind": "cascade", "M0": 32, "dim": 128,
           "pq_n_sub": 16, "deferred_rerank": True, "rerank_mult": 3,
           "ef0": 10, "k_schedule": [16, 8, 3], "metric": "l2"}
    w = work.role_work(cas, [10, 20])
    assert w["filter"] == (32 * 16 * 30, (32 * 16 + 4 * 32 * 16) * 30)
    assert w["dist_h"] == (3 * 30 * 2 * 128, 4 * 30 * 2 * 128 + 4 * 128 * 2)
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = work.least_seconds(1e6, 819e9, p)
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = work.least_seconds(197e12, 1.0, p)
    assert bound == "operations" and t == pytest.approx(1.0)


@pytest.mark.parametrize("metric", ["cosine", "ip"])
def test_work_dist_h_by_metric(metric):
    """Under cosine and ip a Dist.H row is a dot product: 2 operations
    per element where l2 takes 3; the bytes and the filter's count do
    not change."""
    cfg = {"filter_kind": "pca", "M0": 32, "dim": 100, "d_low": 15,
           "k_schedule": [16, 8, 3], "deferred_rerank": False}
    l2 = work.role_work({**cfg, "metric": "l2"}, [10, 20])
    w = work.role_work({**cfg, "metric": metric}, [10, 20])
    assert w["dist_h"] == (2 * 16 * 30 * 100, l2["dist_h"][1])
    assert l2["dist_h"][0] == 3 * 16 * 30 * 100
    assert w["filter"] == l2["filter"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def _cpu_env(**extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **extra}
    for var in ("REPRO_FORCE_PALLAS_INTERPRET", "REPRO_KERNEL_IMPL",
                "PYTHONPATH"):
        env.pop(var, None)
    env.update(extra)
    return env


@pytest.mark.parametrize("how", ["no_tpu", "kernel_env", "alone"])
def test_script_refuses(tmp_path, how):
    """No TPU here: exit code != 0 and no result line, also with a
    kernel-path override, and from a directory holding nothing but
    ``BENCHMARK.json`` and the benchmark's files."""
    root = ROOT
    if how == "alone":
        root = tmp_path
        shutil.copy(ROOT / "BENCHMARK.json", root)
        shutil.copytree(ROOT / "bench", root / "bench",
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = _cpu_env(**({"REPRO_KERNEL_IMPL": "ref"}
                      if how == "kernel_env" else {}))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "sift250k-pca.open", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=root, timeout=300)
    assert out.returncode != 0
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
