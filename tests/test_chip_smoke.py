"""``chip_smoke.py`` driven on the CPU at a tiny size: its refusal to run
without a TPU, the one-chip phases (build through ``MutableIndex.build``,
scheduler-vs-synchronous equality, the cascade arm, the recall report)
and the four-chip phase on four virtual devices. The platform check is
the test's to make: the phases themselves run on whatever JAX finds.
Also the compile-cache placement the smoke and the benchmarks share."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(**extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src"), **extra}
    for var in ("REPRO_FORCE_PALLAS_INTERPRET", "REPRO_KERNEL_IMPL"):
        env.pop(var, None)
    return env


@pytest.mark.parametrize("var", ["", "REPRO_FORCE_PALLAS_INTERPRET",
                                 "REPRO_KERNEL_IMPL"])
def test_platform_check_refuses(smoke, monkeypatch, var):
    """No TPU here: the check exits; a kernel-path override exits even
    before JAX is asked."""
    if var:
        monkeypatch.setenv(var, "1")
    with pytest.raises(SystemExit):
        smoke.check_platform()


@pytest.mark.parametrize("alone", [False, True])
def test_script_fails_without_chip(tmp_path, alone):
    """Run as a script: exit code != 0 and no result line, both from
    the checkout and from a directory holding nothing of the repo but
    the script."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = _cpu_env()
    if alone:
        env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, str(script), "--n", "100"],
                         capture_output=True, text=True, env=env,
                         cwd=script.parent, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_one_chip_phases(smoke):
    """The one-chip path end to end at 1.5k vectors: the scheduler's ids
    equal the synchronous program's, both arms report recall against
    the exact reference, and ``verify`` accepts the run (the kernel
    count is a chip-only check: the CPU traces the jnp oracles)."""
    res = smoke.run_one_chip(1500, 96, 0, batch=16, cascade_batches=2)
    assert res["sched_path"] == "scheduler"
    assert res["sched_equals_sync"]
    assert res["tick_custom_calls"] == 0          # oracles on the CPU
    assert res["build_vps"] > 0 and res["tick_compile_s"] > 0
    for arm in ("pca", "cascade"):
        assert 0.0 <= res[f"recall_{arm}"] <= 1.0
    smoke.verify(res, need_kernels=False)
    with pytest.raises(RuntimeError, match="Pallas"):
        smoke.verify(res)
    with pytest.raises(RuntimeError, match="cascade recall"):
        smoke.verify({**res, "recall_cascade": 0.5}, need_kernels=False)


def test_exact_topk_matches_brute_force(smoke):
    """The smoke's blocked device reference agrees with the host brute
    force across block boundaries (a ragged last block)."""
    import numpy as np
    from repro.data.vectors import (brute_force_topk, make_queries,
                                    make_sift_like)
    x = make_sift_like(3000, seed=5)
    q = make_queries(x, 20, seed=6)
    got = smoke.exact_topk(x, q, 10, block=1024)
    np.testing.assert_array_equal(got, brute_force_topk(x, q, 10))


def test_result_line_format(smoke):
    line = json.loads(smoke.result_line())
    dev = jax.devices()[0]
    assert line == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}


FOUR_CHIPS = textwrap.dedent("""
    import importlib.util, sys
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  sys.argv[1])
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    res = cs.run_four_chips(2000, 0, n_queries=32)
    cs.verify(res)
    assert len({d for _, d in res["placement"]}) == 4, res
    print("FOUR OK", res["mesh_equals_host"])
""")


def test_four_chip_phase_multidevice():
    """Four virtual devices: each shard's arrays sit on their own
    device, and the mesh result is bit-equal to ``shard_search_host``."""
    out = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, cwd=ROOT, timeout=900,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR OK True" in out.stdout


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is where the cache goes and
    nothing is set in code; otherwise it is the fixed, gitignored
    ``.jax_cache/`` at the checkout root."""
    from repro.runtime import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache()
        if env_dir:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
