"""``BENCHMARK.json`` against the benchmark's contract and its own
files, and a cell that exists only as new files runs with nothing
else changed."""
from __future__ import annotations

import json
import re
import shutil
import time

import pytest

from _tiny import CFG, ROOT, RUN, SECONDS
from bench import run, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


@pytest.fixture(scope="module")
def man():
    return spec.manifest()


def _one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 \
        and "\n" not in s and "\t" not in s


def test_shape(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(man["paths"]) <= 16
    for p in man["paths"]:
        assert PATH.match(p) and ".." not in p and not p.startswith("/")
        assert (ROOT / p).is_dir()
    cmd = man["command"]
    assert 1 <= len(cmd) <= 32 and all(_one_line(w) for w in cmd)
    for w in cmd[1:]:
        assert not w.startswith("/") and ".." not in w
        if (ROOT / w).exists():
            assert any(w.startswith(p + "/") for p in man["paths"])
    rs = man["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits in 43,200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units(man):
    names = []
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _one_line(c["source"])
        assert _one_line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        names.append(c["name"])
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _one_line(w["why"])
        names.append(w["name"])
    for kind in ("end_to_end", "per_layer"):
        for m in man[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            names.append(m["name"])
    assert len(names) == len(set(names))
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _one_line(m["layer"])
        if "_roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")
            assert m["unit"] == "%"


def test_every_cell_reports_enough(man):
    cells = {w["name"] for w in man["workloads"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in man["end_to_end"] + man["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for c in cells:
        got = [m for m in e2e.values() if spec.applies(m, c)]
        assert len(got) >= 2
        assert any(spec.applies(m, c) for m in man["per_layer"])
    for m in man["per_layer"]:
        # the metric it moves is reported in every cell it is read in
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert spec.applies(moved, c), (m["name"], c)


def test_files_agree(man):
    """Each cell, deployment, mix and reader is a file found by name,
    and the cell files say what ``BENCHMARK.json`` says."""
    by_cfg = {c["name"]: c for c in man["configs"]}
    used = set()
    for w in man["workloads"]:
        cell = spec.cell(w["name"])
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        assert callable(spec.loop(spec.mix(cell)).run)
        used.add(w["config"])
    assert used == set(by_cfg)
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))
    for name, c in by_cfg.items():
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg == spec.config(name) and cfg["name"] == name
        assert set(c["reduced"]) == set(cfg["reduced"])
        for key, cut in cfg["reduced"].items():
            assert cfg[key] == cut["here"] != cut["published"]
    for name in by_cfg:
        assert callable(spec.index(spec.config(name)).service)
    for m in man["per_layer"]:
        assert callable(spec.reader(m["name"]))
    for m in man["end_to_end"]:
        assert callable(spec.reader(m["name"], kind="end_to_end"))


_EVEN_LOOP = """
import numpy as np
from bench import drive


def plan(mix, seconds, seed, pool):
    n = int(mix["rate_qps"] * seconds)
    pool.fill(n)
    return np.arange(n) / mix["rate_qps"]


def run(sched, pool, due, mix, seconds, *, traced=False, hooks=(),
        drain_s=drive.DRAIN_S):
    import time
    ann = drive.annotator(traced)
    t0 = time.monotonic()
    win = drive.Window(t0=t0, t_end=t0 + seconds)
    hooks = drive.Hooks([(t0 + at, fn) for at, fn in hooks])
    for i, at in enumerate(due):
        while time.monotonic() < t0 + at:
            hooks.poll(time.monotonic())
            drive.tick(sched, win, ann)
        sched.submit(pool[i], k=int(mix["k"]), rid=i, t_sched=t0 + at)
    win.submitted = len(due)
    hooks.poll(float("inf"))
    drive.drain(sched, win, ann, drain_s)
    return win
"""

_INDEX = """
from bench.indexes import mutable


def service(cfg, x, seed, pc):
    return mutable.service(cfg, x, seed, pc)
"""


_DATA = """
import numpy as np


def make(cfg, seed):
    # signed Gaussian blobs around a low-rank manifold
    rng = np.random.default_rng([seed, 7])
    n, d = int(cfg["n_points"]), int(cfg["dim"])
    centers = 4.0 * rng.standard_normal((32, d))
    basis = rng.standard_normal((8, d))
    x = centers[rng.integers(0, 32, n)] \\
        + 0.5 * rng.standard_normal((n, 8)) @ basis \\
        + 0.05 * rng.standard_normal((n, d))
    return x.astype(np.float32)


def queries(cfg, x, n, seed_seq):
    rng = np.random.default_rng(seed_seq)
    q = x[rng.integers(0, len(x), n)] \\
        + 0.05 * rng.standard_normal((n, x.shape[1]))
    return q.astype(np.float32)
"""


def _files_only(tmp_path, man, metric):
    """A copy of the benchmark with a new cell on a new deployment (its
    own data generator and index module, stating ``metric``) under a
    new mix (its own loop module), with a new per-layer and a new
    end-to-end metric, added as files and manifest entries alone."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench, ignore=shutil.ignore_patterns(
        "out", "tests", "__pycache__"))
    cfg = json.loads((bench / "configs" / "sift250k-pca.json").read_text())
    cfg.update(name="files-only", index="mutable_again", data="blobs",
               metric=metric)
    (bench / "configs" / "files-only.json").write_text(json.dumps(cfg))
    (bench / "datasets" / "blobs.py").write_text(_DATA)
    (bench / "indexes" / "mutable_again.py").write_text(_INDEX)
    (bench / "loops" / "even.py").write_text(_EVEN_LOOP)
    (bench / "traffic" / "even.json").write_text(json.dumps(
        {"loop": "even", "k": 10, "rate_qps": 40}))
    name = "files-only.even"
    why = "a cell made of files"
    (bench / "cells" / f"{name}.json").write_text(json.dumps({
        "name": name, "config": "files-only", "traffic": "even",
        "chips": 1, "params": {}, "why": why}))
    (bench / "layer_metrics" / "answers_per_tick.py").write_text(
        "def read(run):\n"
        "    return len(run.answered('steps')) / len(run.tick_seconds())\n")
    (bench / "end_to_end" / "answered.py").write_text(
        "def read(run):\n    return len(run.win.answers)\n")
    man = json.loads(json.dumps(man))
    man["configs"].append({"name": "files-only", "source": "a test",
                           "file": "bench/configs/files-only.json",
                           "reduced": ["n_points"], "why": why})
    man["workloads"].append({"name": name, "config": "files-only",
                             "traffic": "even", "chips": 1, "why": why})
    man["end_to_end"].append({
        "name": "answered", "unit": "queries", "better": "higher",
        "bound": 0.01, "source": "host_clock", "workloads": [name]})
    man["per_layer"].append({
        "name": "answers_per_tick.files_only", "unit": "queries",
        "better": "higher", "source": "host_clock", "layer": "serve",
        "moves": "answered", "workloads": [name]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    return bench, name


def test_cell_of_files_only(tmp_path, man):
    """A new cell on a new deployment with a data generator of its own,
    under a new mix, with new metrics, added as files and manifest
    entries alone, runs and is correct."""
    bench, name = _files_only(tmp_path, man, "l2")
    for trace in (False, True):
        res = run.measure(name, 3, SECONDS, trace,
                          t_start=time.perf_counter(), cfg_over=CFG,
                          bench=bench, **RUN)
        assert res["correct"], res["checks"]
        got = res["metrics"]
        if trace:
            assert got["answers_per_tick.files_only"]["value"] > 0
        else:
            assert got["answered"]["value"] == 40
            assert {"setup_s", "recall_at_10"} <= set(got)


def test_cell_of_files_only_is_held_to_its_metric(tmp_path, man):
    """The same files stating ``"metric": "cosine"``, served by the
    program's squared-L2 path: the comparison holds the answers to the
    stated metric, and the returned distances fail ``dist_gap``."""
    bench, name = _files_only(tmp_path, man, "cosine")
    res = run.measure(name, 3, SECONDS, False,
                      t_start=time.perf_counter(), cfg_over=CFG,
                      bench=bench, **RUN)
    c = res["checks"]
    assert not res["correct"], c
    assert c["dist_gap"]["value"] > c["dist_gap"]["limit"]
    assert c["unanswered"]["value"] == 0 and c["bad_rows"]["value"] == 0


def test_configs_state_data_and_metric(man):
    """Every deployment names its data generator, whose module exists
    with ``make`` and ``queries``, and one of the three metrics."""
    for c in man["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["metric"] in spec.METRICS, c["name"]
        assert NAME.match(cfg["data"])
        assert (ROOT / "bench" / "datasets" / f"{cfg['data']}.py").is_file()
        mod = spec.dataset(cfg)
        assert callable(mod.make) and callable(mod.queries)


@pytest.mark.parametrize("change", ["no_data", "no_metric", "bad_metric"])
def test_config_that_does_not_state_data_and_metric_is_refused(
        tmp_path, change):
    (tmp_path / "configs").mkdir()
    cfg = spec.config("sift250k-pca")
    if change == "bad_metric":
        cfg["metric"] = "angular"
    else:
        del cfg[change[len("no_"):]]
    (tmp_path / "configs" / "x.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError):
        spec.config("x", tmp_path)
