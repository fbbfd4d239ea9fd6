"""Where the benchmark finds things, by the names in ``BENCHMARK.json``
and in the files those name:

* ``cells/<cell>.json``: the cell's configuration, traffic mix, chips,
  why, and the mix's parameters for this cell (``params``);
* ``configs/<config>.json``: one deployment (its source, shapes, index
  setup, cuts, assumed sizes and the guarantees the comparison holds
  it to); its ``index`` names the module that builds it,
  ``indexes/<index>.py``; its ``data`` names the generator of its
  vectors and queries, ``datasets/<data>.py``, with
  ``make(cfg, seed) -> x`` and ``queries(cfg, x, n, seed_seq) -> q``;
  its ``metric`` is the distance the reference holds the answers to,
  one of ``METRICS``. Both keys are required;
* ``traffic/<mix>.json``: one traffic mix's parameters; its ``loop``
  names the generator that drives it, ``loops/<loop>.py``;
* ``end_to_end/<metric>.py`` and ``layer_metrics/<metric>.py``: the
  reader of one metric, ``read(run) -> float | None``; a per-layer
  metric ``<name>.<suffix>`` without a file of its own is read by
  ``<name>.py``.

Adding a cell, a deployment, a mix, a loop, an index kind, a data
generator or a metric adds files and ``BENCHMARK.json`` entries;
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# the distances a deployment may state: squared L2; hnswlib's "cosine"
# space, 1 - q.x / (|q| |x|) (ann-benchmarks' angular distance); and
# hnswlib's "ip" space, 1 - q.x
METRICS = ("l2", "cosine", "ip")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def cell(name: str, bench: Path = BENCH) -> dict:
    c = _json(bench / "cells" / f"{name}.json")
    if c.get("name", name) != name:
        raise ValueError(f"cells/{name}.json names itself {c['name']!r}")
    return {**c, "name": name}


def config(name: str, bench: Path = BENCH) -> dict:
    """The deployment's file; it has to state ``data`` and a ``metric``
    of ``METRICS``."""
    cfg = _json(bench / "configs" / f"{name}.json")
    for key in ("data", "metric"):
        if key not in cfg:
            raise ValueError(f"configs/{name}.json states no {key!r}")
    if cfg["metric"] not in METRICS:
        raise ValueError(f"configs/{name}.json: metric "
                         f"{cfg['metric']!r} is not one of {METRICS}")
    return cfg


def mix(c: dict, bench: Path = BENCH) -> dict:
    """The cell's traffic mix with the cell's parameters over it."""
    return {**_json(bench / "traffic" / f"{c['traffic']}.json"),
            **c.get("params", {})}


def applies(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def module(kind: str, name: str, bench: Path = BENCH):
    """The module ``<bench>/<kind>/<name>.py``; a dotted ``name`` with no
    file of its own falls back to the part before its last dot."""
    d = bench / kind
    path = d / f"{name}.py"
    if not path.exists() and "." in name:
        path = d / f"{name.rsplit('.', 1)[0]}.py"
    if not path.exists():
        raise FileNotFoundError(f"no {kind} module for {name!r} under {d}")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric_name: str, bench: Path = BENCH,
           kind: str = "layer_metrics"):
    """The ``read`` function of a per-layer (or, with ``kind=
    "end_to_end"``, an end-to-end) metric."""
    return module(kind, metric_name, bench).read


def loop(mix: dict, bench: Path = BENCH):
    """The generator module of a traffic mix (``loops/<loop>.py``)."""
    return module("loops", mix["loop"], bench)


def dataset(cfg: dict, bench: Path = BENCH):
    """The generator of a deployment's vectors and queries
    (``datasets/<data>.py``)."""
    return module("datasets", cfg["data"], bench)


def index(cfg: dict, bench: Path = BENCH):
    """The module that builds a deployment's index
    (``indexes/<index>.py``)."""
    return module("indexes", cfg["index"], bench)
