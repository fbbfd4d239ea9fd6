"""Where the benchmark finds things, by the names in ``BENCHMARK.json``
and in the files those name:

* ``cells/<cell>.json``: the cell's configuration, traffic mix, chips,
  why, and the mix's parameters for this cell (``params``);
* ``configs/<config>.json``: one deployment (its source, shapes, index
  setup, cuts, assumed sizes and the guarantees the comparison holds
  it to); its ``index`` names the module that builds it,
  ``indexes/<index>.py``;
* ``traffic/<mix>.json``: one traffic mix's parameters; its ``loop``
  names the generator that drives it, ``loops/<loop>.py``;
* ``end_to_end/<metric>.py`` and ``layer_metrics/<metric>.py``: the
  reader of one metric, ``read(run) -> float | None``; a per-layer
  metric ``<name>.<suffix>`` without a file of its own is read by
  ``<name>.py``.

Adding a cell, a deployment, a mix, a loop, an index kind or a metric
adds files and ``BENCHMARK.json`` entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


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
    return _json(bench / "configs" / f"{name}.json")


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


def index(cfg: dict, bench: Path = BENCH):
    """The module that builds a deployment's index
    (``indexes/<index>.py``)."""
    return module("indexes", cfg["index"], bench)
