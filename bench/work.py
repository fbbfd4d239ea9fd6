"""Least operations and bytes of each kernel role, from the deployment's
shapes and the window's exact per-query step counts
(``Completion.steps``, layer-0 expansion steps).

The count is of the work the algorithm needs, whatever kernel does it:

* ``filter``, per layer-0 step: the M0 neighbour rows of the inline
  payload and the prepared query. PCA: 15 float32 per row and per
  query, 3 operations per element (subtract, multiply, add). PQ codes
  (pq, cascade): S uint8 codes per row and the S table entries they
  select, 4 bytes each, one add per code.
* ``dist_h``, the full-dimension re-rank: D float32 per row re-ranked
  plus the query; 3 operations per element under the ``l2`` metric
  (subtract, multiply, add), 2 under ``cosine`` and ``ip`` (multiply,
  add: rows held unit-norm need only a dot product). Per-step mode
  re-ranks at most ``k_schedule[0]`` survivors per layer-0 step;
  deferred mode re-ranks ``rerank_mult * ef0`` rows once per query. The
  slotted path exposes no exact count of Dist.H rows, so these bounds
  stand in: they count a step's whole survivor list, also where fewer
  rows survive.

Two errors pull a share apart: upper-layer descent work is not counted
while its kernel time is (the share errs low), and the Dist.H row count
is an upper bound (the Dist.H share errs high). Neither is corrected
for; PERF.md says which is larger where a trace shows it.
"""
from __future__ import annotations

import numpy as np

# operations per element of a Dist.H row, by distance metric
DIST_H_OPS = {"l2": 3, "cosine": 2, "ip": 2}


def role_work(cfg: dict, steps) -> dict:
    """{role: (operations, bytes)} for queries with these layer-0 step
    counts under deployment ``cfg``."""
    steps = np.asarray(steps, np.int64)
    n_steps, n_q = int(steps.sum()), len(steps)
    M0, D = int(cfg["M0"]), int(cfg["dim"])
    kind = cfg["filter_kind"]
    out = {}
    if kind == "pca":
        dl = int(cfg["d_low"])
        out["filter"] = (3 * M0 * dl * n_steps,
                         4 * (M0 * dl + dl) * n_steps)
    elif kind in ("pq", "cascade"):
        S = int(cfg["pq_n_sub"])
        out["filter"] = (M0 * S * n_steps, (M0 * S + 4 * M0 * S) * n_steps)
    if cfg.get("deferred_rerank", False) and kind != "none":
        rows = int(cfg["rerank_mult"]) * int(cfg["ef0"]) * n_q
        q_reads = n_q
    else:
        rows = int(cfg["k_schedule"][0]) * n_steps
        q_reads = n_steps
    per_elem = DIST_H_OPS[cfg["metric"]]
    out["dist_h"] = (per_elem * rows * D, 4 * rows * D + 4 * D * q_reads)
    return out


def least_seconds(ops: float, nbytes: float, peaks: dict):
    """(seconds, bound): the least time at the chip's peaks and which of
    the two bounds sets it."""
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_mem, "bytes") if t_mem >= t_ops else (t_ops, "operations")
