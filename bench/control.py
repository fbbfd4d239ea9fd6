#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the plain
reference computed one precision below what the deployment states
(bfloat16 operands for its float32), put in the program's place and
judged exactly as a run's answers are. It has to come out not correct.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --queries 70000

makes each seed's data and the cell's query pool as a run does, takes
the first ``--queries`` rows of the pool (as many as a run answers), and
prints the compared numbers of the control beside their limits, one
JSON line per seed. The benchmark's own runs never run it; the test
suite calls ``control`` at a tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import deploy, reference, spec  # noqa: E402


def control(cell_name: str, seed: int, n_queries: int, *,
            cfg_over=None) -> dict:
    """The control's verdict on ``n_queries`` of the cell's pool."""
    cell = spec.cell(cell_name)
    cfg = {**spec.config(cell["config"]), **(cfg_over or {})}
    mix = spec.mix(cell)
    k = int(mix["k"])
    x = deploy.make_data(cfg, seed)
    q = deploy.QueryPool(cfg, x, seed, 1)[np.arange(n_queries)]
    gt = reference.exact_topk(x, q, 10)
    ids = reference.exact_topk(x, q, k, operand_dtype="bfloat16")
    dists = reference.bf16_dists(x, q, ids)
    # the control's own distances pick its order, as a program's do
    o = np.argsort(dists, axis=1, kind="stable")
    ids = np.take_along_axis(ids, o, axis=1)
    dists = np.take_along_axis(dists, o, axis=1)
    return reference.judge(x, q, ids, dists, gt, unanswered=0,
                           limits=cfg, k=k)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int, required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("control: needs a TPU")
    for s in args.seeds:
        v = control(args.workload, s, args.queries)
        print(json.dumps({"seed": s, **v}), flush=True)


if __name__ == "__main__":
    main()
