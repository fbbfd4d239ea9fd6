"""Shared tiny sizes for driving the benchmark's cells on the CPU."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

# a cell at a size a CPU test can hold: the deployment's shapes and
# index setup, 2k vectors, 16 slots, a load the CPU keeps up with, a
# short warm-up and a short wait for answers still out
CFG = {"n_points": 2000, "n_slots": 16}
MIX = {"rate_qps": 60, "outstanding": 32}
RUN = {"warmup_queries": 48, "drain_s": 20}
SECONDS = 1.0


def cells():
    return sorted(p.stem for p in (ROOT / "bench" / "cells").glob("*.json"))
