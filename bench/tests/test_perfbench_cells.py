"""Every cell file driven end to end on the CPU at a tiny size, without
the harness's look for a chip: set-up, warm-up, its traffic, the
comparison with the reference, and the metrics it reports."""
from __future__ import annotations

import time

import numpy as np
import pytest

from _tiny import CFG, MIX, RUN, SECONDS, cells
from bench import run, spec


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(cell, trace):
    res = run.measure(cell, 2**31 + 11, SECONDS, trace,
                      t_start=time.perf_counter(), cfg_over=CFG,
                      mix_over=MIX, **RUN)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    man = spec.manifest()
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in man[kind] if spec.applies(m, cell)}
    got = set(res["metrics"])
    if trace:
        # no device trace on the CPU: the trace readers find nothing
        assert not res["device"]["busy_s"]
        assert got == {m for m in want
                       if m.split(".")[0] in ("tick_ms",
                                              "steps_per_query")}
        assert "breakdown" in res
    else:
        assert got == want
        assert res["metrics"]["setup_s"]["value"] > 0
    for v in res["metrics"].values():
        assert v["value"] > 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)


@pytest.mark.parametrize("cell", cells())
def test_same_seed_same_inputs(cell):
    """The seed fixes the data, the pool and the arrivals."""
    from bench import deploy
    c = spec.cell(cell)
    cfg = {**spec.config(c["config"]), **CFG}
    mix = {**spec.mix(c), **MIX}
    a, b = (deploy.make_data(cfg, 5) for _ in range(2))
    assert (a == b).all()
    rows = np.arange(0, 9000, 7)
    qa, qb = (deploy.QueryPool(cfg, a, 5, 1)[rows] for _ in range(2))
    assert (qa == qb).all()
    assert not (deploy.QueryPool(cfg, a, 6, 1)[rows] == qa).all()
    pool = deploy.QueryPool(cfg, a, 5, 1)
    assert (pool[rows[5]] == qa[5]).all()
    loop = spec.loop(mix)
    pa, pb = (loop.plan(mix, 2.0, 2**31 + 5, pool) for _ in range(2))
    assert np.array_equal(pa, pb)
