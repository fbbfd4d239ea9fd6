"""The comparison that decides ``correct`` fails what it must: the
control (the reference one precision below the deployment's, in the
program's place), and a run whose timed path is broken underneath --
a step that returns its state unchanged, half of the slots left out,
an answer altered where it is produced. Each is driven on the CPU at a
tiny size, without the harness's look for a chip."""
from __future__ import annotations

import time

import jax
import pytest

from _tiny import CFG, MIX, RUN, SECONDS, cells
from bench import control, run

# enough load to fill every slot, and a short wait for what never comes
FAULT_MIX = {**MIX, "rate_qps": 300}
FAULT_RUN = {**RUN, "drain_s": 5}


@pytest.mark.parametrize("cell", cells())
def test_control_is_not_correct(cell):
    res = control.control(cell, 2**31 + 3, 200,
                          cfg_over={"n_points": 3000})
    assert not res["correct"]
    c = res["checks"]
    assert c["dist_gap"]["value"] > c["dist_gap"]["limit"]


@pytest.mark.parametrize("metric", ["l2", "cosine", "ip"])
def test_control_is_not_correct_under_each_metric(metric):
    """The control under the metric a deployment states: the gap of its
    bfloat16 distances to the float64 ones fails ``dist_gap``."""
    res = control.control("sift250k-pca.open", 2**31 + 5, 200,
                          cfg_over={"n_points": 3000, "metric": metric})
    assert not res["correct"]
    c = res["checks"]
    assert c["dist_gap"]["value"] > c["dist_gap"]["limit"]
    assert c["unanswered"]["value"] == 0 and c["bad_rows"]["value"] == 0


def _frozen_step(db, state, **kw):
    return state


def _half_step(orig):
    def step(db, state, **kw):
        h = state.done.shape[0] // 2
        part = jax.tree_util.tree_map(lambda a: a[:h], state)
        part = orig(db, part, **kw)
        return jax.tree_util.tree_map(lambda f, p: f.at[:h].set(p),
                                      state, part)
    return step


def _altered_answer(orig):
    def retire(self, span):
        out = orig(self, span)
        for c in out[:1]:
            c.ids = c.ids.copy()
            c.ids[-1] = (c.ids[-1] + 1) % 2000
        return out
    return retire


def _scaled_dist_h(orig):
    return jax.jit(lambda x, q: orig(x, q) * 1.001)


@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("fault", ["frozen_step", "half_slots",
                                   "altered_answer", "altered_dist"])
def test_broken_path_is_not_correct(cell, fault, monkeypatch,
                                    fresh_programs):
    from repro.core import search_jax as sj
    from repro.kernels import ops
    from repro.serve.scheduler import StreamScheduler
    if fault == "frozen_step":
        monkeypatch.setattr(sj, "_slot_step_impl", _frozen_step)
    elif fault == "half_slots":
        monkeypatch.setattr(sj, "_slot_step_impl",
                            _half_step(sj._slot_step_impl))
    elif fault == "altered_answer":
        monkeypatch.setattr(StreamScheduler, "_retire",
                            _altered_answer(StreamScheduler._retire))
    else:
        monkeypatch.setattr(ops, "dist_h", _scaled_dist_h(ops.dist_h))
    res = run.measure(cell, 17, SECONDS, False,
                      t_start=time.perf_counter(), cfg_over=CFG,
                      mix_over=FAULT_MIX, **FAULT_RUN)
    c = res["checks"]
    assert not res["correct"], c
    if fault in ("frozen_step", "half_slots"):
        assert c["unanswered"]["value"] > 0
    else:
        assert c["unanswered"]["value"] == 0
        assert c["dist_gap"]["value"] > c["dist_gap"]["limit"] \
            or c["bad_rows"]["value"] > 0
