"""What every traffic generator shares. A mix is a data file of
parameters (``traffic/<mix>.json``, overridden by the cell's
``params``); its ``loop`` names the generator module under ``loops/``
that drives a ``StreamScheduler`` with it. A loop module has

* ``plan(mix, seconds, seed, pool)``: set-up before the window (arrival
  times, the queries it will offer made in advance); returns the plan;
* ``run(sched, pool, plan, mix, seconds, *, traced, hooks, drain_s)``:
  drives the window and returns its ``Window``.

Request ids are rows of the run's query pool (``deploy.QueryPool``);
the warm-up's ids start at ``WARM_RID0``. Every request submitted in
the window is waited for, up to ``drain_s`` seconds past its close;
what never comes back counts as unanswered. The host's own spans are
``jax.profiler.TraceAnnotation``s (``bench.submit``, ``bench.tick``,
``bench.absorb``, ``bench.idle_wait``) when the run is traced.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

# a minute past the window's close for answers still out
DRAIN_S = 60.0
WARM_RID0 = 1 << 40


# what a window keeps of each answer
ANSWER_COLUMNS = ("ids", "dists", "latency_ms", "steps", "t_done")


@dataclass
class Window:
    """What one measured window produced. Times are ``time.monotonic``
    seconds; ``answers`` maps a request id (its row in the query pool)
    to its row in ``rows``, which holds each answer's ``ANSWER_COLUMNS``
    (``t_done`` is its retirement time).

    The answers are kept as columns of arrays and numbers, which the
    garbage collector does not track, and not as the ``Completion``
    objects: a window's tens of thousands of live objects set off full
    collections inside the window, each a stall of the host that a
    server, which drops its answers, does not have."""
    t0: float
    t_end: float
    submitted: int = 0
    shed: int = 0
    answers: Dict[int, int] = field(default_factory=dict)
    rows: Dict[str, list] = field(
        default_factory=lambda: {c: [] for c in ANSWER_COLUMNS})
    ticks: List[tuple] = field(default_factory=list)   # (start, seconds)
    late_s: Optional[np.ndarray] = None                # open loop only
    compiles: int = 0

    @property
    def unanswered(self) -> int:
        return self.submitted - len(self.answers)

    def absorb(self, c, done: float) -> None:
        self.answers[c.rid] = len(self.rows["t_done"])
        for name, v in (("ids", c.ids), ("dists", c.dists),
                        ("latency_ms", float(c.latency_ms)),
                        ("steps", int(c.steps)), ("t_done", done)):
            self.rows[name].append(v)

    def col(self, name: str) -> np.ndarray:
        """A number column (``latency_ms``, ``steps``, ``t_done``) over
        the answered requests."""
        idx = np.fromiter(self.answers.values(), np.int64,
                          len(self.answers))
        return np.asarray(self.rows[name])[idx]


def annotator(traced: bool):
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class Hooks:
    """Calls each ``fn`` of ``[(at, fn), ...]`` once, in order, the
    first time ``poll`` sees its ``at`` passed."""

    def __init__(self, hooks):
        self.todo = sorted(hooks, key=lambda h: h[0])

    def poll(self, now: float) -> None:
        while self.todo and now >= self.todo[0][0]:
            self.todo.pop(0)[1]()


def tick(sched, win: Window, ann) -> None:
    t = time.monotonic()
    with ann("bench.tick"):
        out = sched.tick()
    dt = time.monotonic() - t
    win.ticks.append((t, dt))
    with ann("bench.absorb"):
        done = time.monotonic()
        for c in out:
            if c.rid < WARM_RID0:        # not a warm-up straggler
                win.absorb(c, done)


def drain(sched, win: Window, ann, drain_s: float) -> None:
    deadline = max(time.monotonic(), win.t_end) + drain_s
    while (sched.in_flight or sched.queue_depth) \
            and time.monotonic() < deadline:
        tick(sched, win, ann)


def warm_up(sched, queries: np.ndarray, k: int, *,
            limit_s: float) -> int:
    """Serve ``queries`` (request ids from ``WARM_RID0``) as one burst
    and drain them: every program the window runs has run once, and the
    step-budget telemetry is filled. Gives up after ``limit_s`` seconds
    and returns how many never came back."""
    deadline = time.monotonic() + limit_s
    for i, q in enumerate(queries):
        while not sched.has_capacity() and time.monotonic() < deadline:
            sched.tick()
        sched.submit(q, k=k, rid=WARM_RID0 + i)
    while (sched.in_flight or sched.queue_depth) \
            and time.monotonic() < deadline:
        sched.tick()
    return sched.in_flight + sched.queue_depth
