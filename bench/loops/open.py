"""Open loop: independent users. Arrivals are a Poisson stream at the
mix's ``rate_qps`` (``data.poisson_arrivals``), one query of ``k``
results per request, submitted at its scheduled time whether or not
earlier ones are done; the arrivals that fell due are submitted
together before each tick. Each request is timed from its scheduled
arrival to its retirement, so a stall counts against every request it
delays. Copied from ``benchmarks/bench_load.py``
(``_open_loop_point_sched``)."""
import time

import numpy as np

from bench import data, drive


def plan(mix, seconds, seed, pool):
    due = data.poisson_arrivals(float(mix["rate_qps"]), seconds, seed)
    pool.fill(len(due))
    return due


def run(sched, pool, due, mix, seconds, *, traced=False, hooks=(),
        drain_s=drive.DRAIN_S):
    """Offer ``pool[i]`` at ``t0 + due[i]``; each ``(at, fn)`` of
    ``hooks`` is called once, ``at`` seconds into the window."""
    ann = drive.annotator(traced)
    k = int(mix["k"])
    t0 = time.monotonic()
    win = drive.Window(t0=t0, t_end=t0 + seconds)
    hooks = drive.Hooks([(t0 + at, fn) for at, fn in hooks])
    sched_t = t0 + due
    late = np.zeros(len(due))
    i, n = 0, len(due)
    while i < n:
        now = time.monotonic()
        hooks.poll(now)
        if sched_t[i] <= now:
            with ann("bench.submit"):
                while i < n and sched_t[i] <= now:
                    rid = sched.submit(pool[i], k=k, rid=i,
                                       t_sched=float(sched_t[i]))
                    late[i] = now - sched_t[i]
                    win.shed += rid is None
                    i += 1
        if sched.in_flight or sched.queue_depth:
            drive.tick(sched, win, ann)
        elif i < n:
            with ann("bench.idle_wait"):
                time.sleep(min(max(sched_t[i] - time.monotonic(), 0.0),
                               5e-4))
    win.submitted = n
    win.late_s = late
    while time.monotonic() < win.t_end and (sched.in_flight
                                            or sched.queue_depth):
        hooks.poll(time.monotonic())
        drive.tick(sched, win, ann)
    hooks.poll(float("inf"))
    drive.drain(sched, win, ann, drain_s)
    return win
