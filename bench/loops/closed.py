"""Closed loop: a batch client. The mix's ``outstanding`` requests of
``k`` results are kept in the service at all times; each completion is
replaced at once by the pool's next query."""
import time

from bench import drive


def plan(mix, seconds, seed, pool):
    pool.fill(int(mix["outstanding"]))
    return None


def run(sched, pool, _plan, mix, seconds, *, traced=False, hooks=(),
        drain_s=drive.DRAIN_S):
    """Keep ``outstanding`` requests in the service for ``seconds``;
    each ``(at, fn)`` of ``hooks`` is called once, ``at`` seconds into
    the window."""
    ann = drive.annotator(traced)
    k, target = int(mix["k"]), int(mix["outstanding"])
    t0 = time.monotonic()
    win = drive.Window(t0=t0, t_end=t0 + seconds)
    hooks = drive.Hooks([(t0 + at, fn) for at, fn in hooks])
    nxt = 0
    while True:
        now = time.monotonic()
        hooks.poll(now)
        if now >= win.t_end:
            break
        out = nxt - len(win.answers) - win.shed
        if out < target:
            with ann("bench.submit"):
                for _ in range(target - out):
                    win.shed += sched.submit(pool[nxt], k=k,
                                             rid=nxt) is None
                    nxt += 1
        drive.tick(sched, win, ann)
    hooks.poll(float("inf"))
    win.submitted = nxt
    drive.drain(sched, win, ann, drain_s)
    return win
