"""A kernel's share of its roofline over the traced slice: the least
time of its role's work at the chip's peaks (``work.py``,
``peaks.py``) over the kernel's summed device time in the trace."""
from __future__ import annotations

from bench import reduce_trace, work


def share(run, role: str, kernel: str):
    """Percent, or None where there is no device trace, the deployment
    has no such role, or the trace holds no time for ``kernel``, the
    name of the kernel's jitted wrapper (``reduce_trace.kernel_seconds``; a
    kernel taken off the path leaves its share silent; that is said on
    standard error with the operations the trace does hold)."""
    if run.trace is None or run.peaks is None:
        return None
    w = work.role_work(run.cfg, run.answered("steps"))
    if role not in w or not w[role][1]:
        return None
    t = reduce_trace.kernel_seconds(run.trace, kernel)
    if not t:
        top = list(run.trace["ops"])[:10]
        run.note(f"NO DEVICE TIME FOR {kernel} ({role}) among "
                 f"{len(run.trace['ops'])} device operations; its "
                 f"roofline share is left out. Largest operations: {top}")
        return None
    least, bound = work.least_seconds(*w[role], run.peaks)
    run.note(f"{kernel} ({role}): {w[role][0]:.6g} operations, "
             f"{w[role][1]:.6g} bytes, least {least * 1e3:.6f} ms, "
             f"bound by {bound}; device time {t * 1e3:.6f} ms")
    return 100.0 * least / t
