"""Device idle share over the traced slice: 1 - (union of device
operation intervals) / slice length. Layer: device."""


def read(run):
    tr = run.trace
    if tr is None or not tr["window_s"]:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
