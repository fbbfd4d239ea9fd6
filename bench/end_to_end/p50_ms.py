"""The 50th percentile of the latency of every request of the window,
from its scheduled arrival to its retirement."""
import numpy as np


def read(run):
    lat = run.win.col("latency_ms")
    return float(np.percentile(lat, 50)) if len(lat) else None
