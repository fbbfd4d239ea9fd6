"""Queries answered by the window's close over the window's length."""


import numpy as np


def read(run):
    return np.count_nonzero(run.win.col("t_done") <= run.win.t_end) \
        / run.seconds
