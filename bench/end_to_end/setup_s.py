"""Seconds from the process's start to the window: data, index build,
the warm-up of every program the window runs, with its compiles."""


def read(run):
    return run.setup_s
