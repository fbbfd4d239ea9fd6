"""Mean recall@10 of every answer of the window against the exact
top-10 (the comparison's own reading, ``reference.judge``)."""


def read(run):
    return run.checks["recall_at_10"]["value"]
