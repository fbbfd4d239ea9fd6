"""Mean layer-0 expansion steps per query (``Completion.steps``, an
exact count) over the queries retired in the slice read. Layer:
engine."""


def read(run):
    steps = run.answered("steps")
    return float(steps.mean()) if len(steps) else None
