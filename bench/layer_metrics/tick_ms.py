"""Mean host time of one ``StreamScheduler.tick()`` (admit, step,
retire), on the benchmark's clock, over the ticks that started in the
slice read. Layer: serve."""


def read(run):
    ticks = run.tick_seconds()
    return 1e3 * sum(ticks) / len(ticks) if ticks else None
