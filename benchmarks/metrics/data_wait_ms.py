"""trainer layer: median host time a step waits in ``next(loader)``."""
from statistics import median


def read(run):
    waits = run.samples.get("data_wait_s")
    return 1e3 * median(waits) if waits else None
