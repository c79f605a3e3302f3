"""device layer: share of the traced window in which the device is idle
because the engine is empty and asleep until the next arrival (``serve.idle``):
what a rate below the knee leaves, not a fault."""
from benchmarks import span_reduce


def read(run):
    return span_reduce.metric(run, "idle_share", "engine_empty")
