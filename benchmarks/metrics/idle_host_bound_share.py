"""device layer: share of the traced window in which the device is idle, no
program is running on it, and the serving thread is inside a program span that
is not a fetch and not the empty engine's sleep: the part of
``device_idle_share`` that host code can remove."""
from benchmarks import span_reduce


def read(run):
    return span_reduce.metric(run, "idle_share", "host_bound")
