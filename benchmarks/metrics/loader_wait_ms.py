"""trainer layer: median ``train.data_wait`` span of the traced steps: the
time ``DeviceLoader.__next__`` itself blocked, on the profiler's clock."""
from benchmarks import span_reduce


def read(run):
    return span_reduce.metric(run, "loader_wait_ms")
