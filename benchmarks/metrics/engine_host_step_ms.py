"""engine layer: median over the traced window's ``serve.step`` spans of the
span's duration less its ``*_fetch`` children (those block on the device): the
host time an engine step costs while the device could be starved."""
from benchmarks import span_reduce


def read(run):
    return span_reduce.metric(run, "engine_host_step_ms")
