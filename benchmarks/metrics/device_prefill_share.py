"""model layer: share of the traced window's device-busy time that lies inside
runs of the ``prefill_chunk`` program (the trace's ``XLA Modules`` line); the
rest is ``decode_step``."""
from benchmarks import span_reduce


def read(run):
    return span_reduce.metric(run, "device_program_share", "prefill_chunk")
