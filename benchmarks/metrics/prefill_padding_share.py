"""engine layer: padded positions over all positions of the traced window's
prefill chunks (``serve.prefill``: 1 - sum ``n_valid`` / sum ``chunk``): work
the fixed-shape prefill program does for nothing."""
from benchmarks import span_reduce


def read(run):
    return span_reduce.metric(run, "prefill_padding_share")
