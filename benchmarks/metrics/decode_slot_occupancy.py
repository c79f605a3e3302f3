"""engine layer: decoding slots over all slots, summed over the traced
window's ``serve.decode`` spans (their ``active`` and ``slots`` attributes):
how full the fixed-shape decode batch runs."""
from benchmarks import span_reduce


def read(run):
    return span_reduce.metric(run, "decode_slot_occupancy")
