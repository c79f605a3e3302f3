"""kernels layer: ``flash_decode_roofline`` for the paged-attention kernel
events inside ``prefill_chunk`` program runs alone, against the least time the
prefill chunks of the traced window need."""
from benchmarks import span_reduce


def read(run):
    return span_reduce.paged_attn_roofline(run, "prefill_chunk")
