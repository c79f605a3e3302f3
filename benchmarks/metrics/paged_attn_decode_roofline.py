"""kernels layer: ``flash_decode_roofline`` for the paged-attention kernel
events inside ``decode_step`` program runs alone, against the least time the
decode calls of the traced window need."""
from benchmarks import span_reduce


def read(run):
    return span_reduce.paged_attn_roofline(run, "decode_step")
