"""kvcache layer: mean over the traced window's engine steps of the share of
reserved KV positions not yet written (``serve.step``: 1 - ``kv_tokens`` /
``kv_reserved``): memory that full reservation at admission holds back."""
from benchmarks import span_reduce


def read(run):
    return span_reduce.metric(run, "kv_reserved_unused_share")
