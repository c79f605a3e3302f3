"""engine layer: of the prefill chunks of the traced window's engine run (its
closing ``serve.counters``), the share whose program run also carried the next
token of at least one decoding slot (``EngineCounters.chunks_carrying_decode``):
one pass over the weights did the work of a chunk and a decode step. None for a
program without the counter (the parent of the PR that let a chunk carry the
decode rows)."""
from benchmarks import span_reduce


def read(run):
    spans = span_reduce.for_run(run)
    counters = spans.counters() if spans is not None else {}
    if "chunks_carrying_decode" not in counters:
        return None
    chunks = counters["prefill_chunks"]
    return 100.0 * counters["chunks_carrying_decode"] / chunks if chunks else None
