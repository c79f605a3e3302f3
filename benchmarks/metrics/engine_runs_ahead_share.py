"""engine layer: of the program runs of the traced window's engine run (prefill
chunks and decode runs, from its closing ``serve.counters``), the share that was
enqueued while the host had not yet read the result of the run before it
(``EngineCounters.runs_enqueued_ahead``): the device had its next program
before the host looked at this one's tokens. None for a program without the
counter (the parent of the PR that brought the lookahead loop)."""
from benchmarks import span_reduce


def read(run):
    spans = span_reduce.for_run(run)
    counters = spans.counters() if spans is not None else {}
    if "runs_enqueued_ahead" not in counters:
        return None
    runs = counters["prefill_chunks"] + counters["decode_steps"]
    return 100.0 * counters["runs_enqueued_ahead"] / runs if runs else None
