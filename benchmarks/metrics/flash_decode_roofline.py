"""kernels layer: the least time the paged decode-attention kernel could
take for the traced run's prefill chunks and decode tokens together
(benchmarks/flops.py against benchmarks/peaks.json) over its device time."""
from benchmarks import flops

KERNELS = ('custom_call_target="tpu_custom_call"',)  # every Pallas kernel of these programs


def calls_of(finished, chunk):
    """(rows, keys) of every kernel call the finished requests needed."""
    for _, prompt, tokens in finished:
        n = len(prompt)
        for start in range(0, n, chunk):
            rows = min(chunk, n - start)
            yield rows, start + rows
        for j in range(1, len(tokens)):  # token j is decoded at position n+j-1
            yield 1, n + j


def read(run):
    traced = run.samples.get("traced")
    if run.trace is None or run.peaks is None or not traced:
        return None
    seconds = run.trace.op_seconds(*KERNELS)
    if seconds <= 0:
        return None
    f, b = flops.paged_attention_cost(
        run.sizes, calls_of(traced["finished"], traced["prefill_chunk"]))
    share, bound = flops.roofline_share_pct(f, b, seconds, run.peaks)
    run.say(f"note flash_decode_roofline: bound by {bound}; {seconds!r} s in "
            f"{run.trace.op_names(*KERNELS)}")
    return share
