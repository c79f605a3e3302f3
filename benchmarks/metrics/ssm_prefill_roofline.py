"""kernels layer: the least time the scan over a chunk could take for the
traced window's prefill calls (the engine's ``prefill_tokens`` and
``prefill_chunks`` counters; benchmarks/flops_jamba.py against
benchmarks/peaks.json: the recurrence's own FLOPs, u / Δ / B / C / y once a
row, the state once a call) over the device time of the kernels NAMED
ssm_chunk_fwd. The recurrence has no matrix form: the kernel walks a chunk's
positions one at a time on the vector unit, so its share of a roofline drawn
from the matrix unit's peak and the memory's reads low by construction."""
from benchmarks import flops, flops_jamba, kernel_seconds

KERNELS = ("ssm_chunk_fwd",)


def read(run):
    traced = run.samples.get("traced") or {}
    sizes, counters = traced.get("model_sizes"), traced.get("engine_counters")
    if run.trace is None or run.peaks is None or not counters \
            or "mamba_d_state" not in (sizes or {}):
        return None
    seconds = kernel_seconds.seconds(run.trace, *KERNELS)
    if seconds <= 0:
        return None
    f, b = flops_jamba.ssm_chunk_cost(
        sizes, counters["prefill_tokens"], counters["prefill_chunks"])
    share, bound = flops.roofline_share_pct(f, b, seconds, run.peaks)
    run.say(f"note ssm_prefill_roofline: bound by {bound}; {seconds!r} s in "
            f"{len(kernel_seconds.names(run.trace, *KERNELS))} kernels over "
            f"{counters['prefill_chunks']} calls")
    return share
