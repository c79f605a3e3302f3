"""kernels layer: the least time the chunked-scan kernel could take for the
traced window's prefill calls (benchmarks/flops_olmo_hybrid.py against
benchmarks/peaks.json: the recurrence's own FLOPs, q/k/v/o once a row, the
state once a call) over the device time of the kernels NAMED gdn_chunk_fwd."""
from benchmarks import flops, flops_olmo_hybrid, kernel_seconds

KERNELS = ("gdn_chunk_fwd",)


def read(run):
    traced = run.samples.get("traced") or {}
    sizes = traced.get("model_sizes")
    if run.trace is None or run.peaks is None or sizes is None:
        return None
    seconds = kernel_seconds.seconds(run.trace, *KERNELS)
    if seconds <= 0:
        return None
    f, b = flops_olmo_hybrid.gdn_chunk_cost(
        sizes, flops_olmo_hybrid.prefill_rows(traced["finished"], traced["prefill_chunk"]))
    share, bound = flops.roofline_share_pct(f, b, seconds, run.peaks)
    run.say(f"note gdn_prefill_roofline: bound by {bound}; {seconds!r} s in "
            f"{kernel_seconds.names(run.trace, *KERNELS)}")
    return share
