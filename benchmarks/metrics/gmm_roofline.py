"""kernels layer: the least time the grouped-matmul kernels could take for
the traced steps' COUNTED routed rows (benchmarks/flops_smallthinker.py:
forward 3 products, backward dx 3 + dw 3; the held experts' weights once a
product plus rows in and out) over the device time of the kernels named
gmm_*."""
from benchmarks import flops, flops_smallthinker, kernel_seconds


def read(run):
    sizes = run.samples.get("model_sizes")
    counters = run.samples.get("traced", {}).get("counters")
    if run.trace is None or run.peaks is None or not counters:
        return None
    seconds = kernel_seconds.seconds(run.trace, "gmm_")
    if seconds <= 0:
        return None
    f, b = flops_smallthinker.gmm_cost(
        sizes, sum(c["moe_routed_here"] for c in counters))
    share, bound = flops.roofline_share_pct(
        f / run.chips, b / run.chips, seconds, run.peaks)
    run.say(f"note gmm_roofline: bound by {bound}; {seconds!r} s in "
            f"{kernel_seconds.names(run.trace, 'gmm_')} over {len(counters)} steps")
    return share
