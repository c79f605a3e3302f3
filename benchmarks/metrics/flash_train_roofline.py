"""kernels layer: the least time the flash-attention forward + backward
kernels could take for the traced steps (benchmarks/flops.py against
benchmarks/peaks.json, per chip) over their summed device time."""
from benchmarks import flops

KERNELS = ('custom_call_target="tpu_custom_call"',)  # every Pallas kernel of these programs


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds = run.trace.op_seconds(*KERNELS)
    if seconds <= 0:
        return None
    steps = run.samples["traced"]["steps"]
    f, b = flops.flash_train_cost(
        run.sizes, run.mix["batch_size"], run.mix["seq_len"])
    share, bound = flops.roofline_share_pct(
        steps * f / run.chips, steps * b / run.chips, seconds, run.peaks)
    run.say(f"note flash_train_roofline: bound by {bound}; {seconds!r} s in "
            f"{run.trace.op_names(*KERNELS)} over {steps} steps")
    return share
