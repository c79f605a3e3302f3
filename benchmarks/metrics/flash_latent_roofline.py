"""kernels layer: the least time the flash forward + backward kernels could
take for the traced steps at q/k width 192 and v width 128, over the six
blocks that run them (benchmarks/flops_joyai.py against
benchmarks/peaks.json) — over the device time of the kernels NAMED
flash_fwd / flash_bwd_dq / flash_bwd_dkv."""
from benchmarks import flops, flops_joyai, kernel_seconds

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(run):
    sizes = run.samples.get("model_sizes")
    if run.trace is None or run.peaks is None or sizes is None:
        return None
    seconds = kernel_seconds.seconds(run.trace, *KERNELS)
    if seconds <= 0:
        return None
    steps = run.samples["traced"]["steps"]
    f, b = flops_joyai.flash_latent_cost(
        sizes, run.mix["batch_size"], run.mix["seq_len"])
    share, bound = flops.roofline_share_pct(
        steps * f / run.chips, steps * b / run.chips, seconds, run.peaks)
    run.say(f"note flash_latent_roofline: bound by {bound}; {seconds!r} s in "
            f"{kernel_seconds.names(run.trace, *KERNELS)} over {steps} steps")
    return share
