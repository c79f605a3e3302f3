"""kernels layer: device time of the kernels named ssm_* (the scan over a
chunk and the recurrent step) over the traced window's busy time."""
from benchmarks import kernel_seconds


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    seconds = kernel_seconds.seconds(run.trace, "ssm_")
    return 100.0 * seconds / run.trace.busy_s if seconds > 0 else None
