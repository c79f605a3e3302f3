"""kernels layer: device time of the kernels named gdn_* (the chunked scan
and the recurrent step) over the traced window's busy time."""
from benchmarks import kernel_seconds


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    seconds = kernel_seconds.seconds(run.trace, "gdn_")
    return 100.0 * seconds / run.trace.busy_s if seconds > 0 else None
