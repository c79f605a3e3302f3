"""model layer: model FLOPs (benchmarks/flops.py, no recompute) of the
window's completed steps over the window, against chips x the bf16 peak."""
from benchmarks import flops


def read(run):
    s = run.samples
    if "tokens_per_step" not in s or run.peaks is None:
        return None
    per_step = flops.train_flops_per_step(
        run.sizes, run.mix["batch_size"], run.mix["seq_len"])
    rate = per_step * s["steps"] / s["elapsed_s"]
    return 100.0 * rate / (run.chips * run.peaks["flops_per_s"])
