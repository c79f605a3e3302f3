"""model layer: model FLOPs of the window's completed steps
(benchmarks/flops_joyai.py: no recompute, latent attention at its two
widths, both head passes, the expert term by each step's COUNTED choices
routed to held experts) over the window, against chips x the bf16 peak."""
from benchmarks import flops_joyai as flops


def read(run):
    s = run.samples
    if not s.get("counters") or run.peaks is None or "model_sizes" not in s:
        return None
    total = sum(
        flops.train_flops_per_step(s["model_sizes"], run.mix["batch_size"],
                                   run.mix["seq_len"], c["moe_routed_here"])
        for c in s["counters"])
    return 100.0 * total / s["elapsed_s"] / (run.chips * run.peaks["flops_per_s"])
