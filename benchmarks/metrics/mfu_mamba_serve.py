"""model layer: model FLOPs of the prompts prefilled and the output tokens
produced inside the window (benchmarks/flops_jamba.py: every layer's products,
causal attention in the two attention layers, the recurrence in the Mamba
ones, the head once an output token; no padding, no inactive slot) over the
window, against the chip's bf16 peak: the cell's share of the whole step."""
from benchmarks import flops_jamba as flops


def read(run):
    s = run.samples
    if run.peaks is None or not s.get("window_work") or "mamba_d_state" not in s.get(
            "model_sizes", {}):
        return None
    total = flops.serve_flops(s["model_sizes"], s["window_work"])
    return 100.0 * total / s["seconds"] / (run.chips * run.peaks["flops_per_s"])
