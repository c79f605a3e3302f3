"""kernels layer: the least time the recurrent-step kernel could take for the
traced window's decode steps — every ACTIVE slot's state read and written
once a linear layer (the engine's ``lin_slot_steps`` counter;
benchmarks/flops_olmo_hybrid.py against benchmarks/peaks.json) — over the
device time of the kernels NAMED gdn_step."""
from benchmarks import flops, flops_olmo_hybrid, kernel_seconds

KERNELS = ("gdn_step",)


def read(run):
    traced = run.samples.get("traced") or {}
    sizes, counters = traced.get("model_sizes"), traced.get("engine_counters")
    if run.trace is None or run.peaks is None or sizes is None or not counters:
        return None
    seconds = kernel_seconds.seconds(run.trace, *KERNELS)
    if seconds <= 0:
        return None
    f, b = flops_olmo_hybrid.gdn_step_cost(sizes, counters["lin_slot_steps"])
    share, bound = flops.roofline_share_pct(f, b, seconds, run.peaks)
    run.say(f"note gdn_decode_roofline: bound by {bound}; {seconds!r} s in "
            f"{kernel_seconds.names(run.trace, *KERNELS)} over "
            f"{counters['lin_slot_steps']} slot steps")
    return share
