"""kernels layer: the least time the recurrent-step kernel could take for the
traced window's decode rows — every ACTIVE slot's state read and written once
a Mamba layer (the engine's ``lin_slot_steps`` counter;
benchmarks/flops_jamba.py against benchmarks/peaks.json) — over the device
time of the kernels NAMED ssm_step. The kernel runs on the vector unit and
moves two states a slot: bytes bind it."""
from benchmarks import flops, flops_jamba, kernel_seconds

KERNELS = ("ssm_step",)


def read(run):
    traced = run.samples.get("traced") or {}
    sizes, counters = traced.get("model_sizes"), traced.get("engine_counters")
    if run.trace is None or run.peaks is None or not counters \
            or "mamba_d_state" not in (sizes or {}):
        return None
    seconds = kernel_seconds.seconds(run.trace, *KERNELS)
    if seconds <= 0:
        return None
    f, b = flops_jamba.ssm_step_cost(sizes, counters["lin_slot_steps"])
    share, bound = flops.roofline_share_pct(f, b, seconds, run.peaks)
    run.say(f"note ssm_decode_roofline: bound by {bound}; {seconds!r} s in "
            f"{len(kernel_seconds.names(run.trace, *KERNELS))} kernels over "
            f"{counters['lin_slot_steps']} slot steps")
    return share
