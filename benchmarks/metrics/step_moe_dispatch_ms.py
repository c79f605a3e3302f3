"""parallel layer: device op-milliseconds a step a chip under ``sec_router`` +
``sec_moe_dispatch``, all phases: the expert layer OUTSIDE its ``gmm_*`` kernels."""
from benchmarks import step_sections


def read(run):
    return step_sections.metric(run, ("router", "moe_dispatch"))
