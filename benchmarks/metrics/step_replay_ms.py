"""model layer: device op-milliseconds a step a chip in phase ``replay`` — what
is recomputed to save memory: jax's rematted computation and the compiler's
own ``.remat`` clones."""
from benchmarks import step_sections


def read(run):
    return step_sections.metric(run, phase="replay")
