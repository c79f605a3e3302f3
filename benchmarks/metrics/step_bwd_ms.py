"""model layer: device op-milliseconds a step a chip in phase ``bwd``, every
section."""
from benchmarks import step_sections


def read(run):
    return step_sections.metric(run, phase="bwd")
