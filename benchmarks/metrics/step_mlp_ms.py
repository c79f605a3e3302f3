"""model layer: device op-milliseconds a step a chip under ``sec_mlp``, all phases
(the dense MLP, the dense lead's, the shared expert)."""
from benchmarks import step_sections


def read(run):
    return step_sections.metric(run, ("mlp",))
