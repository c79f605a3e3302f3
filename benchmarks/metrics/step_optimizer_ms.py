"""trainer layer: device op-milliseconds a step a chip under ``sec_optimizer``
(clip, AdamW, the update)."""
from benchmarks import step_sections


def read(run):
    return step_sections.metric(run, ("optimizer",))
