"""model layer: device op-milliseconds a step a chip under ``sec_embed`` +
``sec_head_ce``, all phases (the lookup and its gradient, final norm, head,
cross-entropy; the prediction module's input and second pass)."""
from benchmarks import step_sections


def read(run):
    return step_sections.metric(run, ("embed", "head_ce"))
